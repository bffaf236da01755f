"""A cohort: ``files`` BAMs of one kind, each a sample of its own.

Every file is ``bench/generators/shortread.py``'s, from the configuration's
``params`` but for what tells samples apart: file ``k`` takes the ``k``-th
entry of each list under ``per_file`` (its contig, run and flowcell) and a
seed derived from ``seed`` and ``k``. ``target_bytes`` is the set's size, cut
evenly. File 0 is written to ``path`` and file ``k`` beside it
(``sibling(path, k)``); the files are written by worker processes side by
side, which import numpy and ``bench`` alone.

Returns file 0's index with the set's sums under the keys a run's
``generate`` line prints, and every file's index under ``files``.
"""

from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from pathlib import Path

import numpy as np

from bench.generators import shortread


def sibling(path, k: int) -> Path:
    """Where file ``k`` of the set at ``path`` lies (file 0 at ``path``)."""
    path = Path(path)
    return path if k == 0 else path.with_name(f"{path.stem}.{k}{path.suffix}")


def file_params(params: dict, k: int) -> dict:
    """``shortread``'s parameters of file ``k``."""
    out = {key: v for key, v in params.items()
           if key not in ("files", "per_file")}
    out.update({key: values[k] for key, values in params["per_file"].items()})
    return out


def file_seed(seed: int, k: int) -> int:
    return int(np.random.default_rng([int(seed), 0xC0407, k]).integers(2**31))


def _write(job: tuple) -> dict:
    params, seed, size, path = job
    index = shortread.generate(params, seed, size, path)
    index["path"] = str(path)
    return index


def generate(params: dict, seed: int, target_bytes: int, path) -> dict:
    n = int(params["files"])
    jobs = [(file_params(params, k), file_seed(seed, k), target_bytes // n,
             sibling(path, k)) for k in range(n)]
    # Spawned, not forked: the caller may hold the chip, and a worker needs
    # nothing of it.
    with ProcessPoolExecutor(
            min(n, os.cpu_count() or 1),
            mp_context=multiprocessing.get_context("spawn")) as pool:
        files = list(pool.map(_write, jobs))
    records = sum(len(f["record_starts"]) for f in files)
    flat = sum(f["uncompressed_bytes"] for f in files)
    compressed = sum(f["compressed_bytes"] for f in files)
    return {
        **files[0],
        "uncompressed_bytes": flat,
        "compressed_bytes": compressed,
        "ratio": flat / compressed,
        "record_bytes_mean": sum(
            f["record_bytes_mean"] * len(f["record_starts"]) for f in files
        ) / records,
        "files": files,
    }
