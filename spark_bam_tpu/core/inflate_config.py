"""Read-path inflate knobs: the ``Config.inflate`` string spec.

Same compact-spec pattern as ``deflate``/``faults``/``remote`` so the
frozen Config stays hashable and the ``SPARK_BAM_INFLATE`` env var and
``--inflate`` CLI plumbing work unchanged:

    tokenize=auto,kernel=auto,donate=on

``tokenize`` picks where the DEFLATE *entropy phase* runs for the
two-phase device inflate (tpu/inflate.py):

* ``host``   — the native ``sbt_tokenize_deflate`` decoder tokenizes on
  host and packed token planes ship to HBM (3 bytes per output byte),
  the pre-PR-15 behavior and the permanent correctness fallback.
* ``device`` — raw compressed payload bytes ship instead and the
  bit-reader kernel (tpu/tokenize_device.py / ``tokenize_pallas``)
  decodes Huffman tables and emits token planes on-device; malformed
  members demote per window, never produce wrong bytes.
* ``auto``   — ``host`` on every backend measured so far (see
  ``resolve_tokenize``): decided by measurement, not by capability.

``kernel`` pins the device tokenizer's engine: ``xla`` (the vmap form),
``pallas`` (grid lanes, VMEM rows — Mosaic refuses it for the v5e, so it
is explicit-only and raises what the compiler raised), or ``auto``
(``xla`` on every backend). ``donate`` controls ``jax.jit`` buffer
donation through the dispatch/materialize split so the inflate window
ring reuses HBM instead of re-allocating per window; ``off`` is a
debugging escape hatch only.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

TOKENIZE = ("host", "device", "auto")
KERNEL = ("xla", "pallas", "auto")
ONOFF = ("on", "off")


@dataclass(frozen=True)
class InflateConfig:
    tokenize: str = "auto"
    kernel: str = "auto"
    donate: str = "on"

    @property
    def donate_enabled(self) -> bool:
        return self.donate == "on"

    def resolve_tokenize(self) -> str:
        """Collapse ``auto`` to a concrete mode. The device
        bit-reader is a serial per-block symbol loop under vmap: the CPU
        backend serializes it, and on a TPU v5e one 24 MiB window took
        250 s against 12.4 s for the whole fused window with the native
        tokenizer (one smoke run, CHANGES.md PR 22). So ``auto`` is
        ``host`` on both; ``tokenize=device`` stays reachable by its
        explicit setting."""
        return "host" if self.tokenize == "auto" else self.tokenize

    @staticmethod
    @functools.lru_cache(maxsize=64)
    def parse(spec: str) -> "InflateConfig":
        """Parse a ``tokenize=...,kernel=...,donate=...`` spec ("" ⇒
        defaults). Raises ``ValueError`` on unknown keys/values — the
        CLI validates before any work starts, like every other knob."""
        kw: dict = {}
        for part in (spec or "").split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                # Bare token shorthand: "--inflate device" reads naturally.
                if part in TOKENIZE:
                    kw["tokenize"] = part
                    continue
                raise ValueError(
                    f"Bad inflate spec {spec!r}: {part!r} is not key=value"
                )
            key, value = part.split("=", 1)
            key, value = key.strip(), value.strip()
            if key == "tokenize":
                if value not in TOKENIZE:
                    raise ValueError(
                        f"Bad inflate tokenize {value!r}: expected "
                        f"{' | '.join(TOKENIZE)}"
                    )
                kw["tokenize"] = value
            elif key == "kernel":
                if value not in KERNEL:
                    raise ValueError(
                        f"Bad inflate kernel {value!r}: expected "
                        f"{' | '.join(KERNEL)}"
                    )
                kw["kernel"] = value
            elif key == "donate":
                if value not in ONOFF:
                    raise ValueError(
                        f"Bad inflate donate {value!r}: expected on | off"
                    )
                kw["donate"] = value
            else:
                raise ValueError(f"Unknown inflate key {key!r} in {spec!r}")
        return InflateConfig(**kw)
