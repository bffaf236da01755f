"""``chip_smoke.py`` off the chip: it must refuse to compute, and its
``--allow-cpu`` rehearsal must drive every phase to equal answers while
still reporting failure."""

import json
import os
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def _smoke(*args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"   # the child must never reach for a chip
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    return proc, lines


def test_rehearsal_on_cpu_runs_every_phase_and_still_fails():
    proc, lines = _smoke("--allow-cpu", "--bytes", "8MB")
    assert proc.returncode != 0, proc.stderr[-2000:]
    last = lines[-1]
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert list(last) == ["ok", "device"]
    assert list(last["device"]) == ["platform", "kind", "count"]
    phases = {l["phase"]: l for l in lines[:-1]}
    assert list(phases) == [
        "start", "generate", "count", "count_again", "cli", "serve",
    ], proc.stderr[-2000:]
    for name, line in phases.items():
        assert line["ok"] is True, (name, line)
        assert not any(line.get("demotions", {}).values()), (name, line)
        for value in line.values():
            if isinstance(value, dict) and "equal" in value:
                assert value["equal"] is True, (name, line)
    assert phases["count"]["reads"]["got"] == \
        phases["generate"]["files"]["big"]["reads"]
    assert phases["count_again"]["compiles"] == 0
    assert phases["start"]["cache_dir"]
    assert phases["start"]["native"]["path"].endswith(".so")


def test_without_a_chip_it_fails_before_generating_anything(tmp_path):
    proc, lines = _smoke()
    assert proc.returncode != 0
    assert [l.get("phase") for l in lines] == ["error", None]
    assert "tpu" in lines[0]["error"]
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
