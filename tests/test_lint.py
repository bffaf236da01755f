"""The project-native static-analysis suite (``spark-bam-tpu lint``).

Three layers of coverage (docs/static-analysis.md):

1. per-rule fixtures — a MUST-trigger snippet and a near-miss MUST-NOT
   snippet for each registered rule, driven through ``lint_source``;
2. suppression mechanics — inline allows, the justified baseline,
   stale-entry reporting, content-addressed keys surviving line shifts;
3. the gate itself — the whole repo lints clean against the committed
   baseline, and injecting one canonical violation per rule fails it.

Plus regressions for the real findings this suite surfaced (corrupt
B-tag blobs in cram/bam_bridge.py, the unlocked ``Batcher.tick_s``
write), and the ``slow``-marked runtime lock-order harness that backs
the static ``shared-state`` pass with observed happens-before evidence.
"""

import json
import os
import struct
import threading
import time

import pytest

from spark_bam_tpu.analysis import (
    RULES,
    Baseline,
    Severity,
    lint_source,
    run_lint,
)
from spark_bam_tpu.analysis.findings import finding_key
from spark_bam_tpu.analysis.runtime_sync import LockOrderRecorder

pytestmark = pytest.mark.lint

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASELINE = os.path.join(REPO_ROOT, "lint-baseline.json")


def _findings(rel_path, source, rule_id):
    return [f for f in lint_source(rel_path, source) if f.rule == rule_id]


# ------------------------------------------------------------ jit-purity

JIT_TRIGGER = """\
import jax

@jax.jit
def count(window, n):
    if n > 0:                       # traced value in a Python branch
        return window.sum()
    return window.max()
"""

JIT_NEARMISS = """\
import jax

@jax.jit
def count(window, n=4):
    if window.shape[0] > 8:         # shapes are static at trace time
        return window.sum()
    if n > 2:                       # param with literal default: config
        return window.max()
    if window is None:              # host-level sentinel test
        return None
    return window.min()
"""


def test_jit_purity_triggers_on_traced_branch():
    found = _findings("tpu/fixture.py", JIT_TRIGGER, "jit-purity")
    assert found and found[0].severity == Severity.P1
    assert "n" in found[0].message


def test_jit_purity_ignores_shape_static_and_sentinel():
    assert _findings("tpu/fixture.py", JIT_NEARMISS, "jit-purity") == []


def test_jit_purity_flags_nonliteral_static_argnums():
    src = (
        "import jax\n"
        "def make(idx):\n"
        "    return jax.jit(step, static_argnums=idx)\n"
    )
    found = _findings("parallel/fixture.py", src, "jit-purity")
    assert found and "static_arg" in found[0].message


def test_jit_purity_out_of_scope_module_is_skipped():
    assert _findings("serve/fixture.py", JIT_TRIGGER, "jit-purity") == []


# -------------------------------------------------------- blocking-async

ASYNC_TRIGGER = """\
import time

async def handle(conn):
    time.sleep(0.1)                 # stalls the whole accept loop
    return conn
"""

ASYNC_NEARMISS = """\
import asyncio
import time

async def handle(conn, loop):
    await asyncio.sleep(0.1)
    def work():                     # run_in_executor target: fine
        time.sleep(0.1)
    return await loop.run_in_executor(None, work)
"""


def test_blocking_async_triggers_on_time_sleep():
    found = _findings("fabric/fixture.py", ASYNC_TRIGGER, "blocking-async")
    assert found and found[0].severity == Severity.P1
    assert "time.sleep" in found[0].message


def test_blocking_async_ignores_await_and_executor_targets():
    assert _findings("serve/fixture.py", ASYNC_NEARMISS, "blocking-async") == []


# -------------------------------------------------------- guard-boundary

GUARD_TRIGGER = """\
import struct

def parse(raw):
    return struct.unpack("<i", raw[:4])[0]
"""

GUARD_NEARMISS = """\
import struct

from spark_bam_tpu.core.guard import TruncatedInput

def parse(raw):
    if len(raw) < 4:
        raise TruncatedInput("need 4 bytes")
    return struct.unpack("<i", raw[:4])[0]

def parse_wrapped(raw):
    try:
        return struct.unpack("<q", raw[:8])[0]
    except struct.error as e:
        raise TruncatedInput(str(e)) from e
"""

GUARD_FEEDER = """\
import struct

from spark_bam_tpu.core.guard import TruncatedInput

class Reader:
    def take(self, n):
        if self.off + n > len(self.data):
            raise TruncatedInput("short read")
        out = self.data[self.off:self.off + n]
        self.off += n
        return out

    def unpack(self, fmt):
        return struct.unpack(fmt, self.take(struct.calcsize(fmt)))
"""


def test_guard_boundary_triggers_on_bare_unpack():
    found = _findings("bam/fixture.py", GUARD_TRIGGER, "guard-boundary")
    assert found and found[0].severity == Severity.P1


def test_guard_boundary_accepts_validate_and_catch_idioms():
    assert _findings("cram/fixture.py", GUARD_NEARMISS, "guard-boundary") == []


def test_guard_boundary_accepts_guarded_feeder():
    assert _findings("sbi/fixture.py", GUARD_FEEDER, "guard-boundary") == []


# --------------------------------------------------------- shared-state

STATE_TRIGGER = """\
import threading

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self.rate = 1.0
        self._thread = threading.Thread(target=self._loop)

    def _loop(self):
        while True:
            r = self.rate

    def set_rate(self, r):
        self.rate = r               # foreign-domain write, no lock
"""

STATE_NEARMISS = """\
import threading

class Pump:
    def __init__(self):
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self.rate = 1.0
        self._thread = threading.Thread(target=self._loop)

    def _loop(self):
        while not self._stop.is_set():
            with self._lock:
                r = self.rate

    def set_rate(self, r):
        with self._lock:
            self.rate = r

    def stop(self):
        self._stop.set()            # Events ARE the synchronization
"""


def test_shared_state_triggers_on_unlocked_cross_thread_write():
    found = _findings("serve/fixture.py", STATE_TRIGGER, "shared-state")
    assert found and found[0].severity == Severity.P1
    assert "rate" in found[0].message
    assert "_lock" in (found[0].hint or "")


def test_shared_state_ignores_locked_writes_and_events():
    assert _findings("serve/fixture.py", STATE_NEARMISS, "shared-state") == []


# --------------------------------------------------------- obs-contract

OBS_TRIGGER = """\
from spark_bam_tpu import obs

def tick():
    obs.count("serve.totally_unregistered")
"""

OBS_NEARMISS = """\
from spark_bam_tpu import obs

def tick(r):
    obs.count("serve.batches")
    r.count(4, "blocks", 16)        # not the obs module: out of scope
"""


def test_obs_contract_triggers_on_unregistered_name():
    found = _findings("serve/fixture.py", OBS_TRIGGER, "obs-contract")
    assert found and "not in the registered catalog" in found[0].message


def test_obs_contract_ignores_registered_and_foreign_receivers():
    assert _findings("serve/fixture.py", OBS_NEARMISS, "obs-contract") == []


def test_obs_contract_dynamic_name_severity_split():
    bounded = (
        "from spark_bam_tpu import obs\n"
        "def f(name):\n"
        "    obs.count(f\"serve.{name}\")\n"
    )
    unbounded = (
        "from spark_bam_tpu import obs\n"
        "def f(name):\n"
        "    obs.count(f\"{name}.total\")\n"
    )
    b = _findings("serve/fixture.py", bounded, "obs-contract")
    u = _findings("serve/fixture.py", unbounded, "obs-contract")
    assert b and b[0].severity == Severity.P2
    assert u and u[0].severity == Severity.P1


# ------------------------------------------------- suppression mechanics


def test_inline_allow_suppresses_with_reason():
    src = OBS_TRIGGER.replace(
        'obs.count("serve.totally_unregistered")',
        'obs.count("serve.totally_unregistered")'
        "  # lint: allow[obs-contract] fixture",
    )
    assert _findings("serve/fixture.py", src, "obs-contract") == []


def test_inline_allow_without_reason_stays_live():
    src = OBS_TRIGGER.replace(
        'obs.count("serve.totally_unregistered")',
        'obs.count("serve.totally_unregistered")  # lint: allow[obs-contract]',
    )
    found = _findings("serve/fixture.py", src, "obs-contract")
    assert found and "no reason" in found[0].message


def test_inline_allow_comment_line_carries_past_continuations():
    src = OBS_TRIGGER.replace(
        '    obs.count("serve.totally_unregistered")',
        "    # lint: allow[obs-contract] the reason wraps onto a\n"
        "    # second comment line before the flagged statement\n"
        '    obs.count("serve.totally_unregistered")',
    )
    assert _findings("serve/fixture.py", src, "obs-contract") == []


def test_finding_keys_survive_line_shifts():
    base = lint_source("bam/fixture.py", GUARD_TRIGGER)
    shifted = lint_source("bam/fixture.py", "import os\n\n" + GUARD_TRIGGER)
    assert base and shifted
    assert base[0].key == shifted[0].key
    assert base[0].line != shifted[0].line


def test_finding_key_distinguishes_identical_lines():
    assert finding_key("r", "x = 1", 0) != finding_key("r", "x = 1", 1)


def test_baseline_requires_justification(tmp_path):
    bad = tmp_path / "serve" / "bad.py"
    bad.parent.mkdir()
    bad.write_text(OBS_TRIGGER)
    rep = run_lint(paths=[str(tmp_path)])
    assert len(rep.failing) == 1
    f = rep.failing[0]
    entry = {"rule": f.rule, "path": f.path, "key": f.key}

    silent = Baseline([dict(entry, justification="")])
    rep2 = run_lint(paths=[str(tmp_path)], baseline=silent)
    assert len(rep2.failing) == 1   # unjustified entry does not suppress

    justified = Baseline([dict(entry, justification="fixture")])
    rep3 = run_lint(paths=[str(tmp_path)], baseline=justified)
    assert rep3.ok and len(rep3.suppressed) == 1


def test_baseline_stale_entry_fails_the_gate(tmp_path):
    clean = tmp_path / "serve" / "clean.py"
    clean.parent.mkdir()
    clean.write_text("x = 1\n")
    stale = Baseline([{
        "rule": "obs-contract", "path": "serve/clean.py",
        "key": "obs-contract:deadbeef:0", "justification": "long fixed",
    }])
    # Stale entries only fail a FULL-scope run (root=...): a --rules or
    # paths subset never visits the other entries.
    rep = run_lint(root=str(tmp_path), baseline=stale)
    assert not rep.ok and len(rep.stale_baseline) == 1
    rep2 = run_lint(root=str(tmp_path), rule_ids=["obs-contract"],
                    baseline=stale)
    assert rep2.stale_baseline == []


def test_baseline_write_round_trip(tmp_path):
    bad = tmp_path / "serve" / "bad.py"
    bad.parent.mkdir()
    bad.write_text(OBS_TRIGGER)
    rep = run_lint(paths=[str(tmp_path)])
    path = tmp_path / "baseline.json"
    n = Baseline.write(str(path), rep.findings, "bootstrap fixture")
    assert n == 1
    rep2 = run_lint(paths=[str(tmp_path)], baseline=str(path))
    assert rep2.ok


def test_unknown_rule_id_is_an_error():
    with pytest.raises(ValueError, match="unknown rule"):
        run_lint(rule_ids=["no-such-rule"])


# ------------------------------------------------------------- the gate

CANONICAL_VIOLATIONS = {
    "jit-purity": ("tpu/injected.py", JIT_TRIGGER),
    "blocking-async": ("fabric/injected.py", ASYNC_TRIGGER),
    "guard-boundary": ("bam/injected.py", GUARD_TRIGGER),
    "shared-state": ("serve/injected.py", STATE_TRIGGER),
    "obs-contract": ("serve/injected_obs.py", OBS_TRIGGER),
}


def test_all_registered_rules_have_fixture_coverage():
    assert set(CANONICAL_VIOLATIONS) == set(RULES)


def test_whole_repo_lints_clean_against_committed_baseline():
    rep = run_lint(baseline=BASELINE)
    assert rep.errors == []
    assert rep.stale_baseline == []
    assert rep.failing == [], "\n".join(f.render() for f in rep.failing)
    # Every committed suppression carries a justification by construction
    # (unjustified entries never index), and none is stale.
    assert all(f.justification for f in rep.suppressed)


@pytest.mark.parametrize("rule_id", sorted(CANONICAL_VIOLATIONS))
def test_injected_violation_fails_the_gate(rule_id, tmp_path):
    rel, src = CANONICAL_VIOLATIONS[rule_id]
    target = tmp_path / rel
    target.parent.mkdir(parents=True)
    target.write_text(src)
    rep = run_lint(paths=[str(tmp_path)], baseline=BASELINE)
    assert not rep.ok
    assert any(f.rule == rule_id for f in rep.failing)


# ------------------------------------------------------------------ CLI


def test_cli_lint_exits_zero_on_clean_repo(capsys):
    from spark_bam_tpu.cli.main import main

    assert main(["lint"]) == 0
    out = capsys.readouterr().out
    assert "0 failing" in out


def test_cli_lint_fails_and_writes_artifact_on_violation(tmp_path, capsys):
    from spark_bam_tpu.cli.main import main

    bad = tmp_path / "serve" / "bad.py"
    bad.parent.mkdir()
    bad.write_text(OBS_TRIGGER)
    artifact = tmp_path / "findings.json"
    rc = main(["lint", str(tmp_path), "--no-baseline",
               "--json", str(artifact)])
    assert rc == 1
    data = json.loads(artifact.read_text())
    assert data["ok"] is False
    assert any(f["rule"] == "obs-contract" for f in data["findings"])


def test_cli_lint_unknown_rule_is_usage_error(capsys):
    from spark_bam_tpu.cli.main import main

    assert main(["lint", "--rules", "no-such-rule"]) == 2


# ------------------------------------------------- surfaced-bug regressions


def _tag(tag, typ, payload):
    return tag + typ + payload


def test_split_tags_round_trip_still_works():
    from spark_bam_tpu.cram.bam_bridge import join_tags, split_tags

    raw = (
        _tag(b"NM", b"i", struct.pack("<i", 3))
        + _tag(b"RG", b"Z", b"grp1\x00")
        + _tag(b"BC", b"B", b"c" + struct.pack("<i", 2) + b"\x01\x02")
    )
    entries = split_tags(raw)
    assert [e[0] for e in entries] == [b"NM", b"RG", b"BC"]
    assert join_tags(entries) == raw


@pytest.mark.parametrize("raw", [
    _tag(b"NM", b"i", b"\x01\x02"),                      # fixed value cut
    _tag(b"RG", b"Z", b"no-terminator"),                 # NUL never comes
    _tag(b"BC", b"B", b"c"),                             # B header cut
    _tag(b"BC", b"B", b"c" + struct.pack("<i", 99)),     # payload missing
])
def test_split_tags_truncation_raises_typed(raw):
    from spark_bam_tpu.core.guard import TruncatedInput
    from spark_bam_tpu.cram.bam_bridge import split_tags

    with pytest.raises(TruncatedInput):
        split_tags(raw)


@pytest.mark.parametrize("raw", [
    _tag(b"BC", b"B", b"q" + struct.pack("<i", 1) + b"\x00"),   # subtype
    _tag(b"BC", b"B", b"c" + struct.pack("<i", -5)),            # negative n
    _tag(b"XX", b"?", b""),                                     # type char
])
def test_split_tags_structural_damage_raises_typed(raw):
    from spark_bam_tpu.core.guard import StructurallyInvalid
    from spark_bam_tpu.cram.bam_bridge import split_tags

    with pytest.raises(StructurallyInvalid):
        split_tags(raw)


class _FakeSteps:
    """Just enough of MeshSteps for a host-only Batcher test."""

    class mesh:
        class devices:
            size = 1

    @staticmethod
    def put(x):
        return x

    def serve_step(self, **kw):
        import numpy as np

        def step(ws, ns, eofs, los, owns, lens, ncs):
            return np.zeros((ws.shape[0], 4), dtype=np.int32)

        return step


def test_batcher_tick_retarget_is_synchronized():
    from spark_bam_tpu.serve.batcher import Batcher, RowTask
    import numpy as np

    b = Batcher(_FakeSteps(), width=32, batch_rows=2, tick_ms=1.0)
    try:
        stop = threading.Event()

        def hammer(lo, hi):
            v = lo
            while not stop.is_set():
                b.set_tick_ms(v)
                v = lo if v >= hi else v + 1

        threads = [threading.Thread(target=hammer, args=(1, 5)),
                   threading.Thread(target=hammer, args=(5, 9))]
        for t in threads:
            t.start()
        futures = []
        for _ in range(16):
            task = RowTask(np.zeros(32, np.uint8), 0, False, 0, 0,
                           np.zeros(4, np.int32), 1)
            futures.append(b.submit(task))
        for f in futures:
            assert f.result(timeout=10) == (0, 0)
        stop.set()
        for t in threads:
            t.join(timeout=5)
        final = b.set_tick_ms(7.0)
        assert final == 7.0 and b.tick_s == pytest.approx(0.007)
    finally:
        b.close()


# -------------------------------------------- runtime lock-order harness


@pytest.mark.slow
def test_lock_order_recorder_flags_inversion():
    """The recorder flags an a→b / b→a order cycle even when the run
    never actually interleaved into a deadlock — the threads take the
    inverted orders strictly one after the other."""
    rec = LockOrderRecorder()
    a = rec.wrap(threading.Lock(), "a")
    b = rec.wrap(threading.Lock(), "b")

    def ab():
        with a:
            with b:
                pass

    def ba():
        with b:
            with a:
                pass

    t1 = threading.Thread(target=ab, name="t-ab")
    t1.start(); t1.join(10)
    t2 = threading.Thread(target=ba, name="t-ba")
    t2.start(); t2.join(10)
    cycles = rec.cycles()
    assert cycles and any({"a", "b"} <= set(c) for c in cycles)
    assert rec.threads_touching("a") >= {"t-ab", "t-ba"}


@pytest.mark.slow
def test_lock_order_recorder_clean_on_consistent_order():
    rec = LockOrderRecorder()
    outer = rec.wrap(threading.Lock(), "outer")
    inner = rec.wrap(threading.Lock(), "inner")

    def work():
        for _ in range(200):
            with outer:
                with inner:
                    pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    assert rec.cycles() == []
    assert rec.acquisitions["outer"] == 800


@pytest.mark.slow
def test_batcher_seam_happens_before_under_load(monkeypatch):
    """Observed-evidence twin of the static shared-state pass: wrap the
    Batcher's condition lock and prove both the tick thread and foreign
    mutator threads acquire it (the happens-before edge the PR's
    ``set_tick_ms`` fix introduced)."""
    from spark_bam_tpu.serve.batcher import Batcher

    rec = LockOrderRecorder()
    real_condition = threading.Condition

    def traced_condition(lock=None):
        # Bare Condition() is the Batcher's seam lock; Event/others pass
        # their own lock and stay untraced.
        if lock is None:
            return real_condition(rec.wrap(threading.Lock(), "cond"))
        return real_condition(lock)

    monkeypatch.setattr(threading, "Condition", traced_condition)
    b = Batcher(_FakeSteps(), width=32, batch_rows=2, tick_ms=1.0)
    monkeypatch.undo()
    try:

        def mutate():
            for i in range(50):
                b.set_tick_ms(1.0 + (i % 5))
                b.set_batch_rows(1 + (i % 3))

        threads = [threading.Thread(target=mutate, name=f"mut-{i}")
                   for i in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        time.sleep(0.2)             # a few empty batcher wakeups
        touching = rec.threads_touching("cond")
        assert "serve-batcher" in touching
        assert {f"mut-{i}" for i in range(3)} <= touching
        assert rec.cycles() == []
    finally:
        b.close()
