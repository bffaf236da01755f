"""Device time under named scopes, on a synthetic trace worked out by hand,
and the capture's own decoder against a capture of this JAX."""

import pytest

from bench.readers import trace_scope, xplane
from bench.readers.xplane import Event

MS = 1e6  # nanoseconds


def op(name, start, dur, path=None):
    return Event(name, start * MS, dur * MS, {"tf_op": path} if path else {})


P = "jit(prog)/"
# Two executions of ``prog`` (0-100 ms, 200-300 ms) and one of another
# program between them. In each: a flag fusion under check/flags, a resolve
# ``while`` that holds two gathers, a per-row sum directly under a vmap, and
# an operation under no scope.
PLANES = [
    ("/host:CPU", [("python3", [Event("ignored", 0.0, 500 * MS, {})])]),
    ("/device:TPU:0", [
        ("XLA Modules", [
            Event("jit_prog(11)", 0.0, 100 * MS, {}),
            Event("jit_other(7)", 120 * MS, 50 * MS, {}),
            Event("jit_prog(12)", 200 * MS, 100 * MS, {}),
        ]),
        ("XLA Ops", [
            op("%fusion.1", 0, 10, P + "jit(inner)/check/flags/gather:"),
            op("%while.2", 20, 40, P + "lz77_resolve/while:"),
            op("%gather.3", 25, 10, P + "lz77_resolve/while/body/gather:"),
            op("%gather.3", 40, 10, P + "lz77_resolve/while/body/gather:"),
            op("%reduce.4", 60, 5, P + "vmap(reduce)/reduce_sum:"),
            op("%copy.5", 70, 8),
            op("%fusion.9", 130, 30, "jit(other)/check/flags/gather:"),
            op("%fusion.1", 200, 14, P + "jit(inner)/check/flags/gather:"),
            op("%while.2", 220, 60, P + "lz77_resolve/while:"),
            op("%gather.3", 225, 10, P + "lz77_resolve/while/body/gather:"),
            op("%reduce.4", 285, 5, P + "vmap(reduce)/reduce_sum:"),
            op("%late.6", 400, 50, P + "check/flags/gather:"),
        ]),
    ]),
]


@pytest.mark.parametrize("scopes,expected", [
    ([], 100.0),                       # the executions themselves
    (["lz77_resolve"], 50.0),          # 40 and 60: while and body, once
    (["check"], 12.0),                 # 10 and 14
    (["check", "flags"], 12.0),        # under both: still counted once
    (["reduce"], 5.0),                 # vmap(reduce) is reduce
    (["check", "reduce"], 17.0),
])
def test_scoped_time_per_execution(scopes, expected):
    got = trace_scope.read_planes(PLANES, "prog", scopes)
    assert got == pytest.approx(expected)


def test_operations_outside_the_programs_executions_do_not_count():
    # jit_other's flag fusion (30 ms) and the late one (50 ms) are not in
    # ``prog``'s executions; ``other`` has one execution of its own.
    assert trace_scope.read_planes(PLANES, "other", ["check"]) == (
        pytest.approx(30.0))
    lines = dict(PLANES)["/device:TPU:0"]
    runs = trace_scope.executions(lines, "prog")
    times = trace_scope.scoped_self_times(lines, runs, ["check"])
    assert times[(0, None)] == pytest.approx((40 + 5 + 8) * MS)
    assert sum(times.values()) == pytest.approx((63 + 79) * MS)


def test_nothing_to_read_is_none():
    assert trace_scope.read_planes(PLANES, "absent", ["check"]) is None
    # The program runs, but nothing in it is under such a scope.
    assert trace_scope.read_planes(PLANES, "prog", ["assemble"]) is None
    assert trace_scope.read_planes(PLANES[:1], "prog", []) is None
    assert trace_scope.read({"program": "prog"}, {"profile": None}) is None


def test_a_cut_execution_does_not_move_the_median():
    cut = [(name, [(ln, list(ev)) for ln, ev in lines])
           for name, lines in PLANES]
    lines = dict(cut)["/device:TPU:0"]
    dict(lines)["XLA Modules"].append(Event("jit_prog(11)", 500 * MS,
                                            3 * MS, {}))
    dict(lines)["XLA Ops"].append(
        op("%fusion.1", 500, 3, P + "check/flags/gather:"))
    assert trace_scope.read_planes(cut, "prog", ["check"]) == (
        pytest.approx(10.0))


def test_the_decoder_reads_a_capture_of_this_jax(tmp_path):
    """Every plane, line and event ``ProfileData`` sees, with the stats of
    an annotation; and no device plane reads as nothing."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("check.window", window=3, note="x"):
        jnp.arange(1024).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    planes = xplane.load(path)
    theirs = {
        (plane.name, line.name): [
            (e.name, round(e.start_ns), round(e.duration_ns))
            for e in line.events]
        for plane in ProfileData.from_file(str(path)).planes
        for line in plane.lines}
    mine = {(plane, line): [(e.name, round(e.start_ns),
                             round(e.duration_ns)) for e in events]
            for plane, lines in planes for line, events in lines}
    assert mine == theirs and any(mine.values())
    (found,) = [e for _p, lines in planes for _l, events in lines
                for e in events if e.name == "check.window"]
    assert found.stats == {"window": 3, "note": "x"}
    sources = {"profile": {"file": str(path)}}
    assert trace_scope.read(
        {"program": "count_window_tokens", "scopes": ["check"]},
        sources) is None
