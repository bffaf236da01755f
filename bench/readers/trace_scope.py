"""Device time of one jitted program, whole or under ``jax.named_scope``s.

From the capture of the profiled slice (``sources["profile"]["file"]``), on
the device's clock. The program's executions are the events of the device
plane's ``XLA Modules`` line named ``jit_<program>(<fingerprint>)`` (every
fingerprint: one per compiled shape). With ``"scopes": []`` the value is the
median duration of those executions. With scopes it is the self time of the
``XLA Ops`` events that lie inside an execution and whose name path (the
``tf_op`` stat of the event's metadata,
``jit(count_window)/check/while/body/flags/gather:``) has one of
the scopes among its components (``vmap(reduce)`` counts as ``reduce``),
summed per execution; the value is the
median over the executions (one cut by an edge of the slice does not move
it). An operation under two of the scopes counts once. Self time is an
event's duration less the events nested in it (a ``while`` and its body), as
``trace_reduce`` takes it. Milliseconds.

Nothing to read gives None: no capture, no execution of the program in it,
or not one operation under any of the scopes (a program without the names).
"""

from __future__ import annotations

import re
import statistics
from bisect import bisect_right
from collections import defaultdict

from bench import trace_reduce
from bench.readers import xplane

MODULES_LINE = "XLA Modules"


_WRAPPED = re.compile(r"^(?:\w+\()+(.*?)\)+$")


def scope_of(path, scopes) -> str | None:
    """The first of ``scopes`` among the components of a name path. A
    transformation wraps the component it is applied under
    (``vmap(reduce)``, ``jit(count_window)``): the wrapping is taken off."""
    if not path:
        return None
    parts = {_WRAPPED.sub(r"\1", part)
             for part in str(path).rstrip(":").split("/")}
    return next((s for s in scopes if s in parts), None)


def executions(lines: list, program: str) -> list:
    """``[(start, end)]`` of the program's executions on one device plane."""
    prefix = f"jit_{program}("
    return sorted(
        (e.start_ns, e.start_ns + e.duration_ns)
        for name, events in lines if name == MODULES_LINE
        for e in events if e.name.startswith(prefix)
    )


def scoped_self_times(lines: list, runs: list, scopes) -> dict:
    """``{(execution index, scope): self time ns}`` of the operations that
    start inside one of ``runs``; scope None is under none of ``scopes``."""
    starts = [s for s, _ in runs]
    keyed = []
    for name, events in lines:
        if name != trace_reduce.OPS_LINE:
            continue
        for e in events:
            k = bisect_right(starts, e.start_ns) - 1
            if k >= 0 and e.start_ns < runs[k][1]:
                scope = scope_of(e.stats.get("tf_op"), scopes)
                keyed.append(((k, scope), e.start_ns, e.duration_ns))
    into: dict = defaultdict(float)
    trace_reduce._self_times(keyed, into)
    return into


def read_planes(planes: list, program: str, scopes) -> float | None:
    per_device = []
    for name, lines in planes:
        if not name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        runs = executions(lines, program)
        if not runs:
            continue
        if not scopes:
            per_device.append(statistics.median(e - s for s, e in runs))
        else:
            times = scoped_self_times(lines, runs, scopes)
            if all(scope is None for _run, scope in times):
                continue
            per_device.append(statistics.median(
                sum(v for (run, scope), v in times.items()
                    if run == k and scope is not None)
                for k in range(len(runs))))
    if not per_device:
        return None
    return sum(per_device) / len(per_device) / 1e6


def read(args: dict, sources: dict):
    profile = sources["profile"]
    if not profile or not profile.get("file"):
        return None
    return read_planes(xplane.load(profile["file"]), args["program"],
                       args.get("scopes", []))
