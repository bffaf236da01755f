"""spark-bam-tpu top: fleet telemetry view (one-shot or ``--watch``).

Scrapes the ``telemetry`` op from a serve worker or fabric router and
renders the operator's glance view: per-worker health, queue depth,
per-op p50/p99, the host/H2D/device ms split the inflate attribution
gauges carry, the host as the registry's witness saw it (stops, the
worst one, the host's pace, the collector's pauses), SLO burn rates +
firing alerts, per-op/per-tenant cost
rollups, latency exemplars (trace ids of the slowest kept traces —
feed them to ``metrics-report`` to see the offending tree), and the
router's autoscale move ledger with each move's cited reason. Point it
at the same address clients use — the op is an admin op, so it bypasses
admission control and works mid-overload. ``--watch`` re-scrapes every
``--interval`` seconds (Ctrl-C to stop).
"""

from __future__ import annotations

import sys
import time

from spark_bam_tpu.cli.output import Printer


def _ms(v) -> str:
    return "-" if v is None else f"{float(v):.1f}"


def _hd_split(snapshot) -> str:
    """``h2d/dev`` last-window ms from the attribution gauges."""
    vals = {}
    for g in (snapshot or {}).get("gauges", []):
        if g.get("name") in ("inflate.h2d_ms", "inflate.device_ms"):
            vals[g["name"].rsplit(".", 1)[1]] = g.get("value")
    if not vals:
        return "-"
    return "/".join(_ms(vals.get(k)) for k in ("h2d_ms", "device_ms"))


def _host_line(p: Printer, snapshot: "dict | None", indent: str = "") -> None:
    """``host: stops <n> worst <ms> pace p50/p99 <µs> gc <ms>`` from the
    witness's series (obs/witness.py): wakes 40 ms late or more and the
    latest of all, the timed unit of work, the collector's recorded
    pauses summed. Nothing until the daemon's first request started the
    witness."""
    from spark_bam_tpu.obs.timeseries import _nearest_rank

    snap = snapshot or {}
    hists = {h["name"]: h for h in snap.get("hists", []) if h.get("count")}
    late = hists.get("host.overshoot_ms")
    if late is None:
        return
    stops = sum(c["value"] for c in snap.get("counters", [])
                if c["name"] == "host.stops")
    pace = sorted(hists.get("host.pace_us", {}).get("values", []))
    gc_ms = hists.get("host.gc", {}).get("sum", 0.0)
    p.echo(
        f"{indent}host: stops {stops} worst {_ms(late['max'])}ms "
        f"pace p50/p99 {_ms(_nearest_rank(pace, 0.5))}/"
        f"{_ms(_nearest_rank(pace, 0.99))}us gc {_ms(gc_ms)}ms"
    )


def _slo_lines(p: Printer, slo: "dict | None", indent: str = "") -> None:
    """Per-objective burn rates + the firing set (obs/slo.py status)."""
    if not slo or not slo.get("objectives"):
        return
    for st in slo["objectives"]:
        if not isinstance(st, dict):
            continue
        mark = "FIRING" if st.get("firing") else "ok"
        p.echo(
            f"{indent}slo {st.get('objective')}: "
            f"burn={st.get('burn_fast')}x/{st.get('burn_slow')}x "
            f"value={st.get('value_fast')} [{mark}]"
        )


def _accounting_lines(p: Printer, acc: "dict | None",
                      indent: str = "") -> None:
    """Per-tenant cost rollups (obs/account.py snapshot)."""
    tenants = (acc or {}).get("tenants") or {}
    if not tenants:
        return
    for tenant, a in sorted(tenants.items()):
        p.echo(
            f"{indent}tenant {tenant}: n={a.get('requests', 0)} "
            f"queue={_ms(a.get('queue_ms'))}ms "
            f"host={_ms(a.get('host_ms'))}ms "
            f"dev={_ms(a.get('device_ms'))}ms "
            f"h2d={a.get('h2d_bytes', 0)}B "
            f"out={a.get('bytes_served', 0)}B"
        )


def _exemplar_lines(p: Printer, snapshot: "dict | None",
                    indent: str = "") -> None:
    """Latency exemplars: trace ids of the slowest kept traces — the
    jump from "p99 is burning" to ``metrics-report``'s trace tree."""
    for h in (snapshot or {}).get("hists", []):
        for e in (h.get("exemplars") or [])[:3]:
            p.echo(
                f"{indent}exemplar {h['name']}: {_ms(e[0])}ms "
                f"trace={e[1]}"
            )


def _worker_lines(p: Printer, label: str, tel: dict, indent: str = "") -> None:
    stats = tel.get("stats") or {}
    snap = tel.get("snapshot")
    p.echo(
        f"{indent}{label}: pid={tel.get('pid')} "
        f"served={stats.get('served', 0)} "
        f"queue={stats.get('queue_depth', 0)} "
        f"p50={_ms(stats.get('latency_p50_ms'))}ms "
        f"p99={_ms(stats.get('latency_p99_ms'))}ms "
        f"h2d/dev={_hd_split(snap)}ms"
        + ("" if tel.get("telemetry_enabled") else " (metrics disabled)")
    )
    ops = stats.get("ops") or {}
    for op, s in sorted(ops.items()):
        p.echo(
            f"{indent}  {op}: n={s.get('requests', 0)} "
            f"rows={s.get('rows', 0)} "
            f"p50={_ms(s.get('p50_ms'))}ms p99={_ms(s.get('p99_ms'))}ms"
        )
    _host_line(p, snap, indent=indent + "  ")
    _slo_lines(p, tel.get("slo"), indent=indent + "  ")
    _accounting_lines(p, tel.get("accounting"), indent=indent + "  ")
    _exemplar_lines(p, snap, indent=indent + "  ")


def _render_fabric(p: Printer, resp: dict) -> None:
    workers = resp.get("workers") or {}
    healthy = sum(1 for w in workers.values() if w.get("healthy"))
    p.echo(
        f"fabric: {len(workers)} workers ({healthy} healthy)"
        + (" DRAINING" if resp.get("draining") else "")
    )
    counters = resp.get("counters") or {}
    if counters:
        p.echo("router: " + " ".join(
            f"{k}={v}" for k, v in sorted(counters.items())
        ))
    for wid, w in sorted(workers.items()):
        state = "up" if w.get("healthy") else "EJECTED"
        if w.get("draining"):
            state = "draining"
        head = (f"{wid} [{w.get('address')}] {state} "
                f"inflight={w.get('inflight', 0)}")
        tel = w.get("telemetry")
        if not tel:
            p.echo(f"{head} (no telemetry)")
            continue
        p.echo(head)
        _worker_lines(p, "worker", tel, indent="  ")
    _accounting_lines(p, resp.get("accounting"), indent="")
    moves = (resp.get("moves") or [])[-5:]
    if moves:
        p.echo("autoscale moves:")
        for m in moves:
            fields = " ".join(
                f"{k}={v}" for k, v in sorted((m.get("move") or {}).items())
            )
            p.echo(f"  {m.get('worker')}: {fields} ({m.get('reason')})")
    flight_tail = (resp.get("flight") or [])[-5:]
    if flight_tail:
        p.echo("recent flight events:")
        for ev in flight_tail:
            kind = ev.get("e", "?")
            rest = " ".join(
                f"{k}={v}" for k, v in sorted(ev.items())
                if k not in ("e", "t") and not isinstance(v, (list, dict))
            )
            p.echo(f"  {kind} {rest}")


def _render_once(p: Printer, resp: dict, prometheus: bool) -> None:
    if prometheus:
        if resp.get("prometheus") is not None:
            p.echo(resp["prometheus"].rstrip("\n"))
        else:
            # Single worker: render its own snapshot locally.
            from spark_bam_tpu.obs.exporters import prometheus_text

            p.echo(prometheus_text(resp.get("snapshot") or {}).rstrip("\n"))
        return
    if resp.get("fabric"):
        _render_fabric(p, resp)
    else:
        _worker_lines(p, "worker", resp)


def run(address: str, p: Printer, prometheus: bool = False,
        watch: bool = False, interval_s: float = 2.0) -> None:
    from spark_bam_tpu.serve.client import ServeClient

    fields = {"prometheus": True} if prometheus else {}
    with ServeClient(address) as client:
        resp = client.request("telemetry", **fields)
        if not watch:
            _render_once(p, resp, prometheus)
            return
        try:
            while True:
                # ANSI clear + home, straight to the terminal (the
                # Printer may be teed to a file; the control codes are
                # display-only).
                sys.stderr.write("\x1b[2J\x1b[H")
                sys.stderr.flush()
                _render_once(p, resp, prometheus)
                time.sleep(max(0.1, float(interval_s)))
                resp = client.request("telemetry", **fields)
        except KeyboardInterrupt:
            pass
