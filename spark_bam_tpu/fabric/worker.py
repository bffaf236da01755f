"""Runnable serve worker + local pool supervisor for the fabric.

One worker = one ``SplitService`` accept loop over THIS process's local
devices. Run directly (one per host, the ``jax.distributed`` bring-up
mirroring parallel/multihost.py) or let :class:`WorkerPool` launch N
local processes on a dev box:

    python -m spark_bam_tpu.fabric.worker \
        --listen tcp:127.0.0.1:0 [--devices 2] [--serve SPEC] \
        [--coordinator HOST0:port --num-processes N --process-id K]

On start the worker prints ONE JSON line on stdout —
``{"fabric_worker": true, "address": "tcp:host:port", ...}`` — which is
how the pool (and operators scripting attach mode) learn the bound
address when the listen spec asked for port 0. SIGTERM/SIGINT trigger a
graceful drain: new work is refused with a typed ``Draining`` error,
in-flight requests and queued batcher ticks finish unshed, then the
process exits.

The mesh is built over ``jax.local_devices()`` — NOT the global mesh —
because a serving worker answers only its own requests: a collective
step compiled over the global mesh would deadlock waiting for dispatches
the other hosts never make. Multi-host fabric = one local serving loop
per host, with the router doing the cross-host fan-out.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time


def serve_worker(
    listen: str = "tcp:127.0.0.1:0",
    devices: int = 0,
    serve: str = "",
    columnar: str = "",
    slo: str = "",
    coordinator: "str | None" = None,
    num_processes: int = 1,
    process_id: int = 0,
    announce: bool = True,
    drain_wait_s: float = 30.0,
    ready: "threading.Event | None" = None,
) -> int:
    """Bring up one serve worker and block until SIGTERM-drained."""
    from spark_bam_tpu.core.platform import enable_compile_cache

    if devices:
        from spark_bam_tpu.core.platform import force_cpu_devices

        force_cpu_devices(devices, defer_init=num_processes > 1)
    # Pool workers respawn per fabric bring-up; the persistent compile
    # cache turns the serve step's first compile into a disk hit.
    enable_compile_cache()
    import jax

    if num_processes > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=num_processes,
            process_id=process_id,
        )

    from spark_bam_tpu import obs
    from spark_bam_tpu.core.config import Config
    from spark_bam_tpu.obs import flight
    from spark_bam_tpu.parallel.mesh import local_mesh
    from spark_bam_tpu.serve.server import ServerThread
    from spark_bam_tpu.serve.service import SplitService

    # A live registry regardless of --metrics-out: the stats op's
    # split_resolutions (the per-worker warm-tier proof) reads it.
    if not obs.enabled():
        obs.configure()

    # Disk-fault chaos rides the environment into pool workers exactly
    # like fabric chaos rides SPARK_BAM_FABRIC: the storm tests set
    # SPARK_BAM_DISK_CHAOS before spawning, every worker injects the
    # same seeded fault schedule, and the flight context names it.
    from spark_bam_tpu.core.faults import maybe_install_disk_chaos_from_env

    maybe_install_disk_chaos_from_env()

    config = Config.from_env()
    if serve:
        config = config.replace(serve=serve)
    if columnar:
        config = config.replace(columnar=columnar)
    if slo:
        config = config.replace(slo=slo)
    try:
        # Under a chaos run (SPARK_BAM_FABRIC carries chaos=SEED:SPEC)
        # the worker's own dumps must name the seed too — a postmortem
        # from EITHER side of the fabric seam reproduces the run.
        chaos_spec = config.fabric_config.chaos
    except Exception:
        chaos_spec = ""
    if chaos_spec:
        flight.set_context(chaos=chaos_spec)
    # A SIGKILL'd predecessor can't unlink its ring segments; sweep any
    # whose creating pid is dead so a storm can't leak /dev/shm.
    from spark_bam_tpu.serve.shm import sweep_orphans

    sweep_orphans()
    service = SplitService(config, mesh=local_mesh())

    stop = threading.Event()

    def _drain_and_stop(signum, frame):
        flight.record("sigterm", signum=int(signum))
        service.drain()
        stop.set()

    signal.signal(signal.SIGTERM, _drain_and_stop)
    signal.signal(signal.SIGINT, _drain_and_stop)

    srv = ServerThread(service, listen).start()
    addr = srv.address
    spec = addr if isinstance(addr, str) else f"tcp:{addr[0]}:{addr[1]}"
    flight.record("worker_start", address=spec,
                  devices=int(service.mesh.devices.size))
    if announce:
        print(json.dumps({
            "fabric_worker": True,
            "address": spec,
            "pid": os.getpid(),
            "process_id": int(process_id),
            "devices": int(service.mesh.devices.size),
        }), flush=True)
    if ready is not None:
        ready.set()
    try:
        stop.wait()
        # Drained: let in-flight ticks finish unshed before detaching.
        deadline = time.monotonic() + drain_wait_s
        while (sum(service.gate.inflight().values()) > 0
               and time.monotonic() < deadline):
            time.sleep(0.05)
    except BaseException as exc:
        # The one crash the worker CAN narrate: dump the ring before
        # the exception unwinds the process.
        flight.dump_auto("crash", extra={"address": spec,
                                         "error": repr(exc)})
        raise
    finally:
        srv.stop()
        service.close()
        # Postmortem + trace artifacts on the graceful path: the drain
        # dump names the requests this worker saw; the JSONL trace is
        # what metrics-report merges across the fleet by trace_id.
        flight.dump_auto("drain", extra={"address": spec})
        out = obs.resolve_metrics_path(
            os.environ.get("SPARK_BAM_METRICS_OUT")
        )
        if out:
            try:
                obs.export_jsonl(out)
            except OSError:
                pass
    return 0


class WorkerPool:
    """Launch (or attach to) the fabric's serve workers.

    Launch mode spawns N ``fabric.worker`` subprocesses on this host and
    reads each one's announce line for its bound address; attach mode
    takes addresses of already-running workers (other hosts' loops) and
    supervises nothing. ``kill(i, hard=True)`` exists for the failover
    bench/tests; ``terminate()`` SIGTERMs for graceful drains. The chaos
    layer (fabric/chaos.py ``ChaosStorm``) adds three more verbs:
    ``respawn(i)`` relaunches a killed worker on its ORIGINAL port (the
    router's link re-probes the same address and reinstates it), and
    ``wedge(i)``/``unwedge(i)`` SIGSTOP/SIGCONT a live worker — the
    wedged state keeps every socket open while answering nothing, which
    only a probe timeout can detect.
    """

    def __init__(self, workers: int = 3, devices: int = 1, serve: str = "",
                 columnar: str = "", slo: str = "",
                 attach: "list[str] | None" = None,
                 env: "dict | None" = None, stderr=None):
        self.workers = int(workers)
        self.devices = int(devices)
        self.serve = serve
        self.columnar = columnar
        self.slo = slo
        self.attach = list(attach or [])
        self.env = env
        self.stderr = stderr
        self.procs: list = []
        self.addresses: "list[str]" = []

    def _spawn(self, listen: str):
        import subprocess

        env = dict(os.environ if self.env is None else self.env)
        # -c (not -m): runpy would import the fabric package first and
        # warn about the worker module being re-executed as __main__.
        cmd = [sys.executable, "-c",
               "import sys; from spark_bam_tpu.fabric.worker import main;"
               " sys.exit(main(sys.argv[1:]))",
               "--listen", listen]
        if self.devices:
            cmd += ["--devices", str(self.devices)]
        if self.serve:
            cmd += ["--serve", self.serve]
        if self.columnar:
            cmd += ["--columnar", self.columnar]
        if self.slo:
            cmd += ["--slo", self.slo]
        return subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=self.stderr,
            env=env, text=True,
        )

    def start(self, timeout_s: float = 120.0) -> "list[str]":
        if self.attach:
            self.addresses = list(self.attach)
            return self.addresses
        for _ in range(self.workers):
            self.procs.append(self._spawn("tcp:127.0.0.1:0"))
        deadline = time.monotonic() + timeout_s
        for p in self.procs:
            line = self._read_announce(p, deadline)
            self.addresses.append(line["address"])
        return self.addresses

    @staticmethod
    def _read_announce(proc, deadline: float) -> dict:
        # The worker prints exactly one JSON line once it is listening;
        # anything else on stdout before it (warnings) is skipped.
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"fabric worker exited rc={proc.returncode} before "
                    "announcing its address"
                )
            line = proc.stdout.readline()
            if not line:
                time.sleep(0.05)
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict) and obj.get("fabric_worker"):
                return obj
        raise TimeoutError("fabric worker did not announce in time")

    def kill(self, i: int, hard: bool = False) -> None:
        p = self.procs[i]
        if p.poll() is None:
            p.send_signal(signal.SIGKILL if hard else signal.SIGTERM)

    def respawn(self, i: int, timeout_s: float = 120.0) -> str:
        """Relaunch worker ``i`` on its ORIGINAL port. The router's link
        for that address stays in place; its health monitor reinstates
        the worker on the first successful re-probe — a rolling storm
        leaves the fleet exactly as it found it."""
        old = self.procs[i]
        if old.poll() is None:
            old.kill()
        old.wait(timeout=timeout_s)
        if old.stdout is not None:
            old.stdout.close()
        addr = self.addresses[i]
        deadline = time.monotonic() + timeout_s
        while True:
            # The dying process may hold the port through TCP teardown;
            # retry the bind until the OS releases it.
            proc = self._spawn(addr)
            try:
                line = self._read_announce(proc, deadline)
                break
            except RuntimeError:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.2)
        self.procs[i] = proc
        if line["address"] != addr:
            raise RuntimeError(
                f"respawned worker bound {line['address']}, wanted {addr}"
            )
        return addr

    def wedge(self, i: int) -> None:
        """SIGSTOP worker ``i``: sockets stay open, nothing answers —
        the failure mode only a probe timeout can detect."""
        p = self.procs[i]
        if p.poll() is None:
            p.send_signal(signal.SIGSTOP)

    def unwedge(self, i: int) -> None:
        p = self.procs[i]
        if p.poll() is None:
            p.send_signal(signal.SIGCONT)

    def terminate(self, timeout_s: float = 30.0) -> None:
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + timeout_s
        for p in self.procs:
            left = max(0.1, deadline - time.monotonic())
            try:
                p.wait(timeout=left)
            except Exception:
                p.kill()
        for p in self.procs:
            if p.stdout is not None:
                p.stdout.close()

    def __enter__(self) -> "WorkerPool":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.terminate()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--listen", default="tcp:127.0.0.1:0",
                    help="accept-loop address (tcp:host:port or unix:path; "
                         "port 0 binds an ephemeral port, announced on stdout)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N virtual CPU devices (dev boxes / pool "
                         "mode); 0 = this host's real devices")
    ap.add_argument("--serve", default="", help="ServeConfig spec override")
    ap.add_argument("--columnar", default="",
                    help="ColumnarConfig spec override")
    ap.add_argument("--slo", default="",
                    help="SloConfig spec override (objectives + burn-rate "
                         "alerting, obs/slo.py)")
    ap.add_argument("--coordinator", default=None)
    ap.add_argument("--num-processes", type=int, default=1)
    ap.add_argument("--process-id", type=int, default=0)
    a = ap.parse_args(argv)
    return serve_worker(
        listen=a.listen, devices=a.devices, serve=a.serve,
        columnar=a.columnar, slo=a.slo, coordinator=a.coordinator,
        num_processes=a.num_processes, process_id=a.process_id,
    )


if __name__ == "__main__":
    raise SystemExit(main())
