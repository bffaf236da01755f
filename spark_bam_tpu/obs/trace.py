"""Cross-process trace context: mint, bind, and carry trace/span ids.

One serve request should read as ONE trace no matter how many processes
it crosses (client → router → worker → batcher tick → device dispatch).
This module is the glue: a ``TraceContext`` (trace_id + the span_id of
the caller's active span) bound to the current execution context via
``contextvars`` — which follows both threads (when explicitly rebound at
the pool seam, see ``parallel.executor``) and asyncio tasks — plus a
wire carrier shape for the newline-JSON serve protocol.

Wire format: requests carry an optional ``"trace": {"id": ..., "span":
...}`` field. ``ServeClient`` mints it when observability is enabled in
the client process; the fabric router mints on behalf of bare clients
and relays it to workers; the worker's serve loop rebinds it around the
request handler so every span opened downstream inherits the same
trace_id and parents under the caller's span. ``metrics-report`` then
merges per-process JSONL files by trace_id into one tree.

Ids are opaque hex: 16 hex chars (64 bits) for trace_id and span_id —
collision-safe at fleet request rates, cheap to mint (one urandom call).

Everything here is independent of whether the live registry is
installed; binding a context with obs disabled costs one contextvar set.
"""

from __future__ import annotations

import contextlib
import contextvars
import os


class TraceContext:
    """An immutable (trace_id, parent span_id) pair."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str | None = None):
        self.trace_id = trace_id
        self.span_id = span_id

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"TraceContext({self.trace_id!r}, {self.span_id!r})"


_current: contextvars.ContextVar[TraceContext | None] = contextvars.ContextVar(
    "spark_bam_trace", default=None
)


def new_id() -> str:
    return os.urandom(8).hex()


def mint() -> TraceContext:
    """A fresh root context (new trace_id, no parent span yet)."""
    return TraceContext(new_id())


def current() -> TraceContext | None:
    """The context bound to this thread/task, or None."""
    return _current.get()


@contextlib.contextmanager
def bind(ctx: TraceContext | None):
    """Bind ``ctx`` for the duration of the block (None unbinds)."""
    token = _current.set(ctx)
    try:
        yield ctx
    finally:
        _current.reset(token)


def set_current(ctx: TraceContext | None) -> contextvars.Token:
    """Non-contextmanager bind for callback seams; pair with ``reset``."""
    return _current.set(ctx)


def reset(token: contextvars.Token) -> None:
    _current.reset(token)


def carried(fn):
    """``fn`` for a thread the caller starts or a pool it submits to:
    run there, it has the context bound HERE (a pool's threads begin with
    an empty one), so its spans join the caller's trace under the
    caller's span. ``fn`` itself when no context is bound."""
    ctx = _current.get()
    if ctx is None:
        return fn

    def run(*args, **kwargs):
        token = _current.set(ctx)
        try:
            return fn(*args, **kwargs)
        finally:
            _current.reset(token)

    return run


# ------------------------------------------------------------------ wire
def carrier(ctx: TraceContext | None = None) -> dict | None:
    """The request-field dict for ``ctx`` (default: the bound context)."""
    if ctx is None:
        ctx = _current.get()
    if ctx is None:
        return None
    c = {"id": ctx.trace_id}
    if ctx.span_id:
        c["span"] = ctx.span_id
    return c


def from_carrier(c) -> TraceContext | None:
    """Parse a request's ``trace`` field back into a context (lenient:
    malformed carriers yield None rather than failing the request)."""
    if not isinstance(c, dict):
        return None
    tid = c.get("id")
    if not isinstance(tid, str) or not tid:
        return None
    sid = c.get("span")
    return TraceContext(tid, sid if isinstance(sid, str) and sid else None)
