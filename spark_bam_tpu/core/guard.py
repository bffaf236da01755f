"""Resource-guarded decode boundary: limits + malformed-input taxonomy.

Every parser that consumes untrusted bytes (bam/record, bam/header, bam/bai,
bgzf/header, bgzf/stream, sbi/format, the cram/ readers) trusts the length
fields it reads until this layer says otherwise. One corrupt byte used to be
able to hang a worker (an unbounded count loop), OOM a host (a 2 GB
``remaining``), or yield silently-wrong records (a short slice where a
truncation error belonged). Two halves live here:

- ``DecodeLimits`` — per-field resource ceilings (record bytes, header text,
  reference count/name length, CIGAR ops, sequence length, allocation
  budget), parseable from a compact ``k=v,...`` spec so it threads through
  config/env/CLI unchanged (``Config.limits`` / ``SPARK_BAM_LIMITS`` /
  ``--limits``). Parsers read the process-wide active limits via
  ``current_limits()``; ``scoped_limits`` overrides them for a test or a
  fuzz run.

- The ``MalformedInputError`` hierarchy — typed verdicts on bad bytes,
  plugging into the fault model (core/faults.py):

    ``TruncatedInput``       the bytes end before the structure does
                             (also an ``EOFError``: historical truncation
                             handlers keep working)
    ``StructurallyInvalid``  a field contradicts the format (negative
                             size, missing subfield, overflowing extent)
    ``LimitExceeded``        well-formed but beyond ``DecodeLimits``

  All three are ``ValueError`` + ``Unrecoverable``: deterministic damage
  that no retry fixes. Strict mode raises them with file/virtual-position
  context; tolerant mode quarantines the damaged record or block and
  resumes at the next provable boundary, counting losses in the
  ``guard.*`` metrics tallied here.

The structure-aware mutation fuzzer (tools/fuzz_decode.py) asserts the
contract: every mutant either parses clean, raises a typed
``MalformedInputError``, or quarantines-with-resume — never a hang, never
an over-budget allocation, never an untyped crash. Semantics in
docs/robustness.md ("Malformed inputs").
"""

from __future__ import annotations

import contextlib
import errno
import os
import threading
from dataclasses import dataclass
from functools import lru_cache

from spark_bam_tpu import obs
from spark_bam_tpu.core.config import parse_bytes
from spark_bam_tpu.core.faults import Unrecoverable


# ----------------------------------------------------------------- taxonomy
class MalformedInputError(ValueError, Unrecoverable):
    """The bytes are not a well-formed instance of the format being parsed.

    Deterministic damage: retrying re-reads the same bytes, so the fault
    model never burns retry budget on it (``Unrecoverable``). ``path`` and
    ``pos`` (a virtual/flat position, when the parser knows one) locate the
    damage for the strict-mode error message and the tolerant-mode
    quarantine ledger.
    """

    def __init__(self, msg: str, *, path=None, pos=None):
        self.path = path
        self.pos = pos
        ctx = []
        if path is not None:
            ctx.append(str(path))
        if pos is not None:
            ctx.append(f"at {pos}")
        super().__init__(f"{msg} [{', '.join(ctx)}]" if ctx else msg)


class TruncatedInput(MalformedInputError, EOFError):
    """The input ends before the declared structure does — the bytes that
    should complete it never existed. Subclasses ``EOFError`` so the
    historical clean-truncation handlers (record streams, index writers)
    keep catching it without modification."""


class StructurallyInvalid(MalformedInputError):
    """A field contradicts the format itself: a negative size, a missing
    mandatory subfield, declared sub-regions overflowing the declared
    extent. No limit tuning makes these bytes parseable."""


class LimitExceeded(MalformedInputError):
    """Structurally plausible but beyond the active ``DecodeLimits`` —
    the defense against resource-exhaustion fields (a 2 GB record, a 2³¹
    reference count) that would otherwise hang or OOM a worker."""


class RecordGapError(IOError, Unrecoverable):
    """Tolerant-mode record resync marker: the record at virtual position
    ``pos`` declared an untrustworthy length prefix, so the stream cannot
    locally skip it. Raised once by a tolerant record stream; the load
    layer re-finds the next provable record boundary with the checker and
    resumes (the block-layer analog is ``BlockGapError``)."""

    def __init__(self, pos, reason: str):
        super().__init__(f"unreadable BAM record at {pos}: {reason}")
        self.pos = pos
        self.reason = reason


#: What a check of untrusted bytes raises when the INPUT is at fault — the
#: only errors the device paths may answer by demoting a window to host
#: zlib. ``IOError`` covers the tokenizer's rejects and footer
#: disagreements, ``EOFError`` truncation, and ``MalformedInputError`` the
#: taxonomy above. Compiler, lowering and device runtime errors are none of
#: these and propagate.
INPUT_ERRORS = (IOError, EOFError, MalformedInputError)


class ResourceExhausted(OSError):
    """The environment ran out of a resource mid-operation — disk space
    (``ENOSPC``), quota (``EDQUOT``), a failing device (``EIO``) — while
    writing an artifact. Retryable by the fault model (an ``OSError``
    that is *not* ``Unrecoverable``): space gets freed, quotas get
    raised, devices get replaced. The durable-job plane (jobs/) pauses
    a journaled job on this instead of failing it; resume picks up from
    the last committed checkpoint."""

    def __init__(self, msg: str, *, errno_: "int | None" = None, path=None):
        super().__init__(errno_ or 0, msg, str(path) if path else None)


#: errnos that mean "the environment is out of a resource" rather than
#: "these bytes/paths are wrong" — the write-side mirror of the
#: read-side transient set.
_EXHAUSTED_ERRNOS = frozenset(
    getattr(errno, name)
    for name in ("ENOSPC", "EDQUOT", "EIO", "ENOMEM")
    if hasattr(errno, name)
)


def map_write_error(exc: OSError, what: str, path=None) -> OSError:
    """Classify an ``OSError`` escaping a writer: exhaustion errnos become
    :class:`ResourceExhausted` (retryable, job-pausing); anything else is
    returned unchanged so deterministic errors (``EACCES``, ``ENOENT``)
    keep their type. Callers ``raise map_write_error(e, ...) from e``."""
    if isinstance(exc, ResourceExhausted):
        return exc
    if exc.errno in _EXHAUSTED_ERRNOS:
        return ResourceExhausted(
            f"{what}: {exc.strerror or exc}", errno_=exc.errno, path=path
        )
    return exc


def preflight_space(path, need_bytes: int, margin: float = 1.1) -> None:
    """ENOSPC preflight: refuse to *start* a write that cannot fit.
    ``need_bytes`` is the caller's estimate; ``margin`` covers metadata
    and estimate error. Best-effort — filesystems without ``statvfs``
    skip the check and rely on the mid-write mapping instead."""
    if need_bytes <= 0:
        return
    target = os.path.dirname(os.path.abspath(str(path))) or "."
    try:
        st = os.statvfs(target)
    except (OSError, AttributeError):
        return
    free = st.f_bavail * st.f_frsize
    if free < need_bytes * margin:
        raise ResourceExhausted(
            f"preflight: {path} needs ~{int(need_bytes * margin)} bytes, "
            f"filesystem has {free} free",
            errno_=errno.ENOSPC, path=path,
        )


# ------------------------------------------------------------------- limits
@dataclass(frozen=True)
class DecodeLimits:
    """Resource ceilings for untrusted-byte parsers. Defaults are far above
    anything a well-formed file produces (ultralong nanopore records are
    tens of MB; SAM headers with full RG/PG provenance are single-digit
    MB) while keeping the worst single allocation a corrupt length field
    can force well under a worker's memory."""

    max_record_bytes: int = 64 << 20   # one BAM record (block_size)
    max_header_text: int = 64 << 20    # SAM header text bytes
    max_refs: int = 1 << 20            # reference-dictionary entries
    max_name_len: int = 4096           # one reference/read name
    max_cigar_ops: int = 1 << 16       # CIGAR ops per record (u16 in BAM)
    max_seq_len: int = 1 << 28         # bases per record
    alloc_budget: int = 1 << 30        # per-partition allocation ceiling

    def __post_init__(self):
        for f in (
            "max_record_bytes", "max_header_text", "max_refs",
            "max_name_len", "max_cigar_ops", "max_seq_len", "alloc_budget",
        ):
            if getattr(self, f) <= 0:
                raise ValueError(f"DecodeLimits.{f} must be > 0: "
                                 f"{getattr(self, f)}")

    _KEYS = {
        "record": "max_record_bytes",
        "max_record_bytes": "max_record_bytes",
        "header_text": "max_header_text",
        "text": "max_header_text",
        "max_header_text": "max_header_text",
        "refs": "max_refs",
        "max_refs": "max_refs",
        "name": "max_name_len",
        "max_name_len": "max_name_len",
        "cigar": "max_cigar_ops",
        "max_cigar_ops": "max_cigar_ops",
        "seq": "max_seq_len",
        "max_seq_len": "max_seq_len",
        "alloc": "alloc_budget",
        "alloc_budget": "alloc_budget",
    }

    @staticmethod
    @lru_cache(maxsize=64)
    def parse(spec: str) -> "DecodeLimits":
        """``"record=32MB,refs=1000,alloc=512MB"`` (any subset; ``""`` ⇒
        defaults). Values accept the usual byte-size shorthand."""
        kw: dict = {}
        for part in (spec or "").split(","):
            part = part.strip()
            if not part:
                continue
            if "=" not in part:
                raise ValueError(f"Bad decode-limit entry {part!r} in {spec!r}")
            key, value = (t.strip() for t in part.split("=", 1))
            field = DecodeLimits._KEYS.get(key.replace("-", "_"))
            if field is None:
                raise ValueError(
                    f"Unknown decode-limit key {key!r}: expected one of "
                    f"{', '.join(sorted(set(DecodeLimits._KEYS)))}"
                )
            kw[field] = parse_bytes(value)
        return DecodeLimits(**kw)

    @staticmethod
    def from_env(env=None) -> "DecodeLimits":
        return DecodeLimits.parse(
            (env or os.environ).get("SPARK_BAM_LIMITS", "")
        )


# Process-wide active limits: parsers deep below the config-threading
# surface (record decode, CRAM cursors) read these; ``--limits`` and the
# fuzz harness install overrides. None ⇒ fall through to the env spec.
_active: DecodeLimits | None = None


def current_limits() -> DecodeLimits:
    return _active if _active is not None else DecodeLimits.from_env()


def set_limits(limits: "DecodeLimits | str | None") -> None:
    global _active
    _active = DecodeLimits.parse(limits) if isinstance(limits, str) else limits


@contextlib.contextmanager
def scoped_limits(limits: "DecodeLimits | str"):
    """``with scoped_limits("record=1MB"): ...`` — scoped installation."""
    global _active
    prev = _active
    _active = DecodeLimits.parse(limits) if isinstance(limits, str) else limits
    try:
        yield _active
    finally:
        _active = prev


# ------------------------------------------------------------ guard helpers
def check_count(n: int, what: str, limit: int | None = None, *,
                path=None, pos=None) -> int:
    """Validate a count/length field read from untrusted bytes: negative ⇒
    ``StructurallyInvalid``, beyond ``limit`` ⇒ ``LimitExceeded``."""
    if n < 0:
        raise StructurallyInvalid(f"{what} is negative ({n})",
                                  path=path, pos=pos)
    if limit is not None and n > limit:
        raise LimitExceeded(f"{what} {n} exceeds limit {limit}",
                            path=path, pos=pos)
    return n


def check_available(have: int, need: int, what: str, *,
                    path=None, pos=None) -> None:
    """Explicit truncation check before consuming ``need`` bytes — the
    replacement for silent short slices."""
    if have < need:
        raise TruncatedInput(f"{what}: need {need} bytes, have {have}",
                             path=path, pos=pos)


# ------------------------------------------------------------ loss tallies
class _LossTally:
    """Process-wide quarantine counts, snapshotted by ``run_partitions`` so
    a ``JobReport`` can state exactly what a tolerant load lost."""

    __slots__ = ("lock", "records", "blocks")

    def __init__(self):
        self.lock = threading.Lock()
        self.records = 0
        self.blocks = 0


_loss = _LossTally()


def note_quarantined_records(n: int = 1) -> None:
    obs.count("guard.quarantined_records", n)
    with _loss.lock:
        _loss.records += n


def note_quarantined_block() -> None:
    obs.count("guard.quarantined_blocks")
    with _loss.lock:
        _loss.blocks += 1


def loss_totals() -> tuple[int, int]:
    """(quarantined records, quarantined blocks) since process start."""
    with _loss.lock:
        return _loss.records, _loss.blocks
