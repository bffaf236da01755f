"""BGZF block values (reference bgzf/.../block/Block.scala, Metadata.scala)."""

from __future__ import annotations

from dataclasses import dataclass, field

from spark_bam_tpu.core.guard import StructurallyInvalid
from spark_bam_tpu.core.pos import Pos

MAX_BLOCK_SIZE = 64 * 1024  # uncompressed payload never exceeds 64 KiB
FOOTER_SIZE = 8             # CRC32 + uncompressed-size, both u32


def check_isize(uncompressed_size: int, start: int) -> int:
    """Validate a block footer's ISIZE before anything allocates on it —
    a corrupt 4 GB ISIZE sizes the inflate buffer otherwise."""
    if uncompressed_size > MAX_BLOCK_SIZE:
        raise StructurallyInvalid(
            f"BGZF ISIZE {uncompressed_size} exceeds the "
            f"{MAX_BLOCK_SIZE}-byte block limit", pos=start
        )
    return uncompressed_size


@dataclass(frozen=True)
class Metadata:
    """Block coordinates without the payload."""
    start: int             # compressed-file offset of the block start
    compressed_size: int
    uncompressed_size: int


@dataclass
class Block:
    """Decompressed block payload + coordinates; carries a read cursor ``idx``."""
    data: bytes
    start: int
    compressed_size: int
    idx: int = field(default=0, compare=False)

    @property
    def uncompressed_size(self) -> int:
        return len(self.data)

    @property
    def pos(self) -> Pos:
        return Pos(self.start, self.idx)

    @property
    def next_start(self) -> int:
        return self.start + self.compressed_size

    def metadata(self) -> Metadata:
        return Metadata(self.start, self.compressed_size, self.uncompressed_size)
