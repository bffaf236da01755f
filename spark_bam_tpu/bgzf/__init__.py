from spark_bam_tpu.bgzf.header import (
    Header,
    HeaderParseException,
    HeaderSearchFailedException,
)
from spark_bam_tpu.bgzf.block import Block, Metadata, MAX_BLOCK_SIZE, FOOTER_SIZE
from spark_bam_tpu.bgzf.stream import (
    BlockStream,
    SeekableBlockStream,
    MetadataStream,
    UncompressedBytes,
    SeekableUncompressedBytes,
    pos_iterator,
    scan_metadata,
)
from spark_bam_tpu.bgzf.find_block_start import find_block_start

__all__ = [
    "Header",
    "HeaderParseException",
    "HeaderSearchFailedException",
    "Block",
    "Metadata",
    "MAX_BLOCK_SIZE",
    "FOOTER_SIZE",
    "BlockStream",
    "SeekableBlockStream",
    "MetadataStream",
    "UncompressedBytes",
    "SeekableUncompressedBytes",
    "pos_iterator",
    "scan_metadata",
    "find_block_start",
]
