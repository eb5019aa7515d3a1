"""Table format factory + adaptive reader dispatch.

The pluggable-SST seam (reference TableFactory registry,
table/table_factory.cc:18-40, and the adaptive reader, table/adaptive/ in
/root/reference): builders are chosen by `TableOptions.format`; readers are
dispatched by footer magic, so a DB can hold a mix of formats (e.g.
single_fast at L0/L1, block at L2+) and always open every file.

On the device data plane (ops/pipeline.py, ops/device_compaction.py) a job
reads and writes block, zip and single_fast files, mixed freely; plain and
cuckoo files are read and written by the per-entry path only.
"""

from __future__ import annotations

from toplingdb_tpu.table import format as fmt
from toplingdb_tpu.table.builder import TableBuilder, TableOptions
from toplingdb_tpu.table.cuckoo import CuckooTableBuilder, CuckooTableReader
from toplingdb_tpu.table.plain import PlainTableBuilder, PlainTableReader
from toplingdb_tpu.table.reader import TableReader
from toplingdb_tpu.table.single_fast import (
    SingleFastTableBuilder,
    SingleFastTableReader,
)
from toplingdb_tpu.table.zip_table import ZipTableBuilder, ZipTableReader
from toplingdb_tpu.utils.status import Corruption, InvalidArgument

FORMATS = ("block", "single_fast", "cuckoo", "plain", "zip")


def new_table_builder(wfile, icmp, options: TableOptions | None = None,
                      **kw):
    options = options or TableOptions()
    f = getattr(options, "format", "block")
    if getattr(options, "auto_sort", False) and f != "single_fast":
        raise InvalidArgument(
            "auto_sort is a single_fast-format feature (the block builder "
            "requires sorted adds)"
        )
    if f == "block":
        return TableBuilder(wfile, icmp, options, **kw)
    if f == "single_fast":
        return SingleFastTableBuilder(wfile, icmp, options, **kw)
    if f == "cuckoo":
        return CuckooTableBuilder(wfile, icmp, options, **kw)
    if f == "plain":
        return PlainTableBuilder(wfile, icmp, options, **kw)
    if f == "zip":
        return ZipTableBuilder(wfile, icmp, options, **kw)
    raise InvalidArgument(f"unknown table format {f!r}")


def open_table(rfile, icmp, options: TableOptions | None = None,
               block_cache=None, cache_key_prefix: bytes = b""):
    """Adaptive open: dispatch on the footer magic."""
    size = rfile.size()
    tail = rfile.read(max(0, size - fmt.FOOTER_LEN), fmt.FOOTER_LEN)
    magic = fmt.Footer.read_magic(tail)
    if magic == fmt.MAGIC:
        return TableReader(rfile, icmp, options, block_cache=block_cache,
                           cache_key_prefix=cache_key_prefix)
    if magic == fmt.SINGLE_FAST_MAGIC:
        return SingleFastTableReader(rfile, icmp, options)
    if magic == fmt.CUCKOO_MAGIC:
        return CuckooTableReader(rfile, icmp, options)
    if magic == fmt.PLAIN_MAGIC:
        return PlainTableReader(rfile, icmp, options)
    if magic == fmt.ZIP_MAGIC:
        return ZipTableReader(rfile, icmp, options)
    raise Corruption(f"unknown SST magic {magic:#x}")
