"""A closed loop of ``ParquetFile(data).read(device=True)`` over every
column, each read ending in ``block_until_ready`` on every device array it
returned and freed before the next.

Correctness: a sample of the window's reads, drawn from the seed by a
reservoir, is kept on the device; after the window each goes to Arrow and
is compared, value by value, with pyarrow's read of the same bytes."""

from lib import compare, device_arrays
from lib.rows import rng_for


class Traffic:
    def __init__(self, ctx):
        self.ctx = ctx
        self.keep = ctx.traffic.get("keep_reads", 1)
        self.rng = rng_for(ctx.seed)
        self.kept = []  # (read index, table)
        self.seen = 0
        self.work_bytes = ctx.arrow_bytes

    def read(self):
        from parquet_tpu import ParquetFile

        return ParquetFile(self.ctx.data).read(device=True)

    def warm(self):
        table = self.read()
        device_arrays.block(device_arrays.table_arrays(table))

    def request(self, i):
        table = self.read()
        device_arrays.block(device_arrays.table_arrays(table))
        # a column left on the host is a device read that failed
        failed = bool(device_arrays.host_columns(table))
        # reservoir sample of the window's reads, from the seed
        self.seen += 1
        if len(self.kept) < self.keep:
            self.kept.append((i, table))
        else:
            j = int(self.rng.integers(0, self.seen))
            if j < self.keep:
                self.kept[j] = (i, table)
        return {"failed": failed}

    def release(self):
        """Nothing but the sample stays on the device."""

    def check(self, control):
        import pyarrow as pa
        import pyarrow.parquet as pq

        want = pq.read_table(pa.BufferReader(self.ctx.data))
        bad = 0
        for _, table in self.kept:
            got = (compare.lower_precision(want, self.ctx.cfg["control"])
                   if control else table.to_arrow())
            bad += compare.mismatches(got, want)
        self.kept = []
        return {"mismatched_values": bad}
