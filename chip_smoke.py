#!/usr/bin/env python3
"""Chip smoke: parquet-tpu's main read path, once, on a TPU, over a TPC-H
SF1 ``lineitem`` store.

One process does everything (a chip belongs to one process):

1. setup   — compile cache, JAX/device report, native shim; exits non-zero
             off the TPU or without the shim.
2. load    — a seeded SF1 lineitem table (6,001,215 rows, the 16 columns of
             TPC-H §1.4 in the shapes of ``bench.py:_lineitem_path``),
             written once by parquet-tpu's ``ParquetWriter`` (Snappy,
             dictionary where it applies, ~1M-row row groups, DataPage V2)
             and once by pyarrow, plus the pyarrow file split into 8 files.
3. queries — through the public entry points, each answer compared with
             pyarrow on the same bytes: full device reads of both files,
             ``Dataset.read(device=True)``, a Q6-shaped routed ``scan``, the
             Q1 group-by of ``examples/tpch_q1_tpu.py``, and our file read
             back by pyarrow.
4. check   — counters around phase 3: device decodes happened, no chunk
             fell back to the host, no device route was refused, nothing
             was logged or warned by the package.

``--chips 4`` runs only the mesh paths (the 8-file dataset round-robined
over the chips, ``read_table_sharded``, sharded Q1), each compared with the
one-chip answer and with pyarrow.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
phase raises and exits non-zero.  ``--rows`` other than SF1 is a rehearsal:
it runs the phases on any platform (the CPU included) and exits 3 without
that line, so the line only ever comes from an SF1 run on a TPU.
"""

import argparse
import json
import logging
import os
import shutil
import sys
import time
import warnings

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

SF1_ROWS = 6_001_215
ROW_GROUP = 1_000_000
N_FILES = 8
# pyarrow dictionary-encodes the low-cardinality flags only, as real
# lineitem files are written (bench.py:_lineitem_path)
DICT_COLS = ["l_returnflag", "l_linestatus", "l_shipinstruct", "l_shipmode"]
# Q6 (TPC-H §2.4.6.3 defaults): shipdate in [1994-01-01, 1995-01-01),
# discount BETWEEN 0.06 -/+ 0.01, quantity < 24.  The generator's dates are
# day numbers in [8000, 12000), so the year is the day range below.
Q6_DAYS = (8766, 9130)  # 1994-01-01 .. 1994-12-31 as days since 1970
Q6_DISC = (0.05, 0.07)
Q6_QTY = 24
# float sums: the device and pyarrow add in different orders, and the TPU
# has no native float64 (its f64 is emulated, not IEEE-exact).  The chip
# kept 5.26e-16 (Q6) and 1.71e-12 (Q1) relative; float32 sums of the same
# queries miss 1e-9 (my chip runs, PR 21), so this catches a drop to f32
FLOAT_RTOL = 1e-9


def say(msg: str) -> None:
    print(msg, flush=True)


class Phases:
    """Wall and XLA-compile seconds per phase (compile time from JAX's own
    ``backend_compile_duration`` events)."""

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.compiles = 0
        self.slow = []  # (seconds, function) of compiles over a second
        self.rows = []

        def on_event(event, duration, fun_name="?", **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += duration
                self.compiles += 1
                if duration > 1.0:
                    self.slow.append((round(duration, 3), fun_name))
                if duration > 10.0:  # seen at once, even if a phase hangs
                    say(f"slow compile {duration:.3f}s {fun_name}")

        jax.monitoring.register_event_duration_secs_listener(on_event)

    def run(self, name, fn, *args):
        c0, n0, t0 = self.compile_s, self.compiles, time.perf_counter()
        out = fn(*args)
        row = {"phase": name,
               "wall_s": round(time.perf_counter() - t0, 3),
               "compile_s": round(self.compile_s - c0, 3),
               "compiles": self.compiles - n0}
        if self.slow:
            row["slowest_compiles"] = sorted(self.slow, reverse=True)[:5]
            self.slow = []
        self.rows.append(row)
        say(f"phase {json.dumps(row)}")
        return out


class PackageNoise(logging.Handler):
    """Collects every warning the package logs or warns: a caught-and-
    logged exception anywhere under ``parquet_tpu`` fails the smoke."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.seen = []
        logging.getLogger().addHandler(self)
        self._showwarning = warnings.showwarning
        warnings.showwarning = self._on_warning
        warnings.simplefilter("always")

    def emit(self, record):
        if record.name.startswith("parquet_tpu"):
            self.seen.append(f"log {record.name}: {record.getMessage()}")

    def _on_warning(self, message, category, filename, lineno, *a, **k):
        if os.sep + "parquet_tpu" + os.sep in filename:
            self.seen.append(f"warning {filename}:{lineno}: {message}")
        self._showwarning(message, category, filename, lineno, *a, **k)


# --------------------------------------------------------------------------
# setup
# --------------------------------------------------------------------------

def setup(args):
    import jax

    from parquet_tpu import native
    from parquet_tpu.utils.compile_cache import setup_compile_cache

    cache = setup_compile_cache()
    devs = jax.devices()
    say(f"jax {jax.__version__} devices={devs} "
        f"kind={devs[0].device_kind!r} compile_cache={cache}")
    if devs[0].platform != "tpu" and args.rows == SF1_ROWS:
        raise SystemExit(f"chip_smoke: no TPU (platform "
                         f"{devs[0].platform!r}); nothing was run")
    if args.chips > len(devs):
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devs)} device(s)")
    if native.get_lib() is None:
        raise SystemExit(f"chip_smoke: native shim unavailable: "
                         f"{native.build_error}")
    return devs


# --------------------------------------------------------------------------
# load
# --------------------------------------------------------------------------

def lineitem(n: int, seed: int):
    """TPC-H lineitem, the 16 columns of §1.4, in the value shapes of
    ``bench.py:_lineitem_path``: int64 keys, dictionary string flags, int32
    day-number dates, float64 price/discount/tax, a 27-byte comment."""
    import numpy as np
    import pyarrow as pa

    rng = np.random.default_rng(seed)

    def dict_col(values):
        idx = rng.integers(0, len(values), n).astype(np.int32)
        return pa.DictionaryArray.from_arrays(idx, pa.array(values))

    letters = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz ", np.uint8)
    comment_w = 27
    data = letters[rng.integers(0, len(letters), n * comment_w)]
    offsets = np.arange(n + 1, dtype=np.int32) * comment_w
    comment = pa.StringArray.from_buffers(n, pa.py_buffer(offsets),
                                          pa.py_buffer(data.tobytes()))
    ship = rng.integers(8000, 12000, n).astype(np.int32)
    return pa.table({
        "l_orderkey": np.sort(rng.integers(1, n, n)).astype(np.int64),
        "l_partkey": rng.integers(1, 200_000, n).astype(np.int64),
        "l_suppkey": rng.integers(1, 10_000, n).astype(np.int64),
        "l_linenumber": rng.integers(1, 8, n).astype(np.int32),
        "l_quantity": rng.integers(1, 51, n).astype(np.int64),
        "l_extendedprice": rng.random(n) * 1e5,
        "l_discount": np.round(rng.random(n) * 0.1, 2),
        "l_tax": np.round(rng.random(n) * 0.08, 2),
        "l_returnflag": dict_col(["A", "N", "R"]),
        "l_linestatus": dict_col(["F", "O"]),
        "l_shipdate": ship,
        "l_commitdate": ship + rng.integers(-30, 30, n).astype(np.int32),
        "l_receiptdate": ship + rng.integers(1, 30, n).astype(np.int32),
        "l_shipinstruct": dict_col(["DELIVER IN PERSON", "COLLECT COD",
                                    "NONE", "TAKE BACK RETURN"]),
        "l_shipmode": dict_col(["AIR", "FOB", "MAIL", "RAIL", "REG AIR",
                                "SHIP", "TRUCK"]),
        "l_comment": comment,
    })


def row_group(rows: int) -> int:
    """~1M rows at SF1 (7 row groups); a rehearsal's few rows keep 7."""
    return min(ROW_GROUP, -(-rows // 6))


def load(args, data_dir):
    """Generate the table and write the store; returns paths + table."""
    import pyarrow.parquet as pq

    from parquet_tpu import WriterOptions, write_table

    t0 = time.perf_counter()
    table = lineitem(args.rows, args.seed)
    say(f"generated lineitem rows={table.num_rows} "
        f"arrow_bytes={table.nbytes} in {time.perf_counter() - t0:.2f}s")
    os.makedirs(data_dir, exist_ok=True)
    paths = {}
    # the pyarrow file: one chip reads 1M-row groups; the mesh path needs
    # >= 4 row groups per chip
    rg = (row_group(args.rows) if args.chips == 1
          else -(-args.rows // (4 * args.chips)))
    paths["pyarrow"] = os.path.join(data_dir, "lineitem_pyarrow.parquet")
    pq.write_table(table, paths["pyarrow"], compression="snappy",
                   row_group_size=rg, write_page_index=True,
                   use_dictionary=DICT_COLS)
    if args.chips == 1:
        paths["ours"] = os.path.join(data_dir, "lineitem_ours.parquet")
        # the writer takes strings and dictionary-encodes them itself
        write_table(plain(table), paths["ours"],
                    WriterOptions(compression="snappy", data_page_version=2,
                                  row_group_size=row_group(args.rows),
                                  dictionary=True))
    step = -(-args.rows // N_FILES)
    parts = []
    for i in range(N_FILES):
        p = os.path.join(data_dir, f"part-{i}.parquet")
        pq.write_table(table.slice(i * step, step), p, compression="snappy",
                       row_group_size=row_group(args.rows),
                       write_page_index=True, use_dictionary=DICT_COLS)
        parts.append(p)
    paths["parts"] = parts
    for k, v in paths.items():
        for p in ([v] if isinstance(v, str) else v):
            say(f"wrote {k} {os.path.basename(p)} {os.path.getsize(p)} bytes")
    return paths, table


# --------------------------------------------------------------------------
# comparison helpers
# --------------------------------------------------------------------------

def plain(table):
    """One chunk per column, dictionaries decoded: the form both sides of a
    comparison are brought to before ``equals``."""
    import pyarrow as pa

    cols = []
    for c in table.columns:
        c = c.combine_chunks()
        if pa.types.is_dictionary(c.type):
            c = c.dictionary_decode()
        if pa.types.is_large_string(c.type):
            c = c.cast(pa.string())
        cols.append(c)
    return pa.table(cols, names=table.column_names)


def same(what, got, want):
    got, want = plain(got), plain(want)
    if got.column_names != want.column_names:
        raise AssertionError(f"{what}: columns {got.column_names} != "
                             f"{want.column_names}")
    bad = [n for n in want.column_names
           if not got.column(n).equals(want.column(n))]
    if bad or got.num_rows != want.num_rows:
        raise AssertionError(f"{what}: differs in {bad} "
                             f"(rows {got.num_rows} vs {want.num_rows})")
    say(f"ok {what}: {want.num_rows} rows x {want.num_columns} columns "
        "equal")


def part_array(col):
    """A decoded part's data array: its values, or its dictionary indices
    when the values stay encoded."""
    return col.values if col.values is not None else col.dict_indices


def merged_columns(what, tbl, want):
    """``tbl[path]`` joins each column's parts into one Column, on the
    device when they are device arrays (from several devices after a mesh
    read): a fixed-width and a dictionary column, each vs pyarrow."""
    import pyarrow as pa

    for path in ("l_extendedprice", "l_returnflag"):
        same(f"{what} merged {path}", pa.table({path: tbl[path].to_arrow()}),
             want.select([path]))


def column_routes(table):
    """Per column: where each decoded part lives ('device' when its data
    array is a jax.Array, else 'host')."""
    import jax

    return {path: "+".join(sorted({
        "device" if isinstance(part_array(c), jax.Array) else "host"
        for c in parts})) for path, parts in table._parts.items()}


def devices_of(table):
    """Per column: the ids of the devices holding each part."""
    return {path: [{d.id for d in part_array(c).devices()} for c in parts]
            for path, parts in table._parts.items()}


# --------------------------------------------------------------------------
# queries (one chip)
# --------------------------------------------------------------------------

def full_read(paths):
    import pyarrow.parquet as pq

    from parquet_tpu import ParquetFile

    for key in ("ours", "pyarrow"):
        tbl = ParquetFile(paths[key]).read(device=True)
        routes = column_routes(tbl)
        say(f"routes {key}: {json.dumps(routes)}")
        host = [p for p, r in routes.items() if r != "device"]
        if host:
            raise AssertionError(f"full read of {key}: columns not "
                                 f"device-resident: {host}")
        same(f"full device read of {key} file", tbl.to_arrow(),
             pq.read_table(paths[key]))
        del tbl


def dataset_read(paths):
    import pyarrow.parquet as pq

    from parquet_tpu import Dataset

    tbl = Dataset(paths["parts"]).read(device=True)
    routes = column_routes(tbl)
    host = [p for p, r in routes.items() if r != "device"]
    if host:
        raise AssertionError(f"dataset read: columns not device-resident: "
                             f"{host}")
    want = pq.read_table(paths["pyarrow"])
    same(f"Dataset.read(device=True) over {N_FILES} files", tbl.to_arrow(),
         want)
    merged_columns("Dataset.read(device=True)", tbl, want)


def q6_scan(paths, table):
    """TPC-H Q6 shape: the shipdate range is the routed pushdown ``scan``;
    discount and quantity filter the survivors on the device; revenue =
    sum(extendedprice * discount)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pyarrow.compute as pc

    from parquet_tpu import ParquetFile, op_scope, scan

    pf = ParquetFile(paths["pyarrow"])
    cols = ["l_discount", "l_quantity", "l_extendedprice"]
    with op_scope("chip_smoke.q6") as op:
        out = scan(pf, "l_shipdate", Q6_DAYS[0], Q6_DAYS[1], columns=cols)
    rep = op.report()
    say(f"q6 routes={rep['routes']} rows_decoded={rep['rows_decoded']} "
        f"rows_pruned={rep['rows_pruned']}")
    if rep["routes"] != {"device": 1}:
        raise AssertionError(f"q6 scan did not take the device route: "
                             f"{rep['routes']}")
    if not all(isinstance(out[c], jax.Array) for c in cols):
        raise AssertionError("q6 scan returned host arrays")

    def f64(pairs):
        return jax.lax.bitcast_convert_type(pairs, jnp.float64)

    @jax.jit
    def q6(disc_p, qty_p, price_p):
        disc, price = f64(disc_p), f64(price_p)
        qty = jax.lax.bitcast_convert_type(qty_p, jnp.int64)
        keep = ((disc >= Q6_DISC[0]) & (disc <= Q6_DISC[1])
                & (qty < Q6_QTY))
        return keep, jnp.sum(jnp.where(keep, price * disc, 0.0))

    keep, revenue = q6(out["l_discount"], out["l_quantity"],
                       out["l_extendedprice"])
    keep = np.asarray(keep)
    want = table.filter(
        (pc.field("l_shipdate") >= Q6_DAYS[0])
        & (pc.field("l_shipdate") <= Q6_DAYS[1])
        & (pc.field("l_discount") >= Q6_DISC[0])
        & (pc.field("l_discount") <= Q6_DISC[1])
        & (pc.field("l_quantity") < Q6_QTY))
    # survivors compare as their raw bits: the TPU has no native f64, so a
    # float64 read back from the device is not bit-exact (my chip run,
    # PR 21); the u32 pairs are
    for c, dtype in (("l_extendedprice", np.float64),
                     ("l_discount", np.float64), ("l_quantity", np.int64)):
        got_c = np.ascontiguousarray(
            np.asarray(out[c])[keep]).view(dtype).ravel()
        if not np.array_equal(got_c, want.column(c).to_numpy()):
            raise AssertionError(f"q6 survivors differ in {c}: "
                                 f"{len(got_c)} vs {want.num_rows} rows")
    want_rev = float(np.sum(want.column("l_extendedprice").to_numpy()
                            * want.column("l_discount").to_numpy()))
    np.testing.assert_allclose(float(revenue), want_rev, rtol=FLOAT_RTOL)
    say(f"ok q6: {want.num_rows} qualifying rows equal pyarrow, revenue "
        f"{float(revenue)!r} vs {want_rev!r} (relative error "
        f"{abs(float(revenue) - want_rev) / abs(want_rev):.3g})")


Q1_FLAGS, Q1_STATUS = "ANR", "FO"


def q1_aggregates(gid, qty, price, disc, tax, valid, n_groups=6):
    """Q1's sums per (returnflag, linestatus) group, 64-bit on device."""
    import jax
    import jax.numpy as jnp

    def seg(x):
        return jax.ops.segment_sum(jnp.where(valid, x, 0), gid,
                                   num_segments=n_groups)

    disc_price = price * (1.0 - disc)
    return {"count": seg(valid.astype(jnp.int64)),
            "sum_qty": seg(qty),
            "sum_base_price": seg(price),
            "sum_disc_price": seg(disc_price),
            "sum_charge": seg(disc_price * (1.0 + tax))}


def q1_pairs_to_64(qty_p, price_p, disc_p, tax_p):
    import jax
    import jax.numpy as jnp

    f64 = lambda p: jax.lax.bitcast_convert_type(p, jnp.float64)  # noqa: E731
    return (jax.lax.bitcast_convert_type(qty_p, jnp.int64), f64(price_p),
            f64(disc_p), f64(tax_p))


def q1_reference(table):
    """Q1's sums by pyarrow ``group_by`` on the generated table."""
    import numpy as np
    import pyarrow.compute as pc

    t = plain(table)
    t = t.append_column("disc_price", pc.multiply(
        t.column("l_extendedprice"),
        pc.subtract(1.0, t.column("l_discount"))))
    t = t.append_column("charge", pc.multiply(
        t.column("disc_price"), pc.add(1.0, t.column("l_tax"))))
    g = t.group_by(["l_returnflag", "l_linestatus"]).aggregate(
        [("l_quantity", "count"), ("l_quantity", "sum"),
         ("l_extendedprice", "sum"), ("disc_price", "sum"),
         ("charge", "sum")])
    out = {}
    for row in g.to_pylist():
        gid = (Q1_FLAGS.index(row["l_returnflag"]) * 2
               + Q1_STATUS.index(row["l_linestatus"]))
        out[gid] = np.array([row["l_quantity_count"], row["l_quantity_sum"],
                             row["l_extendedprice_sum"],
                             row["disc_price_sum"], row["charge_sum"]])
    return out


def q1_check(what, got, want):
    """Counts and quantity sums exactly; float sums to ``FLOAT_RTOL``."""
    import numpy as np

    worst = 0.0
    for gid, ref in want.items():
        exact = [int(got["count"][gid]), int(got["sum_qty"][gid])]
        if exact != [int(ref[0]), int(ref[1])]:
            raise AssertionError(f"{what} group {gid}: count/sum_qty "
                                 f"{exact} != {ref[:2].tolist()}")
        sums = np.array([float(got[k][gid]) for k in (
            "sum_base_price", "sum_disc_price", "sum_charge")])
        np.testing.assert_allclose(sums, ref[2:], rtol=FLOAT_RTOL,
                                   err_msg=f"{what} group {gid}")
        worst = max(worst, float(np.max(np.abs(sums - ref[2:])
                                        / np.abs(ref[2:]))))
    say(f"ok {what}: {len(want)} groups equal pyarrow (counts and "
        f"sum_qty exact, float sums within {worst:.3g} relative)")


def q1_single(paths, want):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from parquet_tpu import ParquetFile, read_pytree

    cols = read_pytree(ParquetFile(paths["pyarrow"]), device=True,
                       columns=["l_returnflag", "l_linestatus", "l_quantity",
                                "l_extendedprice", "l_discount", "l_tax"])

    def entries(d):
        v, o = np.asarray(d[0]), np.asarray(d[1], np.int64)
        return [bytes(v[o[i]:o[i + 1]]).decode() for i in range(len(o) - 1)]

    # a multi-row-group file carries a rebased concat of the per-group
    # dictionaries, so map every entry to its group code on the host and
    # remap ids on the device (examples/tpch_q1_tpu.py:run_single)
    fmap = jnp.asarray(np.array(
        [Q1_FLAGS.index(x) for x in entries(cols["l_returnflag"]["dictionary"])],
        np.int32))
    smap = jnp.asarray(np.array(
        [Q1_STATUS.index(x) for x in entries(cols["l_linestatus"]["dictionary"])],
        np.int32))
    for c in ("l_quantity", "l_extendedprice", "l_discount", "l_tax"):
        if not isinstance(cols[c], jax.Array):
            raise AssertionError(f"q1: {c} is not device-resident")

    @jax.jit
    def q1(fidx, sidx, *pairs):
        gid = (fmap[fidx.astype(jnp.int32)] * 2
               + smap[sidx.astype(jnp.int32)])
        qty, price, disc, tax = q1_pairs_to_64(*pairs)
        return q1_aggregates(gid, qty, price, disc, tax,
                             jnp.ones(gid.shape, bool))

    out = q1(cols["l_returnflag"]["indices"], cols["l_linestatus"]["indices"],
             cols["l_quantity"], cols["l_extendedprice"], cols["l_discount"],
             cols["l_tax"])
    got = jax.tree_util.tree_map(np.asarray, out)
    q1_check("q1 (read_pytree + jitted segment_sum)", got, want)
    return got


def round_trip(paths, table):
    import pyarrow.parquet as pq

    same("our file read by pyarrow", pq.read_table(paths["ours"]), table)


def counters_snapshot():
    from parquet_tpu import counters, metrics_snapshot

    return metrics_snapshot(), counters.snapshot()


def check_nothing_hidden(before, noise):
    """Phase 4: device decodes happened; no fallback, refusal, or logged
    exception hid the device."""
    from parquet_tpu import counters, metrics_delta, metrics_snapshot

    delta = metrics_delta(before[0], metrics_snapshot())["counters"]
    dbg = {k: v - before[1].get(k, 0)
           for k, v in counters.snapshot().items()
           if v != before[1].get(k, 0)}
    say(f"counters {json.dumps(dbg, sort_keys=True)}")
    say("metrics " + json.dumps(
        {k: v for k, v in delta.items()
         if k.startswith(("device.", "route.", "chunks_"))}, sort_keys=True))
    problems = []
    if dbg.get("chunks_device_decoded", 0) == 0:
        problems.append("chunks_device_decoded == 0")
    if dbg.get("chunks_host_fallback", 0):
        problems.append(f"chunks_host_fallback = "
                        f"{dbg['chunks_host_fallback']}")
    refused = {k: v for k, v in delta.items()
               if k.startswith("device.route_refusals")}
    if refused:
        problems.append(f"device route refusals {refused}")
    problems += noise.seen
    if problems:
        raise AssertionError("hidden fallbacks: " + "; ".join(problems))
    say(f"ok nothing hidden: {dbg['chunks_device_decoded']} chunks decoded "
        "on the device, 0 host fallbacks, 0 refusals, 0 package warnings")


# --------------------------------------------------------------------------
# queries (four chips)
# --------------------------------------------------------------------------

def mesh_dataset(paths, devs):
    """8 files round-robined over the mesh: file i lives on device i % n."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from parquet_tpu import Dataset, ParquetFile

    tbl = Dataset(paths["parts"]).read(device=True)
    rg_per_file = [len(ParquetFile(p).metadata.row_groups)
                   for p in paths["parts"]]
    for path, devsets in devices_of(tbl).items():
        k = 0
        for i, n_rg in enumerate(rg_per_file):
            want = {devs[i % len(devs)].id}
            for d in devsets[k:k + n_rg]:
                if d != want:
                    raise AssertionError(f"dataset: {path} of file {i} on "
                                         f"devices {d}, want {want}")
            k += n_rg
    spread = {d for ds in devices_of(tbl).values() for s in ds for d in s}
    if len(spread) != len(devs):
        raise AssertionError(f"dataset spans devices {spread}")
    say(f"dataset parts placed file i -> device i % {len(devs)} "
        f"(devices {sorted(spread)})")
    got = tbl.to_arrow()
    one = pa.concat_tables(
        [plain(ParquetFile(p).read(device=True).to_arrow())
         for p in paths["parts"]])  # default device: the one-chip answer
    want = pq.read_table(paths["pyarrow"])
    same("mesh Dataset.read(device=True) vs one chip", got, one)
    same("mesh Dataset.read(device=True) vs pyarrow", got, want)
    merged_columns("mesh Dataset.read(device=True)", tbl, want)


def mesh_sharded(paths, devs, want_q1):
    """read_table_sharded over all chips vs one chip vs pyarrow, then
    sharded Q1 over the index streams of the unified dictionaries."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from parquet_tpu import ParquetFile, default_mesh, read_table_sharded

    n_rg = len(ParquetFile(paths["pyarrow"]).metadata.row_groups)
    if n_rg < 4 * len(devs):
        raise AssertionError(f"{n_rg} row groups < 4 per chip")
    st = read_table_sharded(paths["pyarrow"], mesh=default_mesh())
    for path, arr in st.arrays.items():
        if len(arr.sharding.device_set) != len(devs):
            raise AssertionError(f"sharded {path} spans "
                                 f"{arr.sharding.device_set}")
    routes = {c: "device" for c in st.arrays}
    routes.update({c: "host-ragged (PLAIN strings ship as the ragged "
                      "pair by design)" for c in st.ragged})
    say(f"routes read_table_sharded: {json.dumps(routes)}")
    say(f"read_table_sharded: {n_rg} row groups over {len(devs)} chips, "
        f"every device column spans {len(devs)} devices")
    one = read_table_sharded(paths["pyarrow"], mesh=default_mesh(1))
    # row groups go round-robin over the chips, and each shard holds its
    # groups in file order: the global rows come in that order
    order = [g for d in range(len(devs)) for g in range(d, n_rg, len(devs))]
    meta = pq.ParquetFile(paths["pyarrow"]).metadata
    starts = np.cumsum([0] + [meta.row_group(g).num_rows
                              for g in range(n_rg)])
    one_tbl = one.to_arrow()
    one_in_shard_order = pa.concat_tables(
        [one_tbl.slice(starts[g], starts[g + 1] - starts[g]) for g in order])
    got = st.to_arrow()
    same("read_table_sharded on 4 chips vs one chip", got,
         one_in_shard_order)
    same("read_table_sharded on 4 chips vs pyarrow", got,
         pq.ParquetFile(paths["pyarrow"]).read_row_groups(order))
    same("read_table_sharded on one chip vs pyarrow", one_tbl,
         pq.read_table(paths["pyarrow"]))

    def sharded_q1(t):
        flag = t.arrays["l_returnflag"]
        status = t.arrays["l_linestatus"]
        # unified dictionaries: map dictionary ids to Q1's group codes
        codes = {}
        for c, names in (("l_returnflag", Q1_FLAGS),
                         ("l_linestatus", Q1_STATUS)):
            n = len(t.dictionaries[c][1]) - 1
            codes[c] = jnp.asarray(np.array(
                [names.index(s.decode())
                 for s in t.lookup_strings(c, range(n))], np.int32))

        @jax.jit
        def q1(flag, status, valid, *pairs):
            gid = codes["l_returnflag"][flag] * 2 + codes["l_linestatus"][status]
            qty, price, disc, tax = q1_pairs_to_64(*pairs)
            return q1_aggregates(gid, qty, price, disc, tax, valid)

        out = q1(flag, status, t.row_mask(), t.arrays["l_quantity"],
                 t.arrays["l_extendedprice"], t.arrays["l_discount"],
                 t.arrays["l_tax"])
        return jax.tree_util.tree_map(np.asarray, out)

    got4, got1 = sharded_q1(st), sharded_q1(one)
    q1_check("sharded q1 on 4 chips", got4, want_q1)
    q1_check("sharded q1 on one chip", got1, want_q1)
    for k in ("count", "sum_qty"):
        if not np.array_equal(got4[k], got1[k]):
            raise AssertionError(f"sharded q1 {k}: 4 chips != one chip")
    say("ok sharded q1: 4 chips equal one chip")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=SF1_ROWS,
                    help="lineitem rows (default: SF1); any other count "
                         "is a rehearsal: exits 3 without a result line")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh paths, on four chips")
    args = ap.parse_args(argv)
    data_dir = os.path.join(HERE, ".smoke_data")  # in .gitignore

    import jax

    phases = Phases()
    devs = phases.run("setup", setup, args)
    if args.chips == 4:
        devs = devs[:4]
    shutil.rmtree(data_dir, ignore_errors=True)
    try:
        paths, table = phases.run("load", load, args, data_dir)
        want_q1 = q1_reference(table)
        noise = PackageNoise()
        before = counters_snapshot()
        if args.chips == 1:
            phases.run("full_read", full_read, paths)
            phases.run("dataset_read", dataset_read, paths)
            phases.run("q6_scan", q6_scan, paths, table)
            phases.run("q1", q1_single, paths, want_q1)
            phases.run("round_trip", round_trip, paths, table)
        else:
            phases.run("mesh_dataset", mesh_dataset, paths, devs)
            phases.run("mesh_sharded", mesh_sharded, paths, devs, want_q1)
        phases.run("check", check_nothing_hidden, before, noise)
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    say("phases " + json.dumps(phases.rows))
    dev0 = jax.devices()[0]
    if args.rows != SF1_ROWS or dev0.platform != "tpu":
        say(f"rehearsal ({args.rows} rows on {dev0.platform}) passed; the "
            "result line comes only from SF1 on a TPU")
        return 3
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
