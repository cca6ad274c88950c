"""A single stream of TPC-H Q6 (spec 2.4.6; the power test's one stream).

Each query draws DATE (1 January of 1993-1997), DISCOUNT (0.02-0.09) and
QUANTITY (24 or 25) from the seed, calls the program's routed
``scan(pf, "l_shipdate", lo, hi, columns=[discount, quantity, price])`` and
finishes with the benchmark's own reduction (copied from
``chip_smoke.q6_scan``) on the device: the device route's survivors are
there already, the host route's move there as a JAX user would move them.

Correctness: every query's revenue is compared with numpy's over the
generated table; a sample of queries drawn from the seed also compares its
survivors bit for bit.  The control computes the same revenue in float32.
"""

import datetime

import numpy as np

from lib.rows import rng_for

COLS = ["l_discount", "l_quantity", "l_extendedprice"]
DTYPES = [np.float64, np.int64, np.float64]
EPOCH = datetime.date(1970, 1, 1)


def _day(y, m, d):
    return (datetime.date(y, m, d) - EPOCH).days


def year_range(year):
    """[1 January, 31 December] of ``year`` as inclusive day numbers."""
    return _day(year, 1, 1), _day(year + 1, 1, 1) - 1


def pairs(values, dtype):
    """64-bit host values as the (n, 2) uint32 pairs the device route
    returns: the TPU holds no float64 exactly, so a float64 scalar or array
    put on it is rounded (my chip run, PR 22), while pairs bitcast inside
    a program are exact."""
    import jax.numpy as jnp

    a = np.ascontiguousarray(np.asarray(values, dtype).reshape(-1))
    return jnp.asarray(a.view(np.uint32).reshape(-1, 2))


def _reduce_fn(dtype):
    import jax
    import jax.numpy as jnp

    def f64(p):
        return jax.lax.bitcast_convert_type(p, jnp.float64)

    @jax.jit
    def q6(disc_p, qty_p, price_p, params_p, qty_max_p):
        disc, price = f64(disc_p), f64(price_p)
        qty = jax.lax.bitcast_convert_type(qty_p, jnp.int64)
        d_lo, d_hi = f64(params_p)[0], f64(params_p)[1]
        qty_max = jax.lax.bitcast_convert_type(qty_max_p, jnp.int64)[0]
        keep = (disc >= d_lo) & (disc <= d_hi) & (qty < qty_max)
        rev = price.astype(dtype) * disc.astype(dtype)
        return keep, jnp.sum(jnp.where(keep, rev, jnp.zeros((), dtype)))

    return q6


class Traffic:
    def __init__(self, ctx):
        import jax.numpy as jnp

        from parquet_tpu import ParquetFile

        self.ctx = ctx
        t = ctx.traffic
        self.years = list(range(t["year_first"], t["year_last"] + 1))
        self.discounts = list(range(t["discount_cents_first"],
                                    t["discount_cents_last"] + 1))
        self.quantities = list(t["quantities"])
        self.rng = rng_for(ctx.seed)
        self.sample_rng = rng_for(ctx.seed + 1)
        self.keep = t.get("keep_queries", 2)
        self.pf = ParquetFile(ctx.data)
        self.reduce = _reduce_fn(jnp.float32 if ctx.control else jnp.float64)
        self.answers = []  # (year, cents, qty, route, revenue)
        self.kept = []  # (query index, params, scan output, keep mask)
        self.seen = 0
        self.work_bytes = None

    def params(self):
        return (self.years[int(self.rng.integers(len(self.years)))],
                self.discounts[int(self.rng.integers(len(self.discounts)))],
                self.quantities[int(self.rng.integers(len(self.quantities)))])

    def query(self, year, cents, qty):
        """One Q6: the routed scan, then the reduction on the device;
        returns the route, the revenue, the reduction's inputs and the
        survivor mask."""
        import jax

        from parquet_tpu import op_scope, scan

        lo, hi = year_range(year)
        with jax.profiler.TraceAnnotation("bench.scan"):
            with op_scope("bench.q6") as op:
                out = scan(self.pf, "l_shipdate", lo, hi, columns=COLS)
        routes = op.report()["routes"]
        d_lo, d_hi = (cents - 1) / 100, (cents + 1) / 100
        with jax.profiler.TraceAnnotation("bench.q6_reduce"):
            # the device route returns 64-bit columns as (n, 2) uint32
            # pairs, the host route as numpy: a JAX user moves those to the
            # device in the same form, so one program per year serves both
            cols = [out[c] if isinstance(out[c], jax.Array) else
                    pairs(out[c], dtype) for c, dtype in zip(COLS, DTYPES)]
            keep, rev = self.reduce(*cols, pairs([d_lo, d_hi], np.float64),
                                    pairs([qty], np.int64))
            revenue = float(rev)
        route = "device" if routes == {"device": 1} else "host"
        return route, revenue, cols, keep

    def warm(self):
        """Every year's shapes on both routes (a year's survivors, so its
        shapes, are fixed by the data), then the planner's history cleared:
        the window starts from a fresh process's route choice, whatever the
        warm-up's compiles taught it (PERF.md)."""
        import os

        from parquet_tpu import route_history

        pinned = os.environ.get("PARQUET_TPU_ROUTE")
        try:
            for route in ("device", "host"):
                os.environ["PARQUET_TPU_ROUTE"] = route
                for year in self.years:
                    self.query(year, self.discounts[0], self.quantities[0])
        finally:
            os.environ.pop("PARQUET_TPU_ROUTE")
            if pinned is not None:
                os.environ["PARQUET_TPU_ROUTE"] = pinned
        route_history().reset()

    def request(self, i):
        year, cents, qty = self.params()
        route, revenue, cols, keep = self.query(year, cents, qty)
        self.answers.append((year, cents, qty, route, revenue))
        self.seen += 1
        item = (i, (year, cents, qty), cols, keep)
        if len(self.kept) < self.keep:
            self.kept.append(item)
        else:
            j = int(self.sample_rng.integers(0, self.seen))
            if j < self.keep:
                self.kept[j] = item
        return {"route": route}

    def release(self):
        self.pf = None

    def check(self, control):
        t = self.ctx.table
        ship = t.column("l_shipdate").to_numpy()
        disc = t.column("l_discount").to_numpy()
        qty = t.column("l_quantity").to_numpy()
        price = t.column("l_extendedprice").to_numpy()

        def reference(year, cents, q):
            lo, hi = year_range(year)
            d_lo, d_hi = (cents - 1) / 100, (cents + 1) / 100
            return ((ship >= lo) & (ship <= hi) & (disc >= d_lo)
                    & (disc <= d_hi) & (qty < q))

        worst, refs = 0.0, {}
        for year, cents, q, _route, revenue in self.answers:
            key = (year, cents, q)
            if key not in refs:
                rows = reference(*key)
                refs[key] = float(np.sum(price[rows] * disc[rows]))
            want = refs[key]
            worst = max(worst, abs(revenue - want) / abs(want))
        bad = 0
        for _, key, cols, keep in self.kept:
            rows = reference(*key)
            keep = np.asarray(keep)
            for col, c, dtype in zip(cols, COLS, DTYPES):
                got = np.ascontiguousarray(np.asarray(col)).view(dtype)
                got = got.ravel()[keep]
                want = t.column(c).to_numpy()[rows]
                n = min(len(got), len(want))
                bad += abs(len(got) - len(want)) + int(np.count_nonzero(
                    got[:n].view(np.uint64 if dtype is np.float64
                                 else np.int64)
                    != want[:n].view(np.uint64 if dtype is np.float64
                                     else np.int64)))
        self.kept = []
        return {"revenue_rel_err": worst, "survivor_mismatches": bad}
