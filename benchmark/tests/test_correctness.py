"""The comparison that decides ``correct``, shown to fail.

Each test drives a whole run of a cell at a tiny size on the CPU, past the
harness's look for a chip, and sees ``correct`` come out false: once for the
control (the lower-precision reference in the program's place) and once for
each fault the cell can have, planted in the timed path.  A read has no
state to leave unchanged, so that fault has no case here (PERF.md)."""

import time

import numpy as np
import pytest

from lib import cell

LINEITEM = {"rows": 24_000, "row_group_rows": 4_000}
# a few of each kind of Criteo column keeps the CPU compiles short
CRITEO = {"rows": 4_096, "row_group_rows": 256, "dense_features": 2,
          "cardinalities": {"C1": 39884406, "C2": 39043, "C6": 3},
          "writer": {"library": "pyarrow", "compression": "snappy",
                     "use_dictionary": ["label", "I1", "I2", "C2", "C6"],
                     "write_page_index": False}}
SEED = 2**31 + 12345


def run(workload, cfg, **kw):
    return cell.run(workload, SEED, 0.5, 0, t_process=time.perf_counter(),
                    need_tpu=False, cfg_override=cfg, **kw)


def values(result):
    return {k: c["value"] for k, c in result["checks"].items()}


@pytest.mark.parametrize("workload,cfg", [
    ("lineitem_sf1.full_read", LINEITEM),
    ("lineitem_sf1.q6_power", LINEITEM),
    ("criteo_shard.read", CRITEO),
])
def test_sound_run_is_correct(workload, cfg):
    r = run(workload, cfg)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] >= 1


@pytest.mark.parametrize("workload,cfg,number", [
    ("lineitem_sf1.full_read", LINEITEM, "mismatched_values"),
    ("lineitem_sf1.q6_power", LINEITEM, "revenue_rel_err"),
    ("criteo_shard.read", CRITEO, "mismatched_values"),
])
def test_control_is_not_correct(workload, cfg, number):
    r = run(workload, cfg, control=True)
    assert not r["correct"]
    assert values(r)[number] > r["checks"][number]["limit"]


def _alter_read(kind):
    """A value altered where the read produces it."""
    read = kind.read

    def altered():
        table = read()
        col = table._parts["l_linenumber"][0]
        col.values = col.values.at[0].add(1)
        return table

    kind.read = altered


def _half_read(kind):
    """Half of the row groups left out."""
    from parquet_tpu import ParquetFile

    def half():
        pf = ParquetFile(kind.ctx.data)
        n = len(pf.metadata.row_groups)
        return pf.read(device=True, row_groups=list(range(n // 2)))

    kind.read = half


@pytest.mark.parametrize("fault", [_alter_read, _half_read])
def test_read_faults_are_not_correct(fault):
    r = run("lineitem_sf1.full_read", LINEITEM, patch=fault)
    assert not r["correct"]
    assert values(r)["mismatched_values"] > 0


def _scan_fault(how):
    def patch(kind):
        import parquet_tpu

        scan = parquet_tpu.scan

        def broken(*a, **k):
            out = scan(*a, **k)
            if how == "alter":
                p = out["l_extendedprice"]
                out["l_extendedprice"] = (p.at[0, 0].add(1)
                                          if hasattr(p, "at") else
                                          np.where(np.arange(len(p)) == 0,
                                                   p + 1, p))
            else:  # half of the survivors left out
                out = {c: v[: len(v) // 2] for c, v in out.items()}
            return out

        parquet_tpu.scan = broken  # the test's monkeypatch restores it

    return patch


@pytest.mark.parametrize("how", ["alter", "half"])
def test_q6_faults_are_not_correct(how, monkeypatch):
    import parquet_tpu

    monkeypatch.setattr(parquet_tpu, "scan", parquet_tpu.scan)
    r = run("lineitem_sf1.q6_power", LINEITEM, patch=_scan_fault(how))
    assert not r["correct"]
    assert (values(r)["revenue_rel_err"] > r["checks"]["revenue_rel_err"]
            ["limit"] or values(r)["survivor_mismatches"] > 0)


def test_host_resident_column_is_a_failed_read():
    """A column the read leaves on the host is a device read that failed:
    it counts in ``failed``, and the values still compare."""
    def to_host(kind):
        read = kind.read

        def partly_host():
            table = read()
            for col in table._parts["l_tax"]:
                col.values = np.asarray(col.values)
            return table

        kind.read = partly_host

    r = run("lineitem_sf1.full_read", LINEITEM, patch=to_host)
    assert r["failed"] == r["attempted"] >= 1
    assert r["correct"]
