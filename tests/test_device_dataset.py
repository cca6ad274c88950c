"""Device-scale dataset reads (parallel/mesh.py:read_dataset_device +
Dataset.read/scan(device=True)): byte identity with the host path across
encodings × nulls × multi-file on the emulated mesh, overlap knob parity,
refusal/fallback accounting, corrupt-file skip parity, and device.staging
ledger hygiene under concurrency."""

import os
import threading
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax

from parquet_tpu import Dataset, FaultPolicy, ReadReport, clear_caches
from parquet_tpu.errors import CorruptedError
from parquet_tpu.obs.ledger import ledger_account, ledger_snapshot
from parquet_tpu.obs.metrics import metrics_delta, metrics_snapshot

N_FILES = 4
ROWS = 3000
RG = 1000  # 3 row groups per file


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    clear_caches(reset_stats=True)
    monkeypatch.delenv("PARQUET_TPU_DEVICE_OVERLAP", raising=False)
    yield
    clear_caches(reset_stats=True)


def _mixed_corpus(tmp_path, n_files=N_FILES, rows=ROWS):
    """Multi-file corpus covering the widened decode surface: dictionary
    strings, PLAIN fixed-width, DELTA_BINARY_PACKED ints, DELTA_BYTE_ARRAY
    front-coded strings, BYTE_STREAM_SPLIT floats — each × a nulls
    column."""
    paths = []
    for i in range(n_files):
        base = i * rows
        t = pa.table({
            "plain_i64": pa.array(
                np.arange(base, base + rows, dtype=np.int64)),
            "plain_f32": pa.array(
                (np.arange(rows) * 0.5 + i).astype(np.float32)),
            "dict_s": pa.array([f"f{i}_tag{j % 41}" for j in range(rows)]),
            "delta_i": pa.array(np.cumsum(
                np.random.default_rng(i).integers(0, 9, rows))),
            "dba_s": pa.array([f"prefix/shared/f{i}/{j % 173:06d}"
                               for j in range(rows)]),
            "bss_f": pa.array(np.random.default_rng(i).random(rows)),
            "nul_f": pa.array([None if j % 7 == 0 else float(base + j)
                               for j in range(rows)]),
            "nul_s": pa.array([None if j % 11 == 0 else f"n{j % 53}"
                               for j in range(rows)]),
        })
        p = os.path.join(tmp_path, f"part-{i:02d}.parquet")
        pq.write_table(
            t, p, row_group_size=rows // 3,
            use_dictionary=["dict_s", "nul_s"],
            column_encoding={"delta_i": "DELTA_BINARY_PACKED",
                             "dba_s": "DELTA_BYTE_ARRAY",
                             "bss_f": "BYTE_STREAM_SPLIT",
                             "plain_i64": "PLAIN", "plain_f32": "PLAIN",
                             "nul_f": "PLAIN"})
        paths.append(p)
    return paths


# ---------------------------------------------------------------------------
# byte identity — encodings × nulls × multi-file on the emulated mesh
# ---------------------------------------------------------------------------


def test_mesh_has_multiple_devices():
    # conftest forces the 8-device CPU mesh; the round-robin tests below
    # are vacuous on a single device
    assert len(jax.devices()) >= 4


def test_device_read_byte_identical_across_encodings(tmp_path):
    paths = _mixed_corpus(tmp_path)
    ds = Dataset(paths)
    want = ds.read().to_arrow()
    before = metrics_snapshot()
    got = ds.read(device=True).to_arrow()
    delta = metrics_delta(before, metrics_snapshot())
    assert got.equals(want)
    # every file really took the sharded device route (no silent host
    # rerouting of the whole corpus)
    assert delta["counters"].get("device.files_sharded", 0) == N_FILES


def test_device_read_column_selection_and_single_file(tmp_path):
    paths = _mixed_corpus(tmp_path, n_files=1)
    ds = Dataset(paths)
    cols = ["dict_s", "nul_f", "bss_f"]
    want = ds.read(columns=cols).to_arrow()
    assert ds.read(columns=cols, device=True).to_arrow().equals(want)


# ---------------------------------------------------------------------------
# overlap knob — stage N+1 vs decode N double buffering
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["0", "auto", "force"])
def test_overlap_modes_byte_identical(tmp_path, monkeypatch, mode):
    paths = _mixed_corpus(tmp_path)
    ds = Dataset(paths)
    want = ds.read().to_arrow()
    monkeypatch.setenv("PARQUET_TPU_DEVICE_OVERLAP", mode)
    before = metrics_snapshot()
    got = ds.read(device=True).to_arrow()
    delta = metrics_delta(before, metrics_snapshot())
    assert got.equals(want)
    overlapped = delta["counters"].get("device.stage_overlapped", 0)
    if mode == "0":
        assert overlapped == 0
    else:
        # N files pipeline as stage(i+1) ∥ decode(i): every file but the
        # first overlaps
        assert overlapped == N_FILES - 1


# ---------------------------------------------------------------------------
# refusal accounting — unsupported files fall back per file, host-identical
# ---------------------------------------------------------------------------


def test_unsupported_encoding_falls_back_with_accounting(tmp_path,
                                                         monkeypatch):
    paths = _mixed_corpus(tmp_path)
    ds = Dataset(paths)
    want = ds.read().to_arrow()
    from parquet_tpu.io import planner

    real = planner.device_encoding_supported
    refused = []

    def deny_even(pf, columns=None):
        i = paths.index(pf._path)
        if i % 2 == 0:
            refused.append(i)
            return False, "test: encoding denied"
        return real(pf, columns)

    monkeypatch.setattr(planner, "device_encoding_supported", deny_even)
    before = metrics_snapshot()
    got = ds.read(device=True).to_arrow()
    delta = metrics_delta(before, metrics_snapshot())
    assert got.equals(want)
    assert sorted(set(refused)) == [0, 2]
    key = "device.route_refusals{reason=unsupported}"
    assert delta["counters"].get(key, 0) == 2
    assert delta["counters"].get("device.files_sharded", 0) == N_FILES - 2
    # the refusals surface in the /debugz routes section
    from parquet_tpu.obs.export import debugz_snapshot

    recent = debugz_snapshot()["routes"]["refusals_recent"]
    assert any(r["reason"] == "unsupported" for r in recent)


# ---------------------------------------------------------------------------
# corrupt-file parity — degraded policy semantics match the host path
# ---------------------------------------------------------------------------


def test_corrupt_file_skip_parity_with_host(tmp_path):
    paths = _mixed_corpus(tmp_path)
    # poison one data page of file 1: the device stage dies on it and the
    # per-file host fallback applies the row-group skip
    meta = pq.ParquetFile(paths[1]).metadata
    off = meta.row_group(1).column(0).data_page_offset
    raw = bytearray(open(paths[1], "rb").read())
    for o in (off, off + 1, off + 2):
        raw[o] ^= 0xFF
    open(paths[1], "wb").write(bytes(raw))

    skip = FaultPolicy(backoff_s=0.0, on_corrupt="skip_row_group")
    rep_h, rep_d = ReadReport(), ReadReport()
    host = Dataset(paths, policy=skip).read(report=rep_h)
    dev = Dataset(paths, policy=skip).read(report=rep_d, device=True)
    assert dev.to_arrow().equals(host.to_arrow())
    assert rep_d.files_skipped == rep_h.files_skipped
    assert rep_d.row_groups_skipped == rep_h.row_groups_skipped
    assert rep_d.rows_dropped == rep_h.rows_dropped
    # without a degraded policy both paths fail loudly
    with pytest.raises(CorruptedError):
        Dataset(paths).read(device=True)


def test_corrupt_footer_drops_file_as_unit(tmp_path):
    paths = _mixed_corpus(tmp_path)
    bad = bytearray(open(paths[2], "rb").read())
    bad[-1] ^= 0xFF
    open(paths[2], "wb").write(bytes(bad))
    skip = FaultPolicy(backoff_s=0.0, on_corrupt="skip_row_group")
    rep = ReadReport()
    got = Dataset(paths, policy=skip).read(report=rep, device=True)
    assert rep.files_skipped == [paths[2]]
    want = Dataset([p for p in paths if p != paths[2]]).read().to_arrow()
    assert got.to_arrow().equals(want)


# ---------------------------------------------------------------------------
# scan(device=True) — per-file device round-robin, identical results
# ---------------------------------------------------------------------------


def test_device_scan_matches_host_scan(tmp_path):
    paths = _mixed_corpus(tmp_path)
    ds = Dataset(paths)
    lo, hi = ROWS // 2, 3 * ROWS
    host = ds.scan(path="plain_i64", lo=lo, hi=hi)
    dev = ds.scan(path="plain_i64", lo=lo, hi=hi, device=True)
    assert sorted(host) == sorted(dev)
    for k in host:
        if isinstance(host[k], list):
            assert host[k] == dev[k]
        else:
            np.testing.assert_array_equal(np.asarray(host[k]),
                                          np.asarray(dev[k]))


# ---------------------------------------------------------------------------
# device.staging ledger — admitted, bounded, drains to zero under load
# ---------------------------------------------------------------------------


def _staging_resident():
    snap = ledger_snapshot()
    accounts = snap.get("accounts", snap)
    ent = accounts.get("device.staging", {})
    return int(ent.get("resident_bytes", ent.get("resident", 0)))


def test_staging_ledger_drains_under_hammer(tmp_path, monkeypatch):
    paths = _mixed_corpus(tmp_path)
    ds = Dataset(paths)
    want = ds.read().to_arrow()
    monkeypatch.setenv("PARQUET_TPU_READ_BUDGET", str(64 << 20))
    from parquet_tpu.utils.pool import read_admission

    adm = read_admission()
    adm._reset()
    acct = ledger_account("device.staging")
    high = {"n": 0}
    stop = threading.Event()

    def watch():
        while not stop.is_set():
            high["n"] = max(high["n"], _staging_resident())
            stop.wait(0.002)

    watcher = threading.Thread(target=watch)
    watcher.start()
    errors = []

    def hammer(i):
        try:
            t = ds.read(device=True).to_arrow()
            assert t.equals(want)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    try:
        with ThreadPoolExecutor(max_workers=8) as pool:
            list(pool.map(hammer, range(8)))
    finally:
        stop.set()
        watcher.join()
    assert not errors
    assert _staging_resident() == 0
    # the account really carried bytes while reads were in flight, and
    # admission never let staging exceed the configured budget
    assert high["n"] > 0
    assert adm.high_water <= (64 << 20)


def test_staging_admission_single_read_accounts(tmp_path, monkeypatch):
    paths = _mixed_corpus(tmp_path, n_files=2)
    ds = Dataset(paths)
    monkeypatch.setenv("PARQUET_TPU_READ_BUDGET", str(64 << 20))
    from parquet_tpu.utils.pool import read_admission

    adm = read_admission()
    adm._reset()
    ds.read(device=True)
    assert _staging_resident() == 0
    assert adm.high_water > 0  # staging really passed the admission gate


# ---------------------------------------------------------------------------
# route history — device_mesh bucketed per mesh size
# ---------------------------------------------------------------------------


def test_route_history_mesh_size_bucketing():
    from parquet_tpu.io.planner import RouteHistory

    h = RouteHistory()
    h.observe("device_mesh", 64 << 20, 1.0, mesh_size=4)
    h.observe("device", 64 << 20, 2.0)  # mesh_size 1: bare legacy key
    assert h.gbps("device_mesh", mesh_size=4) is not None
    assert h.gbps("device_mesh") is None  # distinct bucket
    assert h.gbps("device") is not None
    snap = h.snapshot()
    assert "device_mesh@4" in snap and "device" in snap


# ---------------------------------------------------------------------------
# merged columns — file i's parts sit on device i % n; Table.columns and
# tbl[path] concatenate them across devices
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("path", ["plain_i64", "plain_f32", "dict_s",
                                  "bss_f", "nul_f"])
def test_device_read_merged_column_across_devices(tmp_path, monkeypatch,
                                                  path):
    ds = Dataset(_mixed_corpus(tmp_path))
    want = ds.read(columns=[path])[path].to_arrow()
    # on the CPU the run routers pick the host; pin the device decodes
    for knob in ("PLAIN", "DICT", "BSS"):
        monkeypatch.setenv(f"PARQUET_TPU_{knob}_RUNS", "device")
    tbl = ds.read(columns=[path], device=True)
    # the parts really are spread over the mesh, or this test is vacuous
    devs = set()
    for part in tbl._parts[path]:
        for arr in (part.values, part.dict_indices):
            if isinstance(arr, jax.Array):
                devs |= arr.sharding.device_set
    assert len(devs) > 1
    assert tbl[path].to_arrow().equals(want)
