"""Randomized route-equality soak, designed to run ON-CHIP.

The Mosaic w>=17 miscompile (MOSAIC_REPRO_ONCHIP.json) proved that a
kernel correct under CPU emulation can corrupt data on the real TPU, so
the device decode routes need equality evidence gathered on the chip
itself, not just the CI suite's forced-CPU runs.  Each trial writes a
randomized parquet file with pyarrow (encoding x codec x page version x
nullability x random sizes / page sizes), then decodes it two ways:

- the surface host read (``ParquetFile(raw).read()``),
- the device route with ``fallback=False`` (no silent host fallback may
  hide a failure),

and checks both value-equal against the pyarrow oracle.  Trials
that pyarrow itself cannot encode (extended BSS dtypes on old wheels)
are recorded as skips.  Unsupported-by-design device cases surface as
hard failures — the router is supposed to admit everything here.

Writes ``ROUTE_SOAK_<BACKEND>.json`` at the repo root:
``{"backend", "trials", "failures": [...], "skips", "seed"}``.

Usage: python scripts/route_soak.py [n_trials] [seed]
Exit 0 when every executed trial passes, 1 otherwise.

Reference parity note: this is the TPU analog of the reference's CI
running its suite twice with and without the ``purego`` tag (SURVEY.md
§4.4 — asm kernels tested against the pure-Go oracle).
"""

import io
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import pyarrow as pa  # noqa: E402
import pyarrow.parquet as pq  # noqa: E402

KINDS = [
    "plain_i64", "plain_i32", "plain_f8", "plain_f4", "plain_str",
    "dict_i64", "dict_str", "delta_i64", "delta_i32",
    "dlba_str", "dba_str", "bss_f8", "bss_f4", "bss_i4", "bss_f2",
    "list_i64", "list_str",
]
CODECS = ["none", "snappy", "zstd", "gzip", "lz4"]

def _make_table(kind: str, n: int, nullable: bool, rng):
    enc = None
    use_dict = False
    if kind == "plain_i64":
        raw = rng.integers(0, 1 << 50, n)
        enc = "PLAIN"
    elif kind == "plain_i32":
        raw = rng.integers(-(2**31), 2**31, n).astype(np.int32)
        enc = "PLAIN"
    elif kind == "plain_f8":
        raw = rng.random(n)
        enc = "PLAIN"
    elif kind == "plain_f4":
        raw = rng.random(n).astype(np.float32)
        enc = "PLAIN"
    elif kind == "plain_str":
        raw = [f"s{int(x)}" * int(1 + x % 4)
               for x in rng.integers(0, 1000, n)]
        enc = "PLAIN"
    elif kind == "dict_i64":
        raw = rng.integers(0, int(rng.integers(2, 100_000)), n)
        raw[: n // 4] = 7  # long RLE run + bit-packed spans
        use_dict = True
    elif kind == "dict_str":
        card = int(rng.integers(2, 5000))
        raw = [f"key_{int(x)}" for x in rng.integers(0, card, n)]
        use_dict = True
    elif kind == "delta_i64":
        raw = 1_000_000 + np.cumsum(rng.integers(0, 500, n))
        enc = "DELTA_BINARY_PACKED"
    elif kind == "delta_i32":
        raw = np.cumsum(rng.integers(-200, 200, n)).astype(np.int32)
        enc = "DELTA_BINARY_PACKED"
    elif kind == "dlba_str":
        raw = [f"v{int(x)}" * int(x % 5) for x in
               rng.integers(0, 10_000, n)]
        enc = "DELTA_LENGTH_BYTE_ARRAY"
    elif kind == "dba_str":
        raw = np.sort(rng.integers(0, 1 << 30, n))
        raw = [f"pfx{int(x):08d}" for x in raw]
        enc = "DELTA_BYTE_ARRAY"
    elif kind.startswith("list_"):
        # repeated columns: def/rep level streams + the nested assemblers
        lens = rng.integers(0, 7, n)
        lens[rng.random(n) < 0.1] = 0
        offs = np.zeros(n + 1, np.int32)
        np.cumsum(lens, out=offs[1:])
        total = int(offs[-1])
        if kind == "list_i64":
            inner = pa.array(rng.integers(0, int(rng.integers(2, 50_000)),
                                          max(total, 1))[:total])
            use_dict = bool(rng.random() < 0.5)
        else:
            card = int(rng.integers(2, 2000))
            inner = pa.array([f"e{int(x)}" for x in
                              rng.integers(0, card, total)])
            use_dict = True
        mask = (rng.random(n) < 0.15) if nullable else None
        v = pa.ListArray.from_arrays(
            pa.array(offs), inner,
            mask=pa.array(mask) if mask is not None else None)
        return pa.table({"c": v}), None, use_dict
    elif kind.startswith("bss_"):
        dt = {"f8": np.float64, "f4": np.float32,
              "i4": np.int32, "f2": np.float16}[kind[4:]]
        if dt is np.int32:
            raw = rng.integers(-(2**31), 2**31, n).astype(dt)
        else:
            raw = (rng.random(n) * 100 - 50).astype(dt)
        enc = "BYTE_STREAM_SPLIT"
    else:  # pragma: no cover
        raise AssertionError(kind)
    mask = (rng.random(n) < float(rng.uniform(0.01, 0.4))) if nullable \
        else None
    v = pa.array(raw, mask=mask)
    return pa.table({"c": v}), enc, use_dict


def one_trial(i: int, rng) -> dict:
    from parquet_tpu.io.reader import ParquetFile
    from parquet_tpu.parallel import device_reader as dr

    kind = KINDS[int(rng.integers(0, len(KINDS)))]
    codec = CODECS[int(rng.integers(0, len(CODECS)))]
    n = int(rng.integers(1_000, 150_000))
    nullable = bool(rng.random() < 0.4)
    v2 = bool(rng.random() < 0.5)
    page_kb = int(rng.choice([4, 16, 64, 256, 1024]))
    desc = dict(i=i, kind=kind, codec=codec, n=n, nullable=nullable,
                v2=v2, page_kb=page_kb)

    t, enc, use_dict = _make_table(kind, n, nullable, rng)
    kw = dict(compression=codec if codec != "none" else "none",
              use_dictionary=use_dict,
              row_group_size=1 << 30,
              data_page_size=page_kb * 1024,
              data_page_version="2.0" if v2 else "1.0",
              use_byte_stream_split=False)
    if enc:
        kw["column_encoding"] = {"c": enc}
    b = io.BytesIO()
    try:
        pq.write_table(t, b, **kw)
    except Exception as e:
        return {**desc, "status": "skip", "reason": f"pyarrow encode: {e}"}
    raw = b.getvalue()
    oracle = t.column("c").combine_chunks()

    try:
        # 1) surface host read
        got = ParquetFile(raw).read().to_arrow().column("c").combine_chunks()
        if not got.cast(oracle.type).equals(oracle):
            return {**desc, "status": "FAIL", "stage": "surface_read"}
        # 2) device route, no fallback
        dev_arrow = dr.decode_chunk_device(
            ParquetFile(raw).row_group(0).column(0),
            fallback=False).to_arrow()
        if not dev_arrow.cast(oracle.type).equals(oracle):
            return {**desc, "status": "FAIL", "stage": "device_vs_oracle"}
    except Exception:
        return {**desc, "status": "FAIL", "stage": "exception",
                "trace": traceback.format_exc(limit=8)}
    return {**desc, "status": "pass"}


def one_write_trial(i: int, rng) -> dict:
    """WRITE-side soak: random table → OUR writer under randomized options
    → pyarrow reads it back (independent oracle) AND our reader re-reads
    it (self-consistency).  The read-side trials above cover decode; this
    covers encoders, statistics, indexes and page framing."""
    from parquet_tpu import ParquetFile, WriterOptions, write_table

    kind = KINDS[int(rng.integers(0, len(KINDS)))]
    codec = CODECS[int(rng.integers(0, len(CODECS)))]
    n = int(rng.integers(500, 80_000))
    nullable = bool(rng.random() < 0.4)
    v2 = bool(rng.random() < 0.5)
    page_kb = int(rng.choice([4, 16, 64, 256]))
    use_dict = bool(rng.random() < 0.6)
    rg_rows = int(rng.choice([n + 1, max(n // 3, 1)]))
    desc = dict(i=i, mode="write", kind=kind, codec=codec, n=n,
                nullable=nullable, v2=v2, page_kb=page_kb,
                use_dict=use_dict, rg_rows=rg_rows)
    t, _, _ = _make_table(kind, n, nullable, rng)
    try:
        buf = io.BytesIO()
        write_table(t, buf, WriterOptions(
            compression=codec,
            data_page_size=page_kb * 1024,
            data_page_version=2 if v2 else 1,
            dictionary=use_dict,
            row_group_size=rg_rows,
            write_page_index=bool(rng.random() < 0.7)))
        raw = buf.getvalue()
        oracle = t.column("c").combine_chunks()
        got = pq.read_table(io.BytesIO(raw)).column("c").combine_chunks()
        if not got.cast(oracle.type).equals(oracle):
            return {**desc, "status": "FAIL", "stage": "pyarrow_readback"}
        ours = (ParquetFile(raw).read().to_arrow().column("c")
                .combine_chunks())
        if pa.types.is_dictionary(ours.type):
            ours = ours.cast(oracle.type)
        if not ours.cast(oracle.type).equals(oracle):
            return {**desc, "status": "FAIL", "stage": "self_readback"}
    except Exception:
        return {**desc, "status": "FAIL", "stage": "exception",
                "trace": traceback.format_exc(limit=8)}
    return {**desc, "status": "pass"}


def main() -> int:
    import jax

    # ROUTE_SOAK_CPU=1: an off-chip smoke run on the CPU backend
    if os.environ.get("ROUTE_SOAK_CPU", "") not in ("", "0"):
        jax.config.update("jax_platforms", "cpu")

    args = [a for a in sys.argv[1:]]
    write_mode = "--write" in args
    args = [a for a in args if a != "--write"]
    n_trials = int(args[0]) if args else 200
    seed = int(args[1]) if len(args) > 1 else 0
    rng = np.random.default_rng(seed)
    backend = jax.default_backend()
    trial = one_write_trial if write_mode else one_trial

    failures, skips, passed = [], 0, 0
    t0 = time.time()
    for i in range(n_trials):
        r = trial(i, rng)
        if r["status"] == "pass":
            passed += 1
        elif r["status"] == "skip":
            skips += 1
        else:
            failures.append(r)
            print("FAIL:", json.dumps(r)[:500], flush=True)
        if (i + 1) % 20 == 0:
            print(f"{i+1}/{n_trials} pass={passed} skip={skips} "
                  f"fail={len(failures)} ({time.time()-t0:.0f}s)", flush=True)

    art = {
        "backend": backend,
        "jax": jax.__version__,
        "date": time.strftime("%Y-%m-%d"),
        "trials": n_trials, "passed": passed, "skips": skips,
        "seed": seed, "failures": failures,
        "wall_s": round(time.time() - t0, 1),
    }
    art["mode"] = "write" if write_mode else "read"
    root = os.path.join(os.path.dirname(__file__), "..")
    suffix = "_WRITE" if write_mode else ""
    path = os.path.join(root, f"ROUTE_SOAK_{backend.upper()}{suffix}.json")
    with open(path, "w") as f:
        json.dump(art, f, indent=1)
    print("wrote", path, ":", json.dumps({k: art[k] for k in
          ("backend", "trials", "passed", "skips")}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
