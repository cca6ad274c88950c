"""The knob registry: every ``PARQUET_TPU_*`` environment variable the
engine reads, declared once with name/type/default/doc.

Pure data — this module imports nothing but ``utils.env.declare`` and
runs at the env accessor's first use.  The README "Environment knobs"
table is GENERATED from these declarations (``python -m parquet_tpu
analyze --knobs-md``); lint rule PT002 fails the build on any
``os.environ`` read that bypasses the accessor and on any accessor call
naming an undeclared knob, so a new knob cannot ship undeclared or
undocumented.

Defaults here are the *parse* defaults (what the accessor returns when
the var is unset or unparseable); a few sites layer policy on top —
e.g. ``PARQUET_TPU_LOOKUP_BUDGET`` unset falls back to the global read
budget and then to the 64 MiB lookup-tier default inside
``AdmissionController`` — and those policies live with the site, not
here.
"""

from ..utils.env import declare

# --------------------------------------------------------------- pool / read
declare("PARQUET_TPU_POOL_WORKERS", "int", 0,
        "shared worker-pool width; 0/unset sizes to the machine "
        "(max(2, min(16, cpus)))")
declare("PARQUET_TPU_READ_BUDGET", "opt_bytes", None,
        "unified FIFO byte budget over ALL in-flight read spans "
        "(scans, streams, lookups); 0 disables admission entirely")
declare("PARQUET_TPU_LOOKUP_BUDGET", "opt_bytes", None,
        "lookup-tier sub-budget inside the read budget; unset keeps the "
        "64 MiB lookup default, 0 disables the tier gate")
declare("PARQUET_TPU_SCAN_BUDGET", "opt_bytes", None,
        "scan/stream-tier sub-budget inside the read budget; unset "
        "leaves bulk reads unbudgeted")
declare("PARQUET_TPU_READ_STREAMED", "bool", True,
        "route very large whole-file reads through the streamed path "
        "instead of whole-chunk decode")
declare("PARQUET_TPU_STREAM_PARALLEL", "bool", True,
        "fan per-column streamed decode across the shared pool when the "
        "batch is large enough")
declare("PARQUET_TPU_ROUTE", "str", "",
        "pin filtered-scan routing: host|device (cpu|tpu accepted); "
        "unset lets the cost model choose")

# ------------------------------------------------------------------- caches
declare("PARQUET_TPU_CHUNK_CACHE", "bytes", 256 << 20,
        "decoded whole-chunk LRU capacity in bytes; 0 disables")
declare("PARQUET_TPU_PAGE_CACHE", "bytes", 64 << 20,
        "decoded-page LRU capacity in bytes (the lookup serving tier); "
        "0 disables")
declare("PARQUET_TPU_FOOTER_CACHE", "int", 256,
        "parsed-footer cache capacity in entries; 0 disables")
declare("PARQUET_TPU_NEG_LOOKUP", "bytes", 4 << 20,
        "negative-lookup memo capacity in bytes (keys proven absent); "
        "0 disables")

# ----------------------------------------------------------- memory pressure
declare("PARQUET_TPU_MEM_SOFT", "bytes", 0,
        "soft memory watermark over the resource-ledger total: crossing "
        "it runs the cache reclaimers; 0/unset off")
declare("PARQUET_TPU_MEM_HARD", "bytes", 0,
        "hard memory watermark: additionally blocks NEW read admissions "
        "until the total drops; 0/unset off")

# -------------------------------------------------------- sources / prefetch
declare("PARQUET_TPU_MMAP", "bool", True,
        "open local paths as zero-copy MmapSource (pread fallback on "
        "mmap failure); 0 forces plain pread FileSource")
declare("PARQUET_TPU_MMAP_DROPBEHIND", "bool", False,
        "one-shot streamed drains release consumed page-cache spans "
        "behind the read frontier (known-one-shot bulk scans only)")
declare("PARQUET_TPU_PREFETCH", "str", "1",
        "readahead mode: off|auto|ring|mmap (0/off disables; ring=pool "
        "window preads, mmap=madvise hints; default auto)")
declare("PARQUET_TPU_PREFETCH_AUTOTUNE", "bool", True,
        "adapt prefetch depth/window from observed pool-wait bubbles "
        "and remote latency class")
declare("PARQUET_TPU_PREFETCH_DEPTH", "opt_int", None,
        "pin the readahead depth in windows (autotune then leaves it "
        "alone); unset = tuned")
declare("PARQUET_TPU_PREFETCH_WINDOW", "opt_int", None,
        "pin the readahead window size in bytes; unset = tuned")

# -------------------------------------------------------------------- write
declare("PARQUET_TPU_WRITE_OVERLAP", "str", "1",
        "encode/emit pipelining: off|auto|force (auto gates on >1 CPU "
        "and ≥8 MB per row group)")
declare("PARQUET_TPU_WRITE_DEPTH", "int", 1,
        "encoded row groups allowed in flight behind a slow sink; 1 = "
        "emit inline, ≥2 adds a background emitter thread")
declare("PARQUET_TPU_WRITE_PENDED", "bytes", 256 << 20,
        "byte cap on encoded groups queued for emit at depth ≥2")
declare("PARQUET_TPU_WRITE_BUFFER", "opt_bytes", None,
        "pin the coalescing writeback buffer size in bytes (0 = "
        "pass-through); unset = 4 MiB default + autotune")
declare("PARQUET_TPU_WRITE_AUTOTUNE", "bool", True,
        "grow/decay the writeback buffer from observed sink flushes "
        "per row group")

# ------------------------------------------------------------------- lookup
declare("PARQUET_TPU_LOOKUP_KEY_SHARD", "int", 1024,
        "minimum unique keys per shard before a large lookup batch fans "
        "its key set across pool workers; 0 disables sharding")

# -------------------------------------------------------------- aggregation
declare("PARQUET_TPU_AGG_DICT", "bool", True,
        "dictionary tier of the aggregation cascade: SUM/COUNT DISTINCT/"
        "MIN/MAX/group-by over dict-encoded chunks aggregate the index "
        "stream without expanding values; 0 falls back to exact decode")
declare("PARQUET_TPU_FUSED", "str", "auto",
        "fused single-pass execution (decode+mask+fold page streaming, "
        "no whole-column intermediates): on|off|auto — auto lets the "
        "cost model fuse once the estimated decode bytes clear the "
        "threshold (io/planner.py choose_fused)")

# -------------------------------------------------------------------- write
declare("PARQUET_TPU_MMAP_SINK", "bool", False,
        "opt-in mmap-backed atomic path sink experiment: writes copy "
        "into a mapped temp file instead of buffered write() calls "
        "(same fsync+rename commit; measured ~0.75x of the writev "
        "path — kept opt-in for syscall-restricted regimes, see bench "
        "cfg6 mmap_sink)")

# ------------------------------------------------------------------- remote
declare("PARQUET_TPU_REMOTE_PARALLEL", "int", 4,
        "max concurrent range requests a multi-range read plan may "
        "issue against one remote source (capped by the connection "
        "pool); 0/1 disables parallel preads")
declare("PARQUET_TPU_REMOTE_POOL", "int", 4,
        "persistent connections kept per remote host")
declare("PARQUET_TPU_REMOTE_TIMEOUT", "float", 30.0,
        "socket timeout in seconds for remote range requests")
declare("PARQUET_TPU_REMOTE_HEDGE", "str", "auto",
        "hedged-read delay: 0/off disables, a float pins seconds, "
        "auto adapts to the observed p95 remote latency")
declare("PARQUET_TPU_REMOTE_BREAKER", "int", 5,
        "consecutive connection-class failures before a host's circuit "
        "opens (fail-fast)")
declare("PARQUET_TPU_REMOTE_BREAKER_COOLDOWN", "float", 1.0,
        "seconds an open circuit waits before its half-open probe")
declare("PARQUET_TPU_S3_ENDPOINT", "str", "",
        "HTTP(S) endpoint s3:// URLs resolve against (path-style: "
        "{endpoint}/{bucket}/{key}); required for s3:// sources and "
        "ListObjectsV2 prefix expansion — unset makes s3:// paths an "
        "error")

# ------------------------------------------------------------------- remote
declare("PARQUET_TPU_REMOTE_AUTH_RETRY", "int", 1,
        "credential refreshes attempted on a 401/403 remote response "
        "before it surfaces (auth hook re-invoked with refresh=True); "
        "0 disables the refresh path")

# ------------------------------------------------------------------ serving
declare("PARQUET_TPU_SERVE_DRAIN_S", "float", 10.0,
        "seconds a graceful daemon shutdown (SIGTERM / Server.close) "
        "waits for in-flight requests before giving up")
declare("PARQUET_TPU_SERVE_RETRY_AFTER_S", "float", 1.0,
        "Retry-After seconds a shed 429 advertises to bulk-class "
        "requests under hard memory pressure")
declare("PARQUET_TPU_SERVE_MAX_BODY", "bytes", 64 << 20,
        "serving-daemon request-body cap in bytes (larger bodies are "
        "refused 413 before buffering)")

# -------------------------------------------------------------------- fleet
declare("PARQUET_TPU_FLEET_VNODES", "int", 64,
        "virtual nodes per fleet member on the consistent-hash ring "
        "(more = smoother key/file spread, slower ring build)")
declare("PARQUET_TPU_FLEET_PEER_TIMEOUT_S", "float", 10.0,
        "per-peer sub-request timeout in seconds for fleet "
        "scatter-gather when the request carries no deadline")
declare("PARQUET_TPU_FLEET_MARGIN_S", "float", 0.25,
        "seconds the fleet gather reserves out of the request deadline "
        "for merging peer results (per-peer deadline = remaining - "
        "margin)")
declare("PARQUET_TPU_FLEET_HEDGE_S", "opt_float", None,
        "seconds before a slow peer sub-request is hedged with a local "
        "execution of its shard; unset adapts to the observed peer "
        "latency (remote hedge machinery), 0 disables hedging")
declare("PARQUET_TPU_FLEET_CAS_TTL_S", "float", 30.0,
        "age in seconds after which a manifest CAS claim file left by a "
        "crashed committer may be broken (takeover)")
declare("PARQUET_TPU_FLEET_CAS_RETRIES", "int", 8,
        "optimistic-concurrency re-reads a manifest commit attempts "
        "when CAS arbitration reports a conflicting writer")

# ------------------------------------------------------------ observability
declare("PARQUET_TPU_TRACE", "str", "",
        "enable span tracing and flush Chrome trace-event JSON to this "
        "path at exit")
declare("PARQUET_TPU_TRACE_SAMPLE", "int", 1,
        "head-sample 1-in-N operations onto per-request trace tracks "
        "(1 = trace every op)")
declare("PARQUET_TPU_SLOW_OP_S", "opt_float", None,
        "tail-capture threshold in seconds: slower ops promote their "
        "span ring and write a slow-op record; 0 keeps every op")
declare("PARQUET_TPU_SLOW_LOG", "str", "",
        "append one JSON line per slow op to this file")
declare("PARQUET_TPU_TRACE_DIR", "str", "",
        "jax profiler output directory for profiler_trace() regions")

# ------------------------------------------------------ lockcheck sanitizer
declare("PARQUET_TPU_LOCKCHECK", "bool", False,
        "instrument every utils/locks.py lock: record per-thread "
        "held-lock sets, the global lock-order graph, cycle (potential "
        "deadlock) and blocking-under-lock findings; plain stdlib locks "
        "(zero overhead) when off")
declare("PARQUET_TPU_LOCKCHECK_REPORT", "str", "",
        "write the lockcheck JSON report (graph + findings) to this "
        "path at interpreter exit")

# ----------------------------------------------------------- device / native
declare("PARQUET_TPU_PALLAS", "str", "",
        "dense bit-unpack routing: 1/pallas = the Pallas kernel (interpret "
        "mode off the TPU), 0/jnp = the jnp twin, off = per-value gathers; "
        "unset = the kernel on a TPU, the twin elsewhere")
declare("PARQUET_TPU_DEVICE_OVERLAP", "str", "auto",
        "mesh-read stage/decode pipelining: 0/off=stage then decode "
        "sequentially, auto=overlap when the shard has >1 file, "
        "force=always submit stage N+1 before decode N")
declare("PARQUET_TPU_NO_X64", "bool", False,
        "skip enabling jax 64-bit mode at import (INT64/FP64 columns "
        "then decode via the 32-bit paths)")
declare("PARQUET_TPU_NO_NATIVE", "bool", False,
        "disable the C++ native helper module (pure-python/numpy "
        "fallbacks everywhere)")
