"""parquet-tools-style CLI:
``python -m parquet_tpu [meta|schema|pages|head|verify|stats]``.

Reference parity: the reference ships ``print.go`` (PrintSchema) as a
library; this front end makes the same dumps reachable from a shell.
``verify`` runs the integrity subsystem (io/integrity.py) and exits 0 only
when EVERY file is provably clean — the operational check after an ingest
or before trusting a checkpoint.  It accepts multiple paths and shell-style
globs, verifying files in parallel on the shared pool with a per-file
report line; any corrupt or unreadable file makes the exit code 1.

``stats`` dumps the process-wide telemetry registry (parquet_tpu/obs):
every counter, gauge, and latency histogram (p50/p95/p99), human-readable
by default, ``--json`` for the :func:`parquet_tpu.metrics_snapshot` dict,
``--prom`` for Prometheus exposition text.  With file arguments, the files
are read (decoded through the full pipeline, in parallel on the shared
pool) first, so the dump meters that work — a one-shot way to see cache /
prefetch / planner counters for a real workload; without files it renders
whatever this process has already recorded (the pre-declared core families
exist at 0, so scrapers can always tell "nothing ran" from "not wired").
"""

import argparse
import sys


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="parquet_tpu")
    p.add_argument("command",
                   choices=["meta", "schema", "pages", "head", "verify",
                            "stats", "analyze", "aggregate", "serve"],
                   help="meta: file summary; schema: schema tree; pages: "
                        "page-level dump; head: first rows as JSON lines; "
                        "verify: end-to-end integrity check (exit 0 = every "
                        "file clean, 1 = any corrupt); stats: dump the "
                        "process-wide metrics registry (reads any given "
                        "files first so the counters meter that work); "
                        "analyze: invariant lint + lockcheck hammer over "
                        "the package (exit 0 = clean, 1 = findings) — the "
                        "pre-merge correctness gate; aggregate: answer "
                        "COUNT/MIN/MAX/SUM/AVG/VAR/DISTINCT/top-k from "
                        "metadata without decoding where provable "
                        "(io/aggregate.py); serve: run the long-lived "
                        "serving daemon (parquet_tpu/serve) hosting "
                        "configured datasets behind /v1/lookup|scan|"
                        "aggregate|write + /metrics /healthz /debugz "
                        "with multi-tenant QoS")
    p.add_argument("file", nargs="*",
                   help="parquet file path(s); verify accepts several and "
                        "shell-style globs, checked in parallel; stats "
                        "accepts zero or more (globs ok) to read first")
    p.add_argument("--row-group", type=int, default=0,
                   help="pages: which row group")
    p.add_argument("--column", type=int, default=0,
                   help="pages: which leaf column (schema order)")
    p.add_argument("-n", type=int, default=10, help="head: rows to print")
    p.add_argument("--decode", action="store_true",
                   help="verify: additionally decode every column chunk "
                        "(slowest, strongest check)")
    p.add_argument("--json", action="store_true",
                   help="verify: emit one IntegrityReport JSON per line; "
                        "stats: emit the metrics_snapshot() dict as JSON")
    p.add_argument("--prom", action="store_true",
                   help="stats: emit Prometheus exposition text format")
    p.add_argument("--debugz", action="store_true",
                   help="stats: emit the live /debugz introspection JSON "
                        "(resource-ledger accounts, per-cache top entries, "
                        "admission gate, pool, open-op table)")
    p.add_argument("--serve", type=int, metavar="PORT", default=None,
                   help="stats: serve the registry over HTTP instead of "
                        "dumping once — /metrics (Prometheus 0.0.4) and "
                        "/metrics.json; 0 binds an ephemeral port; runs "
                        "until interrupted")
    p.add_argument("--host", default=None, metavar="ADDR",
                   help="stats --serve / serve: bind address (default "
                        "loopback for stats, the config's host for "
                        "serve; 0.0.0.0 to let a fleet Prometheus "
                        "scrape it)")
    p.add_argument("--agg", action="append", default=[], metavar="SPEC",
                   help="aggregate: one aggregate per flag — count, "
                        "count:COL, min:COL, max:COL, sum:COL, "
                        "sum_sq:COL, avg:COL, var:COL[:sample], "
                        "distinct:COL, top:COL:K (repeatable)")
    p.add_argument("--where", default=None, metavar="COL:LO:HI",
                   help="aggregate: inclusive range predicate (empty "
                        "LO/HI = open bound; values parse as int, float, "
                        "then string)")
    p.add_argument("--group-by", default=None, metavar="COL",
                   help="aggregate: group results by this flat column")
    p.add_argument("--explain", action="store_true",
                   help="aggregate: print the per-row-group tier trace")
    p.add_argument("--knobs-md", action="store_true",
                   help="analyze: print the generated README "
                        "'Environment knobs' table and exit")
    p.add_argument("--no-hammer", action="store_true",
                   help="analyze: skip the lockcheck hammer subprocess "
                        "(lint + knob-table sync only)")
    p.add_argument("--config", default=None, metavar="PATH",
                   help="serve: the serve.json configuration (datasets "
                        "to host + tenant QoS contracts)")
    p.add_argument("--port", type=int, default=None, metavar="PORT",
                   help="serve: override the config's port (0 binds an "
                        "ephemeral port, printed at startup)")
    # intermixed: `verify --json a b` and `stats --prom` must both parse
    # now that `file` is optional (plain parse_args cannot place
    # positionals after an optional once nargs="*" matched zero)
    args = p.parse_intermixed_args(argv)

    if args.command == "analyze":
        return _analyze(args)

    if args.command == "aggregate":
        return _aggregate_cmd(args)

    if args.command == "serve":
        return _serve_cmd(args)

    if args.command == "stats":
        import json

        from .obs import metrics_snapshot, render_prometheus

        if args.file:
            from .dataset import expand_paths
            from .errors import CorruptedError
            from .io.reader import ParquetFile
            from .utils.pool import map_in_order

            missing: list = []
            files = expand_paths(args.file, missing=missing)
            for item in missing:
                print(f"parquet_tpu: {item}: no files match",
                      file=sys.stderr)
            if missing:
                return 1

            def meter(path):
                # only the metering side effect is wanted: returning the
                # Table would hold every decoded file in memory at once
                ParquetFile(path).read()
                return None

            try:
                for _ in map_in_order(meter, files):
                    pass
            except (OSError, ValueError, KeyError, CorruptedError) as e:
                print(f"parquet_tpu: {e}", file=sys.stderr)
                return 1
        if args.serve is not None:
            from .obs.export import start_metrics_server

            srv = start_metrics_server(args.serve,
                                       host=args.host or "127.0.0.1")
            # line-buffered contract for scripts that scrape the port
            print(f"serving metrics on {srv.url} "
                  f"(and {srv.url}.json); Ctrl-C to stop", flush=True)
            try:
                srv.join()
            except KeyboardInterrupt:
                srv.close()
            return 0
        if args.debugz:
            from .obs import debugz_snapshot

            print(json.dumps(debugz_snapshot(), sort_keys=True))
        elif args.prom:
            sys.stdout.write(render_prometheus())
        elif args.json:
            print(json.dumps(metrics_snapshot(), sort_keys=True))
        else:
            snap = metrics_snapshot()
            for kind in ("counters", "gauges"):
                for k, v in sorted(snap[kind].items()):
                    print(f"{k} {v}")
            for k, h in sorted(snap["histograms"].items()):
                print(f"{k} count={h['count']} sum={h['sum']} "
                      f"p50={h['p50']} p95={h['p95']} p99={h['p99']}")
        return 0

    if not args.file:
        print(f"parquet_tpu: {args.command} requires a file",
              file=sys.stderr)
        return 1

    if args.command == "verify":
        # never opens ParquetFile up front: a corrupt footer must yield a
        # report and exit code, not a traceback
        import json

        from .dataset import expand_paths
        from .io.integrity import verify_file
        from .utils.pool import map_in_order

        missing: list = []
        files = expand_paths(args.file, missing=missing)
        for item in missing:
            print(f"parquet_tpu: {item}: no files match", file=sys.stderr)
        if not files:
            return 1

        def one(path):
            try:
                return verify_file(path, decode=args.decode)
            except OSError as e:  # unreadable file: a failure, not a crash
                return e

        bad = len(missing)
        for path, rep in zip(files, map_in_order(one, files)):
            if isinstance(rep, Exception):
                print(f"parquet_tpu: {path}: {rep}", file=sys.stderr)
                bad += 1
                continue
            print(json.dumps(rep.as_dict()) if args.json else rep.summary())
            if not rep.ok:
                bad += 1
        return 1 if bad else 0

    from .io.reader import ParquetFile
    from .utils.printer import print_file, print_pages, print_schema

    if len(args.file) != 1:
        print(f"parquet_tpu: {args.command} takes exactly one file",
              file=sys.stderr)
        return 1
    try:
        if args.n < 1:
            raise ValueError("-n must be >= 1")
        pf = ParquetFile(args.file[0])
        if args.command == "meta":
            print_file(pf, file=sys.stdout)
        elif args.command == "schema":
            print_schema(pf.schema, file=sys.stdout)
        elif args.command == "pages":
            if not 0 <= args.row_group < len(pf.row_groups):
                raise ValueError(f"row group {args.row_group} out of range "
                                 f"(file has {len(pf.row_groups)})")
            if not 0 <= args.column < len(pf.schema.leaves):
                raise ValueError(f"column {args.column} out of range "
                                 f"(schema has {len(pf.schema.leaves)} leaves)")
            print_pages(pf, args.row_group, args.column, file=sys.stdout)
        elif args.command == "head":
            import json

            tab = pf.iter_batches(batch_rows=args.n)
            batch = next(iter(tab), None)
            if batch is not None:
                rows = batch.to_arrow().to_pylist()[: args.n]
                for r in rows:
                    print(json.dumps(r, default=repr))
    except (OSError, ValueError, KeyError) as e:
        print(f"parquet_tpu: {e}", file=sys.stderr)
        return 1
    return 0


def _serve_cmd(args) -> int:
    """``python -m parquet_tpu serve --config serve.json [--port N]
    [--host ADDR]``: run the serving daemon in the foreground until
    SIGTERM/SIGINT, then drain in-flight requests
    (``PARQUET_TPU_SERVE_DRAIN_S``) and exit 0."""
    import signal
    import threading

    from .serve import Server, load_config
    from .utils.compile_cache import setup_compile_cache

    if not args.config:
        print("parquet_tpu: serve requires --config serve.json",
              file=sys.stderr)
        return 1
    setup_compile_cache()
    try:
        config = load_config(args.config)
        # None = not passed -> the config's host wins; an explicit
        # --host (loopback included) always overrides
        srv = Server(config, host=args.host, port=args.port)
    except (OSError, ValueError, KeyError) as e:
        print(f"parquet_tpu: {e}", file=sys.stderr)
        return 1
    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())
    # line-buffered contract for scripts that scrape the port
    print(f"serving {len(config.datasets)} dataset(s) on {srv.url} "
          f"(tenants: {', '.join(sorted(config.tenants)) or 'default'}); "
          f"SIGTERM drains and exits", flush=True)
    stop.wait()
    drained = srv.close(drain=True)
    print("drained and stopped" if drained
          else "stopped with requests still in flight", flush=True)
    return 0 if drained else 1


def _parse_value(tok: str):
    """CLI predicate bound: int, then float, then the raw string (the
    predicate normalizer maps str → utf-8 bytes); empty = open bound."""
    if tok == "":
        return None
    for cast in (int, float):
        try:
            return cast(tok)
        except ValueError:
            continue
    return tok


def _aggregate_cmd(args) -> int:
    """``python -m parquet_tpu aggregate FILE... --agg SPEC [--where
    COL:LO:HI] [--group-by COL] [--explain] [--json]``."""
    import json

    from .algebra.expr import col
    from .dataset import Dataset
    from .errors import CorruptedError
    from .serve.codecs import parse_agg_spec

    if not args.file:
        print("parquet_tpu: aggregate requires a file", file=sys.stderr)
        return 1
    try:
        # one spec grammar shared with the daemon's /v1/aggregate
        # (serve/codecs.py) — the two front ends can never drift
        aggs = [parse_agg_spec(spec) for spec in (args.agg or ["count"])]
        where = None
        if args.where is not None:
            path, lo, hi = (args.where.split(":", 2) + ["", ""])[:3]
            where = col(path).between(_parse_value(lo), _parse_value(hi))
        ds = Dataset(args.file)
        res = ds.aggregate(aggs, where=where, group_by=args.group_by)
        doc = {"aggregates": {k: _jsonable(v) for k, v in res.items()},
               "tiers": {k: v for k, v in res.counters.items() if v}}
        if res.groups is not None:
            doc["groups"] = [_jsonable(k) for k in res.groups]
        print(json.dumps(doc, sort_keys=True))
        if args.explain:
            print(res.explain(), file=sys.stderr)
    except (OSError, ValueError, KeyError, CorruptedError) as e:
        print(f"parquet_tpu: {e}", file=sys.stderr)
        return 1
    return 0


def _jsonable(v):
    if isinstance(v, bytes):
        return v.decode("utf-8", "replace")
    if isinstance(v, list):
        return [_jsonable(x) for x in v]
    item = getattr(v, "item", None)
    return item() if item is not None else v


def _knobs_readme_stale():
    """Compare the committed README knob table against the registry's
    generated one.  Returns (stale: bool, detail: str); a missing
    README or markers means 'not applicable' (installed package)."""
    import os

    from .utils.env import knobs_markdown

    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    readme = os.path.join(here, "README.md")
    if not os.path.exists(readme):
        return False, "no README.md (installed package?)"
    text = open(readme).read()
    begin, end = "<!-- knobs:begin -->", "<!-- knobs:end -->"
    if begin not in text or end not in text:
        return True, "README.md has no knobs:begin/knobs:end markers"
    committed = text.split(begin, 1)[1].split(end, 1)[0].strip()
    generated = knobs_markdown().strip()
    if committed != generated:
        return True, ("README knob table is stale — regenerate with "
                      "`python -m parquet_tpu analyze --knobs-md`")
    return False, "README knob table matches the registry"


def _analyze(args) -> int:
    """``python -m parquet_tpu analyze [--json] [--knobs-md]
    [--no-hammer]``: the standing pre-merge correctness gate — static
    invariant lint (PT001-PT006), README knob-table sync, and a
    lockcheck-instrumented hammer pass in a subprocess (the env var must
    be set before import so even import-time singleton locks are
    wrapped)."""
    import json
    import os
    import subprocess

    from .analysis.lint import run_lint
    from .utils.env import knobs_markdown

    if args.knobs_md:
        sys.stdout.write(knobs_markdown())
        return 0

    findings = run_lint()
    stale, knob_detail = _knobs_readme_stale()
    hammer: dict = {"skipped": True}
    if not args.no_hammer:
        # ptlint: disable=PT002 -- whole-environment copy handed to the
        # hammer subprocess, not a knob read
        env = dict(os.environ)
        env["PARQUET_TPU_LOCKCHECK"] = "1"
        env.setdefault("JAX_PLATFORMS", "cpu")
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "parquet_tpu.analysis.lockcheck"],
                capture_output=True, text=True, env=env, timeout=600)
        except subprocess.TimeoutExpired as e:
            # a hammer that never returns is the strongest possible
            # finding (an interleaving actually deadlocked) — report it
            # as a failure, never as a crash of the gate itself
            hammer = {"ok": False,
                      "error": "lockcheck hammer timed out after 600s "
                               "(likely a real deadlock)",
                      "stdout": (e.stdout or "")[-2000:] if e.stdout
                      else "",
                      "stderr": (e.stderr or "")[-2000:] if e.stderr
                      else ""}
        else:
            try:
                hammer = json.loads(proc.stdout.strip().splitlines()[-1])
            except (ValueError, IndexError):
                hammer = {"ok": False,
                          "error": "hammer produced no report",
                          "stdout": proc.stdout[-2000:],
                          "stderr": proc.stderr[-2000:]}
    hammer_ok = bool(hammer.get("ok", True))
    ok = not findings and not stale and hammer_ok

    if args.json:
        print(json.dumps({
            "ok": ok,
            "lint": [f.as_dict() for f in findings],
            "knobs_md": {"stale": stale, "detail": knob_detail},
            "lockcheck": hammer,
        }, sort_keys=True))
        return 0 if ok else 1

    for f in findings:
        print(f.render())
    print(f"lint: {len(findings)} finding(s)")
    print(f"knobs: {knob_detail}")
    if hammer.get("skipped"):
        print("lockcheck: skipped (--no-hammer)")
    else:
        cyc = hammer.get("cycles", [])
        blk = [x for x in hammer.get("findings", [])
               if x.get("kind") != "lock_order_cycle"]
        print(f"lockcheck: {hammer.get('acquisitions', 0)} acquisitions, "
              f"{len(hammer.get('edges', []))} lock-order edges, "
              f"{len(cyc)} cycle(s), {len(blk)} other finding(s)")
        for c in cyc:
            print(f"  cycle: {' -> '.join(c + [c[0]])}")
        for x in blk:
            print(f"  {x.get('kind')}: {x.get('blocking', x.get('lock'))} "
                  f"held={x.get('held')}")
        if not hammer_ok and "error" in hammer:
            print(f"  error: {hammer['error']}")
            if hammer.get("stderr"):
                print(hammer["stderr"])
    print("analyze: " + ("OK" if ok else "FAILED"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
