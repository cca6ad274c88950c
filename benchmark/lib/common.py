"""What every cell shares: paths, the JSON files the harness reads by name,
the device check, the package-noise trap and the compile counter.

The device check, ``PackageNoise`` and the compile listener are copied from
``chip_smoke.py`` (PR 21) so that later PRs cannot move the yardstick."""

import importlib.util
import json
import logging
import os
import sys
import time
import warnings

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def say(msg: str) -> None:
    """Progress goes to stderr: stdout ends with the one result line."""
    print(msg, file=sys.stderr, flush=True)


def load_json(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def load_module(rel: str):
    """A file under the benchmark's directory, imported by its path (metric
    readers carry dots in their names)."""
    path = os.path.join(BENCH, rel)
    name = "bench_" + rel.replace("/", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(name: str):
    """The workload entry, its configuration entry and file, and its
    traffic file, all by name from ``BENCHMARK.json``."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = load_json(conf["file"])
    traffic = load_json(os.path.join("benchmark", "traffic",
                                     cell["traffic"] + ".json"))
    return bench, cell, cfg, traffic


def use_cache_in_checkout() -> str:
    """JAX's persistent compilation cache at one fixed path in the checkout
    (the program's own default, ``parquet_tpu/utils/compile_cache.py``),
    whatever the environment says, and every program written to it: JAX
    0.9 keeps only compiles of a second or more unless told otherwise."""
    d = os.path.join(ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = d
    os.environ["TPU_LOG_DIR"] = "disabled"  # libtpu logs to /tmp otherwise
    import jax

    from parquet_tpu.utils.compile_cache import setup_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: a size cap set by the machine makes JAX evict entries
    # while other threads write them, and every write then fails
    jax.config.update("jax_compilation_cache_max_size", -1)
    return setup_compile_cache()


def devices_for(chips: int, need_tpu: bool = True):
    """The cell's devices and the device kind's peaks; exits without a
    result off the TPU, short of chips, or on a kind the table lacks."""
    import jax

    devs = jax.devices()
    kind = devs[0].device_kind
    if need_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU (platform {devs[0].platform!r})"
                         "; nothing was measured")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"sees {len(devs)}")
    peaks = load_json("benchmark/peaks.json")["devices"]
    if need_tpu and kind not in peaks:
        raise SystemExit(f"benchmark: device kind {kind!r} is not in "
                         "benchmark/peaks.json")
    return devs[:chips], peaks.get(kind)


class PackageNoise(logging.Handler):
    """Collects every warning the package logs or warns (chip_smoke.py)."""

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


class Compiles:
    """XLA compiles from JAX's ``backend_compile_duration`` events, with the
    host time of each, so a window can count the ones inside it.  JAX 0.9
    sends the event for a program loaded from the persistent cache too;
    ``cache_hits`` counts those."""

    def __init__(self):
        import jax

        self.events = []  # (perf_counter at the end, seconds, function)
        self.cache_hits = 0  # of those, programs loaded from the cache

        def on_event(event, duration, fun_name="?", **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.events.append((time.perf_counter(), duration, fun_name))

        def on_count(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        jax.monitoring.register_event_listener(on_count)

    def between(self, t0: float, t1: float):
        return [e for e in self.events if t0 <= e[0] <= t1]


def failure_counts() -> dict:
    """The program's counts that mark a request as failed when they rise."""
    from parquet_tpu import counters, metrics_snapshot

    refused = sum(v for k, v in metrics_snapshot()["counters"].items()
                  if k.startswith("device.route_refusals"))
    return {"chunks_host_fallback":
            counters.snapshot().get("chunks_host_fallback", 0),
            "device.route_refusals": refused}
