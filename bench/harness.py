"""What every cell shares: finding its files by name, the compile cache, the
device check, compile counting and the comparison of leaf norms.

A cell named ``<config>.<traffic>`` in ``BENCHMARK.json`` resolves to
``bench/configs/<config>.json``, ``bench/refs/<config>.py`` and
``bench/traffic/<traffic>.json``; its per-layer metrics to
``bench/metrics/<metric>.py``. The configuration's ``entry`` names the
driver (``bench/drive_<entry>.py``).
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# a fixed directory in the checkout: the path is part of every cache key
CACHE_DIR = os.path.join(ROOT, ".bench_cache")


class NoDevice(SystemExit):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell(name: str, bench: dict | None = None) -> dict:
    """The cell's entry in BENCHMARK.json with its files loaded."""
    bench = bench or benchmark()
    for w in bench["workloads"]:
        if w["name"] == name:
            return load_cell(w)
    raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")


def load_cell(w: dict) -> dict:
    """A workload entry ({"name", "config", "traffic", "chips"}) with its
    configuration, reference module and traffic mix loaded: {"cell",
    "config", "ref", "mix"}."""
    return {
        "cell": w,
        "config": load_json(os.path.join(BENCH, "configs", w["config"] + ".json")),
        "ref": load_module(os.path.join(BENCH, "refs", w["config"] + ".py"),
                           "bench_ref_" + w["config"]),
        "mix": load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json")),
    }


def driver(entry: str):
    return importlib.import_module(f"bench.drive_{entry}")


def metric_reader(name: str):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))


# the least-recently-used budget of the persistent cache: one cell's programs
# (train step ~0.1 GB, reference ~0.1 GB each, seven serving programs) must
# all stay, or each run compiles again what the last one evicted
CACHE_MAX_BYTES = 4 << 30


def setup_env() -> str:
    """Persistent compile cache (the environment's directory, else the fixed
    one in the checkout) for every program however short its compile, with
    room for all of a cell's programs; tile choices from the shape
    heuristic, not a user's autotune cache."""
    os.environ["REPRO_AUTOTUNE"] = "0"
    os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(CACHE_DIR, "autotune.json")
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", CACHE_MAX_BYTES)
    return cache


def device(chips: int) -> dict:
    """The devices as JAX reports them; exits (no result) without a TPU or
    with fewer chips than the cell asks for."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(json.dumps({"device": info}), file=sys.stderr, flush=True)
    if info["platform"] != "tpu":
        raise NoDevice(f"bench: needs a TPU; JAX found {info['platform']}")
    if info["count"] < chips:
        raise NoDevice(f"bench: the cell needs {chips} chip(s); JAX found "
                       f"{info['count']}")
    return info


def allocator_peak() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks)) if peaks else 0


class CompileCounter:
    """Counts compile requests (cache hits and misses) and jaxpr traces from
    JAX's monitoring events, so a window can show that it compiled nothing."""

    def __init__(self):
        import jax.monitoring as mon

        self.counts = {"requests": 0, "misses": 0, "traces": 0}
        self._names = {"/jax/compilation_cache/compile_requests_use_cache": "requests",
                       "/jax/compilation_cache/cache_misses": "misses"}
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        key = self._names.get(name)
        if key:
            self.counts[key] += 1

    def _duration(self, name, _secs, **_):
        if name == "/jax/core/compile/jaxpr_trace_duration":
            self.counts["traces"] += 1

    def snapshot(self) -> dict:
        return dict(self.counts)

    def since(self, snap: dict) -> dict:
        return {k: self.counts[k] - snap[k] for k in self.counts}


def leaf_norm_gaps(got: dict, ref: dict, *, keep=None) -> tuple[float, str, float]:
    """Per leaf, |‖got‖ − ‖ref‖| over max(‖ref leaf‖, median ‖ref leaf‖).
    ``got``/``ref`` map a leaf path to its norm; ``keep`` limits the leaves
    compared. Returns (worst gap, its leaf, median gap)."""
    import statistics

    names = [k for k in ref if keep is None or k in keep]
    med = statistics.median(ref[k] for k in names)
    gaps = {k: abs(got[k] - ref[k]) / max(ref[k], med, 1e-30) for k in names}
    worst = max(names, key=lambda k: gaps[k])
    return gaps[worst], worst, statistics.median(gaps.values())


def leaf_norms(tree) -> dict:
    import jax
    import numpy as np

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(np.asarray(x, np.float64)))
            for p, x in flat}


def finite(x: float) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)
