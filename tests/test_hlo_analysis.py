"""The trip-count-aware HLO analyzer vs known ground truth."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo import analyze_hlo
from repro.analysis.roofline import V5E, roofline_terms


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_single_dot_flops_exact():
    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    c = _compile(lambda x, y: x @ y, a, b)
    r = analyze_hlo(c.as_text())
    assert r["flops"] == pytest.approx(2 * 64 * 128 * 32, rel=1e-6)


def test_scan_trip_count_scaling():
    """A 10-iteration scan must report ~10x the flops of its body — the
    exact failure mode of raw cost_analysis (DESIGN.md §7)."""
    w = jax.ShapeDtypeStruct((10, 64, 64), jnp.float32)
    x = jax.ShapeDtypeStruct((8, 64), jnp.float32)

    def f(w, x):
        def body(x, wl):
            return jnp.tanh(x @ wl), None
        return jax.lax.scan(body, x, w)[0].sum()

    c = _compile(f, w, x)
    r = analyze_hlo(c.as_text())
    body_flops = 2 * 8 * 64 * 64
    assert r["flops"] == pytest.approx(10 * body_flops, rel=0.05)
    # raw cost_analysis undercounts:
    ca = c.cost_analysis()
    d = ca if isinstance(ca, dict) else ca[0]
    assert d["flops"] < 2 * body_flops


def test_nested_scan_multiplies():
    w = jax.ShapeDtypeStruct((3, 4, 16, 16), jnp.float32)
    x = jax.ShapeDtypeStruct((2, 16), jnp.float32)

    def f(w, x):
        def outer(x, wo):
            def inner(x, wl):
                return jnp.tanh(x @ wl), None
            return jax.lax.scan(inner, x, wo)[0], None
        return jax.lax.scan(outer, x, w)[0].sum()

    c = _compile(f, w, x)
    r = analyze_hlo(c.as_text())
    assert r["flops"] == pytest.approx(12 * 2 * 2 * 16 * 16, rel=0.05)


def test_memory_traffic_lower_bound():
    """Elementwise op: traffic >= in + out bytes."""
    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    c = _compile(lambda x: jnp.tanh(x) * 2.0, x)
    r = analyze_hlo(c.as_text())
    assert r["mem_bytes"] >= 2 * 1024 * 1024 * 4 * 0.99


def test_roofline_terms_math():
    analysis = {"flops": V5E.peak_flops, "mem_bytes": 2 * V5E.hbm_bw,
                "collective_bytes": 0.5 * V5E.ici_bw}
    t = roofline_terms(analysis, model_flops_per_device=V5E.peak_flops / 2)
    assert t["compute_s"] == pytest.approx(1.0)
    assert t["memory_s"] == pytest.approx(2.0)
    assert t["collective_s"] == pytest.approx(0.5)
    assert t["dominant"] == "memory"
    assert t["useful_compute_ratio"] == pytest.approx(0.5)
    assert t["bound_overlap_s"] == pytest.approx(2.0)
    assert t["mfu_overlap_bound"] == pytest.approx(0.25)


def test_collectives_counted_inside_scan():
    """Collective bytes inside a scanned body scale with the trip count."""
    import os
    import subprocess
    import sys

    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, sys
sys.path.insert(0, "src")
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.analysis.hlo import analyze_hlo
from repro.distributed.sharding import make_mesh
mesh = make_mesh((4,), ("d",))
w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
x = jax.ShapeDtypeStruct((4, 128), jnp.float32)
def f(w, x):
    def body(x, wl):
        return jnp.tanh(x @ wl), None
    return jax.lax.scan(body, x, w)[0].sum()
c = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, None, "d")),
                             NamedSharding(mesh, P()))).lower(w, x).compile()
r = analyze_hlo(c.as_text())
assert r["collective_bytes"] > 0, "expected collectives"
counts = r["collective_counts"]
assert sum(counts.values()) >= 8, counts  # one+ per scan iteration
print("OK")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=os.path.dirname(os.path.dirname(__file__)),
                         timeout=300)
    assert "OK" in out.stdout, out.stderr[-2000:]
