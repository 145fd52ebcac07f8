"""Mesh-parallel correctness on 4 virtual CPU devices (DESIGN.md §15).

Parity is pinned in subprocesses (the forced-host-platform flag must be set
before jax initializes; conftest.run_in_cpu_mesh) so these run in the plain
single-CPU fast tier:

  - packed_shard fwd/bwd == single-device packed kernel (rtol <= 1e-4)
  - slot-sharded paged serve pool: greedy decode BIT-identical to the
    single-device pool under quant=none
  - registry mesh symmetry, autotune key versioning and plan description
    run in-process (eligibility is a capability question, not placement)
"""
import jax.numpy as jnp
import pytest

from conftest import run_in_cpu_mesh
from repro.core.dispatch import (MixerShape, backends, eligible, get_backend,
                                 resolve, sharded_plan)


# ---------------------------------------------------------------------------
# subprocess parity (4 virtual devices)
# ---------------------------------------------------------------------------


def test_packed_shard_matches_packed_fwd_bwd():
    run_in_cpu_mesh(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.distributed.sharding import make_mesh
from repro.kernels.flare_packed import flare_mixer_packed
from repro.kernels.flare_packed_shard import flare_mixer_packed_shard

assert jax.device_count() == 4
mesh = make_mesh((2, 2), ("data", "model"))
rng = np.random.default_rng(0)
B, H, N, M, D = 2, 4, 96, 5, 8
q = jnp.asarray(rng.normal(size=(H, M, D)), jnp.float32)
k = jnp.asarray(rng.normal(size=(B, H, N, D)), jnp.float32)
v = jnp.asarray(rng.normal(size=(B, H, N, D)), jnp.float32)

y0 = flare_mixer_packed(q, k, v, block_n=32)
y1 = flare_mixer_packed_shard(q, k, v, mesh=mesh, block_n=32)
assert float(jnp.max(jnp.abs(y0 - y1))) < 1e-4

def loss0(q, k, v):
    return jnp.sum(jnp.sin(flare_mixer_packed(q, k, v, block_n=32)))
def loss1(q, k, v):
    return jnp.sum(jnp.sin(flare_mixer_packed_shard(q, k, v, mesh=mesh,
                                                    block_n=32)))
g0 = jax.grad(loss0, argnums=(0, 1, 2))(q, k, v)
g1 = jax.grad(loss1, argnums=(0, 1, 2))(q, k, v)
for a, b, nme in zip(g0, g1, "qkv"):
    e = float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-8))
    assert e < 1e-4, (nme, e)
print("PASS")
""")


def test_packed_shard_1d_mesh_and_registry_route():
    # sequence-only sharding (no latent axis), driven through the registry
    run_in_cpu_mesh(r"""
import numpy as np, jax, jax.numpy as jnp
from repro.distributed.sharding import make_mesh
from repro.core.dispatch import resolve, MixerShape
from repro.kernels.flare_packed import flare_mixer_packed

mesh = make_mesh((4,), ("data",))
rng = np.random.default_rng(1)
B, H, N, M, D = 2, 4, 128, 6, 8
q = jnp.asarray(rng.normal(size=(H, M, D)), jnp.float32)
k = jnp.asarray(rng.normal(size=(B, H, N, D)), jnp.float32)
v = jnp.asarray(rng.normal(size=(B, H, N, D)), jnp.float32)

backend, plan = resolve("packed_shard", shape=MixerShape.from_qkv(q, k),
                        dtype=k.dtype, mesh=mesh)
assert "mesh_shape" in plan.params, plan.params
y = backend.run(plan, q, k, v)
y0 = flare_mixer_packed(q, k, v)
assert float(jnp.max(jnp.abs(y - y0))) < 1e-4

# an indivisible sequence (N % 4 != 0) must be rejected at plan time, which
# is what lets `resolve("auto", ...)` fall through to the jnp sharded forms
from repro.backends.packed_shard import build_shard_plan
try:
    build_shard_plan(MixerShape(batch=2, heads=4, tokens=63, latents=6,
                                head_dim=8), mesh, ("data",), (), jnp.float32)
except ValueError:
    pass
else:
    raise SystemExit("indivisible N accepted by build_shard_plan")
print("PASS")
""")


def test_sharded_pool_greedy_decode_bit_identical():
    out = run_in_cpu_mesh(r"""
import warnings
warnings.filterwarnings("ignore")
import jax, numpy as np
from repro.configs import get_smoke_config
from repro.models.api import get_model
from repro.serve.engine import ServeEngine
from repro.distributed.sharding import make_mesh

cfg = get_smoke_config("qwen2_1_5b")
model = get_model(cfg, seq_len_hint=64)
params = model.init(jax.random.PRNGKey(0))

def run(mesh):
    eng = ServeEngine(model, params, capacity=64, slots=4, seed=0,
                      pool_tokens=256, block_size=16, mesh=mesh)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, int(n)) for n in (5, 9, 12, 7, 11, 6)]
    for p in prompts:
        eng.submit(p, max_new_tokens=8)
    outs = eng.run_all()
    eng.check_invariants()
    return outs, eng

o1, e1 = run(None)
o2, e2 = run(make_mesh((2, 2), ("data", "model")))
assert e2.stats["shards"] == 4, e2.stats
assert e2.stats["mesh_shape"] == "data2xmodel2", e2.stats["mesh_shape"]
assert len(o1) == len(o2) == 6
for i, (a, b) in enumerate(zip(o1, o2)):
    assert np.array_equal(a, b), (i, a.tolist(), b.tolist())
print("PASS shards=%d" % e2.stats["shards"])
""")
    assert "shards=4" in out


# ---------------------------------------------------------------------------
# in-process: registry symmetry, keys, plan description
# ---------------------------------------------------------------------------

SHAPE = MixerShape(batch=4, heads=4, tokens=64, latents=8, head_dim=8)


def _probe_mesh():
    from repro.distributed.sharding import make_mesh

    return make_mesh((1, 1), ("data", "model"))


def test_registry_mesh_symmetry():
    # a backend is eligible with a mesh XOR without one, never both — the
    # invariant behind "scored by mesh availability"
    mesh = _probe_mesh()
    for b in backends():
        now = eligible(b, causal=False, dtype=jnp.float32, mesh=None)
        withm = eligible(b, causal=False, dtype=jnp.float32, mesh=mesh)
        assert not (now and withm), b.name
        if b.caps.sharded:
            assert not now, f"{b.name} sharded but eligible without a mesh"


def test_sharded_backends_registered_and_mesh_gated():
    for name in ("packed_shard", "paged_shard"):
        b = get_backend(name)
        assert b.caps.sharded
        with pytest.raises(ValueError):
            resolve(name, shape=SHAPE, dtype=jnp.float32, causal=False)


def test_auto_without_mesh_never_picks_sharded():
    for grad in (False, True):
        _, plan = resolve("auto", shape=SHAPE, dtype=jnp.float32, causal=False,
                          grad=grad)
        assert not get_backend(plan.backend).caps.sharded, plan.backend


def test_auto_with_mesh_resolves_sharded():
    _, plan = resolve("auto", shape=SHAPE, dtype=jnp.float32, causal=False,
                      mesh=_probe_mesh())
    assert get_backend(plan.backend).caps.sharded, plan.backend


def test_packed_shard_plan_describes_mesh_shape():
    plan = sharded_plan(_probe_mesh(), ("data",), ("model",), shape=SHAPE,
                        dtype=jnp.float32, prefer=("packed_shard",))
    assert plan.backend == "packed_shard"
    assert "mesh_shape=data1xmodel1" in plan.describe(), plan.describe()


def test_autotune_keys_gain_mesh_component():
    from repro.backends.autotune import cache_key, legacy_cache_key

    plain = cache_key(SHAPE, jnp.float32, "cpu", "packed")
    meshed = cache_key(SHAPE, jnp.float32, "cpu", "packed", mesh=(2, 2))
    assert "|mesh2x2|" in meshed and "mesh" not in plain
    # unsharded keys stay byte-identical to the historical format (migration:
    # old caches keep hitting), and the legacy fallback key is un-versioned
    assert plain == cache_key(SHAPE, jnp.float32, "cpu", "packed", mesh=None)
    assert legacy_cache_key(SHAPE, jnp.float32, "cpu", "packed",
                            mesh=(2, 2)).endswith("|mesh2x2")
