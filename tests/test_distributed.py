"""Distributed correctness on 8 virtual devices (subprocess — the main test
process must keep seeing 1 device).

Covers:
  - sequence-parallel FLARE (shard_map + psum) == single-device operator
  - sharded train step == unsharded train step (same loss trajectory)
  - sharding rules produce valid NamedShardings for every arch's params
"""
import os
import subprocess
import sys

import pytest

# multi-minute suite: deselect with `-m 'not slow'` (see pyproject.toml)
pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout=600):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, cwd=REPO, timeout=timeout)
    assert out.returncode == 0 and "PASS" in out.stdout, (out.stdout + out.stderr)[-3000:]


def test_seqparallel_flare_equals_dense():
    _run(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.flare import flare_mixer
from repro.core.flare_sp import flare_mixer_seqparallel

from repro.distributed.sharding import make_mesh
from jax import shard_map
mesh = make_mesh((8,), ("seq",))
key = jax.random.PRNGKey(0)
H, M, N, D, B = 4, 16, 64, 8, 2
ks = jax.random.split(key, 3)
q = jax.random.normal(ks[0], (H, M, D)) * 0.5
k = jax.random.normal(ks[1], (B, H, N, D)) * 0.5
v = jax.random.normal(ks[2], (B, H, N, D))

sp = shard_map(
    lambda q_, k_, v_: flare_mixer_seqparallel(q_, k_, v_, axis_name="seq"),
    mesh=mesh,
    in_specs=(P(), P(None, None, "seq", None), P(None, None, "seq", None)),
    out_specs=P(None, None, "seq", None),
)
y_sp = sp(q, k, v)
y_ref = flare_mixer(q, k, v)
np.testing.assert_allclose(np.asarray(y_sp), np.asarray(y_ref), atol=1e-5)

# the sharded_plan helper must map this mesh onto the same backend
from repro.core.dispatch import sharded_plan
assert sharded_plan(mesh, ("seq",), lat_axes="seq").backend == "seqparallel"
print("PASS")
""")


def test_sharded_train_step_matches_single_device():
    _run(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.config import ModelConfig, AttnConfig, TrainConfig
from repro.models.api import get_model
from repro.optim.adamw import init_adamw
from repro.train.steps import make_train_step
from repro.distributed.sharding import param_shardings, batch_spec

cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64, d_ff=128,
                  vocab=128, attn=AttnConfig("gqa", num_heads=4, num_kv_heads=2,
                  head_dim=16), remat="none")
m = get_model(cfg)
key = jax.random.PRNGKey(0)
params = m.init(key)
opt = init_adamw(params)
toks = jax.random.randint(key, (8, 16), 0, 128)
batch = {"tokens": toks, "labels": toks}
tcfg = TrainConfig(steps=10, learning_rate=1e-3)
step = make_train_step(m.loss, tcfg, num_microbatches=2)

# single device
p1, o1, m1 = jax.jit(step)(params, opt, batch)

# 4x2 mesh
from repro.distributed.sharding import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
p_sh = param_shardings(jax.eval_shape(lambda: params), mesh)
o_sh = type(opt)(m=param_shardings(jax.eval_shape(lambda: opt.m), mesh),
                 v=param_shardings(jax.eval_shape(lambda: opt.v), mesh),
                 step=NamedSharding(mesh, P()))
b_sh = {k: NamedSharding(mesh, batch_spec(mesh)) for k in batch}
with mesh:
    p2, o2, m2 = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh))(params, opt, batch)

# bf16 compute: different shardings change partial-sum groupings, so
# cross-layout agreement is limited by bf16 reduction noise (~1e-3).
assert abs(float(m1["loss"]) - float(m2["loss"])) < 5e-3, (m1["loss"], m2["loss"])
for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                               atol=2e-3)
print("PASS")
""")


def test_param_shardings_valid_for_all_archs():
    _run(r"""
import jax
from jax.sharding import NamedSharding
from repro.configs import ARCH_IDS, get_config
from repro.models.api import get_model
from repro.distributed.sharding import param_shardings

from repro.distributed.sharding import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
for arch in ARCH_IDS:
    cfg = get_config(arch)
    model = get_model(cfg)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    sh = param_shardings(shapes, mesh)
    flat_s = jax.tree_util.tree_flatten_with_path(shapes)[0]
    flat_h = jax.tree.leaves(sh)
    assert len(flat_s) == len(flat_h)
    for (kp, leaf), s in zip(flat_s, flat_h):
        assert isinstance(s, NamedSharding)
        # every spec must divide the dims it shards
        spec = s.spec
        for dim, axes in zip(leaf.shape, tuple(spec) + (None,) * (leaf.ndim - len(spec))):
            if axes is None:
                continue
            axes = axes if isinstance(axes, tuple) else (axes,)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            assert dim % size == 0, (arch, kp, leaf.shape, spec)
print("PASS")
""", timeout=900)


def test_grad_compression_in_shard_map():
    _run(r"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import compressed_mean

from repro.distributed.sharding import make_mesh
from jax import shard_map

mesh = make_mesh((8,), ("dp",))
g = jax.random.normal(jax.random.PRNGKey(0), (8, 128))

f = shard_map(
    lambda gs: compressed_mean(gs[0], "dp")[0][None],
    mesh=mesh, in_specs=P("dp", None), out_specs=P("dp", None))
approx = np.asarray(f(g))  # every shard returns the same mean
exact = np.asarray(g.mean(0))
for row in approx:
    rel = np.linalg.norm(row - exact) / np.linalg.norm(exact)
    assert rel < 0.02, rel
print("PASS")
""")
