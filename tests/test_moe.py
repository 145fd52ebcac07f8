"""MoE routing, dispatch and combine invariants."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import MoEConfig
from repro.models.moe import init_moe, moe_ffn, route

KEY = jax.random.PRNGKey(5)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_output_shape_and_finite():
    cfg = MoEConfig(num_experts=8, top_k=2, num_shared=1, expert_ffn=32,
                    shared_ffn=32)
    p = init_moe(KEY, cfg, 64)
    x = jax.random.normal(KEY, (2, 17, 64))
    y, st = moe_ffn(p, x, cfg)
    assert y.shape == x.shape
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(st.aux))
    # every slot is routed to a held expert when all experts are held
    assert float(st.rows_held) == 2 * 17 * 2


def test_router_gates_normalized_deepseek():
    cfg = MoEConfig(num_experts=8, top_k=3, norm_topk_prob=True)
    logits = jax.random.normal(KEY, (4, 10, 8))
    gate, idx, _ = route(logits, cfg)
    np.testing.assert_allclose(gate.sum(-1), 1.0, atol=1e-5)
    assert int(idx.max()) < 8


def test_router_gates_normalized_mixtral():
    """Mixtral's rule (top-k on the logits, softmax over the selected) is
    softmax over all, top-k, renormalized: ``norm_topk_prob=True``."""
    cfg = MoEConfig(num_experts=8, top_k=2, norm_topk_prob=True)
    logits = jax.random.normal(KEY, (4, 10, 8))
    gate, idx, _ = route(logits, cfg)
    val, want_idx = jax.lax.top_k(logits, 2)
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(gate, jax.nn.softmax(val, axis=-1), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(gate.sum(-1), 1.0, atol=1e-5)


def test_every_slot_computed_under_imbalance():
    """Dropless: with every token routed to one expert, each still gets that
    expert's full output (a capacity would have dropped most of them)."""
    cfg = MoEConfig(num_experts=4, top_k=1, expert_ffn=16, norm_topk_prob=False)
    p = init_moe(KEY, cfg, 32)
    # a router that sends every token to expert 2 with gate ~1
    p["router"]["kernel"] = jnp.zeros_like(p["router"]["kernel"])
    x = jax.random.normal(KEY, (1, 64, 32))
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)
    p["router"]["kernel"] = p["router"]["kernel"].at[0, 2].set(50.0)
    y, st = moe_ffn(p, x, cfg)
    xf = x.reshape(64, 32)
    h = jax.nn.silu(xf @ p["w_gate"][2]) * (xf @ p["w_up"][2])
    probs = jax.nn.softmax(xf @ p["router"]["kernel"], axis=-1)
    want = probs[:, 2:3] * (h @ p["w_down"][2])
    np.testing.assert_allclose(y.reshape(64, 32), want, rtol=2e-2, atol=2e-3)
    assert float(st.rows_held) == 64
    assert float(st.load_max_over_mean) == 4.0


def test_uniform_router_balanced_aux():
    """With near-uniform routing the aux loss approaches 1 (its minimum)."""
    cfg = MoEConfig(num_experts=8, top_k=2, expert_ffn=16)
    p = init_moe(KEY, cfg, 32)
    p["router"]["kernel"] = jnp.zeros_like(p["router"]["kernel"])  # uniform logits
    x = jax.random.normal(KEY, (2, 64, 32))
    _, st = moe_ffn(p, x, cfg)
    assert 0.9 < float(st.aux) < 1.3


def test_expert_specialization():
    """Tokens routed to expert e must be processed by expert e's weights:
    zeroing one expert's weights only changes tokens routed there."""
    cfg = MoEConfig(num_experts=4, top_k=1, expert_ffn=16, norm_topk_prob=False)
    p = init_moe(KEY, cfg, 32)
    x = jax.random.normal(KEY, (1, 16, 32))
    logits = x.reshape(16, 32) @ np.asarray(p["router"]["kernel"])
    top1 = np.argmax(logits, -1)
    y0, _ = moe_ffn(p, x, cfg)
    p2 = dict(p)
    p2["w_down"] = p["w_down"].at[2].set(0.0)
    y1, _ = moe_ffn(p2, x, cfg)
    changed = np.abs(np.asarray(y0 - y1)).sum(-1)[0] > 1e-6
    np.testing.assert_array_equal(changed, top1 == 2)


_SHARDED = r"""
import sys
import jax, jax.numpy as jnp, numpy as np
from repro.config import MoEConfig
from repro.distributed.sharding import make_mesh, param_shardings
from repro.models.moe import init_moe, moe_ffn

held, ffn = int(sys.argv[1]), int(sys.argv[2])
cfg = MoEConfig(num_experts=16, top_k=3, num_shared=1, expert_ffn=ffn, shared_ffn=32,
                norm_topk_prob=False, experts_held=held, expert_offset=16 - held)
params = init_moe(jax.random.PRNGKey(0), cfg, 64)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 24, 64), jnp.float32)

def loss(p, x):
    y, st = moe_ffn(p, x, cfg)
    return jnp.sum(jnp.sin(y)) + st.aux, (y, st.rows_held)

f = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))
(_, (y1, rows1)), g1 = f(params, x)
mesh = make_mesh((2, 4), ("data", "model"))
sharded = jax.device_put(params, param_shardings(jax.eval_shape(lambda: params), mesh))
with jax.sharding.set_mesh(mesh):
    hlo = f.lower(sharded, x).as_text()
    (_, (y2, rows2)), g2 = f(sharded, x)
assert "manual" in hlo  # the routed experts ran under shard_map
np.testing.assert_allclose(np.asarray(y2), np.asarray(y1), rtol=1e-5, atol=1e-5)
for a, b in zip(jax.tree.leaves(g2), jax.tree.leaves(g1)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
assert float(rows2) == float(rows1) > 0
print("PASS")
"""


@pytest.mark.parametrize("held,ffn", [(8, 30), (6, 32)], ids=["experts_split", "width_split"])
def test_sharded_layer_matches_unsharded(held, ffn):
    """Under a 2 x 4 (data, model) mesh the routed experts run split over
    "model" (8 held: two experts a rank; 6 held: a quarter of every
    expert's width) and the output and every gradient equal the unsharded
    layer's."""
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", _SHARDED, str(held), str(ffn)],
                         capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert out.returncode == 0 and "PASS" in out.stdout, (out.stdout + out.stderr)[-3000:]
