"""DeepSeek-V2-Lite's parts against the plain reference
(``bench/refs/deepseek_v2_lite.py``) on seeded random weights at a small
size with the same kinds of parts: a dense first layer, MLA with YaRN, 8
routed experts top-3 (all held, or a share of 2), and a shared expert."""
import copy
import dataclasses
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness  # noqa: E402
from bench.drive_train_lm import model_config  # noqa: E402
from repro.models.api import get_model  # noqa: E402
from repro.models.moe import init_moe, moe_ffn, route  # noqa: E402
from repro.nn.modules import swiglu  # noqa: E402

CELL = {"name": "deepseek_v2_lite.train_4k", "config": "deepseek_v2_lite",
        "traffic": "train_4k", "chips": 1}


def small(held=8, offset=0, compute="float32"):
    """The cell's configuration at a small size: the reference's keys and
    the program's ModelConfig, and the reference module."""
    c = harness.load_cell(CELL)
    cfg = copy.deepcopy(c["config"])
    cfg.update(hidden_size=64, intermediate_size=96, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, num_attention_heads=4,
               moe_intermediate_size=32, num_hidden_layers=3, n_routed_experts=held,
               router_experts=8, num_experts_per_tok=3, n_shared_experts=1,
               expert_offset=offset, vocab_size=256)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], original_max_position_embeddings=16)
    p = cfg["program"]
    p.update(num_layers=3, d_model=64, d_ff=96, vocab=256, compute_dtype=compute)
    p["attn"] = dict(p["attn"], num_heads=4, num_kv_heads=4, head_dim=16,
                     mla=dict(kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
                              qk_rope_head_dim=8, v_head_dim=16),
                     rope_scaling=dict(p["attn"]["rope_scaling"],
                                       original_max_position_embeddings=16))
    p["moe"] = dict(p["moe"], num_experts=8, top_k=3, num_shared=1, expert_ffn=32,
                    shared_ffn=32, experts_held=held, expert_offset=offset)
    return cfg, model_config(p), c["ref"]


def _batch(key, b=2, s=40, vocab=256):
    toks = jax.random.randint(key, (b, s + 1), 0, vocab)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _rel(a, b):
    return float(jnp.linalg.norm(a - b)) / max(float(jnp.linalg.norm(b)), 1e-30)


@pytest.mark.parametrize("held,offset", [(8, 0), (2, 2)])
def test_logits_loss_and_grads_match_reference(held, offset):
    cfg, mcfg, ref = small(held, offset)
    params = ref.weights(jax.random.PRNGKey(1), cfg)
    batch = _batch(jax.random.PRNGKey(2))
    model = get_model(mcfg)
    logits, _ = model.forward(params, batch)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.seq_forward(params, t, cfg)[0] for t in batch["tokens"]])
        lr, gr = jax.value_and_grad(ref.loss)(params, batch, cfg)
    assert _rel(logits, want) < 1e-5
    lp, gp = jax.value_and_grad(model.loss)(params, batch)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    flat_p = jax.tree_util.tree_flatten_with_path(gp)[0]
    flat_r = dict(jax.tree_util.tree_flatten_with_path(gr)[0])
    assert len(flat_p) == len(flat_r)
    for path, g in flat_p:
        assert _rel(g, flat_r[path]) < 1e-4, jax.tree_util.keystr(path)


def test_router_softmax_over_all_then_top_k():
    cfg, mcfg, ref = small()
    logits = jax.random.normal(jax.random.PRNGKey(3), (50, 8)) * 2.0
    gate, idx, probs = route(logits, mcfg.moe)
    full = jax.nn.softmax(logits, axis=-1)
    top, top_idx = jax.lax.top_k(full, 3)
    np.testing.assert_array_equal(idx, top_idx)
    np.testing.assert_allclose(gate, top, rtol=1e-6)
    np.testing.assert_allclose(probs, full, rtol=1e-6)
    assert float(jnp.max(gate.sum(-1))) < 1.0  # DeepSeek-V2 leaves them unnormalized
    # the reference's router gives the same gates on the same input
    x = jax.random.normal(jax.random.PRNGKey(4), (50, 64))
    w = jax.random.normal(jax.random.PRNGKey(5), (64, 8)) / 8.0
    g_ref, _, i_ref = ref.router({"router": {"kernel": w}}, x, cfg)
    g, i, _ = route(x @ w, mcfg.moe)
    np.testing.assert_array_equal(i, i_ref)
    np.testing.assert_allclose(jnp.take_along_axis(g_ref, i_ref, axis=-1), g, rtol=1e-5)


def test_yarn_at_the_published_config():
    from repro.configs import get_config
    from repro.models.attention import mla_softmax_scale
    from repro.models.rope import yarn_correction_range, yarn_frequencies

    c = harness.load_cell(CELL)
    cfg, ref = c["config"], c["ref"]
    attn = get_config("deepseek_v2_lite_16b").attn
    assert yarn_correction_range(64, 10000.0, attn.rope_scaling) == (10, 23)
    assert ref.yarn_low_high(cfg) == (10, 23)
    assert mla_softmax_scale(attn) == pytest.approx(0.11472, abs=5e-6)
    assert ref.softmax_scale(cfg) == pytest.approx(0.11472, abs=5e-6)
    np.testing.assert_allclose(yarn_frequencies(64, 10000.0, attn.rope_scaling),
                               ref.yarn_inv_freq(cfg), rtol=1e-6)
    # the model's MoE config as the benchmark runs it is the catalog's
    assert model_config(cfg["program"]).attn.rope_scaling == attn.rope_scaling


def test_expert_shares_add_up_to_the_uncut_layer():
    """The 8 one-expert shares' routed outputs, with the shared expert
    counted once, add up to the reference's whole layer."""
    cfg, mcfg, ref = small(held=8)
    moe_cfg = mcfg.moe
    full = init_moe(jax.random.PRNGKey(6), moe_cfg, 64)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 24, 64))
    shared = swiglu(full["shared"], x)  # what every share computes alike
    total = shared
    for i in range(8):
        share = dict(full, w_gate=full["w_gate"][i:i + 1], w_up=full["w_up"][i:i + 1],
                     w_down=full["w_down"][i:i + 1])
        y_i, st = moe_ffn(share, x, dataclasses.replace(moe_cfg, experts_held=1,
                                                         expert_offset=i))
        total = total + (y_i - shared)
    with jax.default_matmul_precision("highest"):
        want = jnp.stack([ref.moe(full, xs, dict(cfg, n_routed_experts=8, expert_offset=0))[0]
                          for xs in x])
    assert _rel(total, want) < 1e-5


def test_dropless_when_every_token_picks_one_held_expert():
    """A share holding experts 2-3: with every token routed to expert 3
    (and two experts held elsewhere), each token still gets expert 3's full
    output, however many tokens that is."""
    cfg, mcfg, ref = small(held=2, offset=2)
    p = init_moe(jax.random.PRNGKey(8), mcfg.moe, 64)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 64, 64))
    x = x.at[..., 0].set(jnp.abs(x[..., 0]) + 1.0)
    k = jnp.zeros((64, 8)).at[0, 3].set(40.0).at[0, 6].set(30.0).at[0, 7].set(20.0)
    p["router"]["kernel"] = k
    y, st = moe_ffn(p, x, mcfg.moe)
    assert float(st.rows_held) == 64  # one held slot per token; the others go elsewhere
    with jax.default_matmul_precision("highest"):
        want = ref.moe(p, x[0], cfg)[0]
    assert _rel(y[0], want) < 1e-5


def test_trainer_returns_the_routing_counters():
    """A model that counts returns ``moe.rows_held`` and
    ``moe.load_max_over_mean`` with each step's metrics; one that does not,
    nothing more."""
    import tempfile

    from repro.config import TrainConfig
    from repro.configs import get_smoke_config
    from repro.train.trainer import Trainer

    for arch, counted in (("deepseek_v2_lite_16b", True), ("qwen2_1_5b", False)):
        cfg = get_smoke_config(arch)
        with tempfile.TemporaryDirectory() as d:
            tr = Trainer(get_model(cfg), TrainConfig(steps=2, checkpoint_dir=d,
                                                     checkpoint_every=100))
            hist = tr.fit(lambda s: _batch(jax.random.PRNGKey(s), 2, 16, cfg.vocab))
        assert ("moe.rows_held" in hist[-1]) == counted
        if counted:
            # all experts held: every slot of 2 x 16 tokens, top-3, in 2 MoE layers
            assert hist[-1]["moe.rows_held"] == 2 * 16 * 3 * 2
            assert hist[-1]["moe.load_max_over_mean"] >= 1.0


_TRAINER_ON_MESH = r"""
import tempfile
import jax, numpy as np
from repro.config import TrainConfig
from repro.configs.deepseek_v2_lite_16b import smoke_config
from repro.distributed.sharding import make_mesh
from repro.models.api import get_model
from repro.train.trainer import Trainer

cfg = smoke_config()
toks = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (3, 8, 33), 0, cfg.vocab))
feed = lambda s: {"tokens": toks[s % 3, :, :-1], "labels": toks[s % 3, :, 1:]}
runs = []
for mesh in (None, make_mesh((2, 4), ("data", "model"))):
    tr = Trainer(get_model(cfg), TrainConfig(steps=3, checkpoint_every=1 << 30,
                 log_every=1 << 30, checkpoint_dir=tempfile.mkdtemp()), mesh)
    hist = tr.fit(feed)
    runs.append(([h["loss"] for h in hist], [h["moe.rows_held"] for h in hist],
                 tr.step_program(feed(0)).as_text(), jax.device_get(tr.params)))
(l1, r1, _, p1), (l2, r2, hlo, p2) = runs
assert "shard_map" in hlo  # the routed experts ran under shard_map (op_name)
assert r1 == r2
np.testing.assert_allclose(l2, l1, rtol=2e-3)
# bf16 compute: a gradient element near nought can change sign with the
# layout's reduction order, and Adam then moves it by 2 x lr (1e-3) the
# other way in each of the two steps that update (lr is 0 at step 0)
for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(p1)):
    np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32), atol=4e-3)
print("PASS")
"""


def test_trainer_on_a_mesh_matches_one_device():
    """``Trainer`` on a 2 x 4 (data, model) mesh: the routed experts run
    split over "model", the step keeps the params' layout from step to
    step, and three steps give the losses, routing counters and params of
    one device."""
    import subprocess

    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("JAX_PLATFORMS", None)
    out = subprocess.run([sys.executable, "-c", _TRAINER_ON_MESH], capture_output=True,
                         text=True, env=env, cwd=ROOT, timeout=600)
    assert out.returncode == 0 and "PASS" in out.stdout, (out.stdout + out.stderr)[-3000:]
