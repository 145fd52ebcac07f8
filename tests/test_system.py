"""End-to-end behaviour tests for the whole system.

1. PDE surrogate: train the paper's FLARE model on real CG-solved Darcy data
   and beat the predict-zero baseline (relative L2 < 1).
2. FLARE-LM: train the causal-FLARE decoder on the Markov token stream and
   beat the unigram entropy.
3. The fused-kernel path and the SDPA path agree on the same params.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.config import AttnConfig, ModelConfig, TrainConfig
from repro.data.pde_data import darcy_batch
from repro.data.synthetic import TokenStream
from repro.models import pde
from repro.models.api import get_model
from repro.optim.adamw import adamw_update, init_adamw

import pytest

# multi-minute suite: deselect with `-m 'not slow'` (see pyproject.toml)
pytestmark = pytest.mark.slow

KEY = jax.random.PRNGKey(0)


def _step(loss_fn, p, o, b, lr):
    l, g = jax.value_and_grad(loss_fn)(p, b)
    p, o, _ = adamw_update(p, g, o, lr=lr, grad_clip=1.0)
    return p, o, l


def _train(loss_fn, params, batches, *, lr=2e-3, steps=60):
    opt = init_adamw(params)
    step = jax.jit(lambda p, o, b: _step(loss_fn, p, o, b, lr))
    losses = []
    for i in range(steps):
        params, opt, l = step(params, opt, batches[i % len(batches)])
        losses.append(float(l))
    return params, losses


def test_pde_surrogate_end_to_end():
    batches = [darcy_batch(0, i, 4, grid=16, cg_iters=120) for i in range(3)]
    params = pde.init_surrogate(KEY, "flare", in_dim=3, out_dim=1, dim=32,
                                num_blocks=2, num_heads=4, num_latents=16)
    loss_fn = lambda p, b: pde.surrogate_loss(p, b, mixer="flare", num_heads=4)
    params, losses = _train(loss_fn, params, batches, steps=80)
    # relative L2 < 1 means better than predicting zero; expect much better
    assert losses[-1] < 0.7 * losses[0], (losses[0], losses[-1])
    assert losses[-1] < 0.9

    # held-out generalization
    test_batch = darcy_batch(0, 99, 4, grid=16, cg_iters=120)
    test_err = float(pde.surrogate_loss(params, test_batch, mixer="flare", num_heads=4))
    assert test_err < 1.0


def test_flare_lm_end_to_end():
    V = 64
    cfg = ModelConfig(name="flm", family="flare_lm", num_layers=2, d_model=64,
                      d_ff=128, vocab=V,
                      attn=AttnConfig("flare_stream", num_heads=4, head_dim=16,
                                      flare_latents=8, flare_chunk=8),
                      remat="none")
    model = get_model(cfg)
    params = model.init(KEY)
    stream = TokenStream(V, 32, seed=5)
    batches = [{k: jnp.asarray(v) for k, v in stream.batch(i, 0, 1, 8).items()}
               for i in range(5)]
    params, losses = _train(model.loss, params, batches, steps=60)
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


def test_kernel_path_matches_sdpa_path():
    """The packed-plan forward == the sdpa-plan forward on the same params."""
    from repro.core.policy import MixerPolicy

    params = pde.init_surrogate(KEY, "flare", in_dim=3, out_dim=1, dim=32,
                                num_blocks=1, num_heads=4, num_latents=16)
    x = jax.random.normal(KEY, (2, 64, 3))
    y1 = pde.surrogate_forward(params, x, mixer="flare", num_heads=4,
                               policy=MixerPolicy(backends=("sdpa",)))
    y2 = pde.surrogate_forward(params, x, mixer="flare", num_heads=4,
                               policy=MixerPolicy(backends=("packed",)))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


def test_all_mixers_run_one_step():
    """Every Table-1 baseline trains one step without NaN."""
    batch = darcy_batch(0, 0, 2, grid=8, cg_iters=60)
    for mixer in ("flare", "vanilla", "perceiver", "linformer", "transolver"):
        params = pde.init_surrogate(KEY, mixer, in_dim=3, out_dim=1, dim=32,
                                    num_blocks=1, num_heads=4, num_latents=8)
        loss_fn = lambda p, b: pde.surrogate_loss(p, b, mixer=mixer, num_heads=4)
        l, g = jax.value_and_grad(loss_fn)(params, batch)
        assert np.isfinite(float(l)), mixer
        for leaf in jax.tree.leaves(g):
            assert bool(jnp.isfinite(leaf).all()), mixer
