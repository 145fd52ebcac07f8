"""The benchmark's plain references against the program, on the CPU at
small sizes: the FLARE operator against the program's kernel oracle, the
PDE surrogate's loss and gradients against the program's model on the same
weights, and its learning-rate schedule against the program's."""
import copy
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import gen, harness  # noqa: E402

from bench.drive_train import model_config  # noqa: E402


def small_pde():
    c = harness.cell("flare_pde.train_40k")
    cfg = copy.deepcopy(c["config"])
    cfg.update(num_blocks=2, hidden_size=16, num_heads=2, num_latents=16)
    cfg["program"].update(num_layers=2, d_model=16, d_ff=16, flare_heads=2, flare_latents=16)
    mix = dict(c["mix"], batch=2, grid=8, cg_iters=20, distinct_batches=6)
    return dict(c, config=cfg, mix=mix)


def test_flare_mix_matches_kernel_oracle():
    from repro.kernels.ref import flare_mixer_ref

    ref = harness.cell("flare_pde.train_40k")["ref"]
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    b, h, n, m, d = 2, 3, 40, 12, 8
    q = jax.random.normal(k[0], (h, m, d))
    kk = jax.random.normal(k[1], (b, h, n, d))
    v = jax.random.normal(k[2], (b, h, n, d))
    got = ref.flare_mix(q, kk, v)
    want = flare_mixer_ref(jnp.broadcast_to(q[None], (b, h, m, d)).reshape(b * h, m, d),
                           kk.reshape(b * h, n, d), v.reshape(b * h, n, d))
    np.testing.assert_allclose(got.reshape(b * h, n, d), want, rtol=1e-5, atol=1e-5)


def test_pde_loss_and_grads_match_program():
    from repro.models.api import get_model

    c = small_pde()
    cfg, ref = c["config"], c["ref"]
    params = ref.weights(jax.random.PRNGKey(1), cfg)
    batch = gen.darcy_batches(c["mix"], jax.random.PRNGKey(2))[0]
    model = get_model(model_config(cfg["program"]))
    lp, gp = jax.value_and_grad(model.loss)(params, batch)
    lr, gr = jax.value_and_grad(ref.loss)(params, batch)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-4 * float(jnp.linalg.norm(b)) + 1e-12


def test_onecycle_matches_program_schedule():
    from repro.optim.schedule import onecycle_schedule

    ref = harness.cell("flare_pde.train_40k")["ref"]
    opt = {"learning_rate": 1e-3, "steps": 50, "warmup_frac": 0.1}
    for s in (0, 1, 4, 5, 6, 30, 49, 60):
        want = float(onecycle_schedule(s, total_steps=50, peak_lr=1e-3, warmup_frac=0.1))
        # the program's schedule is float32 arithmetic
        assert ref.onecycle_lr(s, opt) == pytest.approx(want, rel=1e-4)
