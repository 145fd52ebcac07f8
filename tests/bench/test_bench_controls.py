"""The comparison that decides ``correct``, shown to fail.

Each test drives a whole benchmark run (``bench/run.py``'s ``main``) on the
CPU at a small size, with the harness's look for a chip answered as a TPU
v5e would answer it: sound, it comes out correct; with the timed path
broken underneath (a training step that returns its state unchanged, one
that takes the mean over half of each batch), it comes out not correct. One chip, so no exchange
between chips exists to leave out.

The control, the program one precision below the configuration's, is kept
here too at a size the CPU holds; the benchmark's own runs never run it
(``bench/control.py`` reads it on the chip).
"""
import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402

def small_train():
    c = harness.cell("flare_pde.train_40k")
    cfg = copy.deepcopy(c["config"])
    cfg.update(num_blocks=2, hidden_size=16, num_heads=2, num_latents=16)
    cfg["program"].update(num_layers=2, d_model=16, d_ff=16, flare_heads=2, flare_latents=16)
    mix = dict(c["mix"], batch=4, grid=8, cg_iters=20, distinct_batches=6)
    return dict(c, config=cfg, mix=mix)


@pytest.fixture
def on_chip(monkeypatch):
    """The harness's look for a chip answered as one TPU v5e answers it,
    with no compile cache set up; ``run(c)`` drives a whole run of cell
    ``c``."""
    from bench import run

    def paths():
        for p in (os.path.join(harness.ROOT, "src"), harness.ROOT):
            if p not in sys.path:
                sys.path.insert(0, p)

    monkeypatch.setattr(harness, "setup_env", paths)
    monkeypatch.setattr(harness, "device", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": chips})

    def go(c, trace=0):
        monkeypatch.setattr(harness, "cell", lambda name, bench=None: c)
        return run.main(["--workload", c["cell"]["name"], "--seed", "2147483659",
                         "--seconds", "1", "--trace", str(trace)])

    return go


def run_cell(c, capsys, on_chip, trace=0):
    rc = on_chip(c, trace)
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(out)[-1] == "checks"
    return out


def test_train_sound(capsys, on_chip):
    out = run_cell(small_train(), capsys, on_chip)
    assert out["correct"] is True
    assert set(out["metrics"]) == {"setup_s", "train_step_s"}


def test_train_state_unchanged(capsys, monkeypatch, on_chip):
    import repro.train.trainer as trainer_mod

    make = trainer_mod.make_train_step

    def frozen(loss_fn, tcfg, **kw):
        step = make(loss_fn, tcfg, **kw)

        def run(params, opt_state, batch):
            return (params, opt_state) + (step(params, opt_state, batch)[2],)

        return run

    monkeypatch.setattr(trainer_mod, "make_train_step", frozen)
    out = run_cell(small_train(), capsys, on_chip)
    assert out["correct"] is False
    assert out["checks"]["update_norm_gap"]["value"] == pytest.approx(1.0)


def test_train_half_batch(capsys, monkeypatch, on_chip):
    import repro.train.trainer as trainer_mod

    make = trainer_mod.make_train_step

    def half(loss_fn, tcfg, **kw):
        step = make(loss_fn, tcfg, **kw)

        def run(params, opt_state, batch):
            return step(params, opt_state,
                        {k: v[: v.shape[0] // 2] for k, v in batch.items()})

        return run

    monkeypatch.setattr(trainer_mod, "make_train_step", half)
    out = run_cell(small_train(), capsys, on_chip)
    assert out["correct"] is False


def test_train_control_readings():
    """The control (the program's own bfloat16 path: weights held and
    updated in bfloat16) and the half-batch fault planted in the reference,
    read at a size the CPU holds: each fails the cell's limits, and the
    program passes them."""
    from bench import control

    row = control.train_readings(small_train(), 2147483659)
    assert row["program"]["correct"] is True
    assert row["control"]["correct"] is False
    assert row["half_batch"]["correct"] is False
