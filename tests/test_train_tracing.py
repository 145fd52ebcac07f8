"""The train step instrumented from inside, on the CPU.

- The compiled train step (``Trainer.step_program``) names the packed FLARE
  kernel's launches and its layout work in its instructions' ``op_name``s:
  ``flare_packed_fwd`` in the forward pass and again under JAX's
  ``rematted_computation`` (``remat: "full"``), ``flare_packed_bwd`` in
  the backward pass, ``flare_packed.layout`` around the packing.
- ``Trainer.fit`` opens its host phases (``train/data``, ``train/dispatch``,
  ``train/sync``, ``train/checkpoint``) as tracer spans that carry the step
  number, and still emits ``train_step`` once per step after them.
- Those spans and the profiler's host events of the same names lie on one
  clock.
"""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import TrainConfig
from repro.configs import get_smoke_config
from repro.models.api import get_model
from repro.obs import NULL_TRACER, Tracer, phase
from repro.train.trainer import Trainer

PHASES = ("train/data", "train/dispatch", "train/sync")


def _batch(step, b=2, n=64):
    rng = np.random.default_rng(step)
    return {"x": rng.standard_normal((b, n, 3)).astype(np.float32),
            "y": rng.standard_normal((b, n, 1)).astype(np.float32)}


def _trainer(tmp_path, *, tracer=None, policy=None, remat="none", every=1 << 30):
    cfg = dataclasses.replace(get_smoke_config("flare_pde"), remat=remat)
    tcfg = TrainConfig(steps=100, checkpoint_every=every, log_every=1 << 30,
                       checkpoint_dir=str(tmp_path / "ckpt"))
    return Trainer(get_model(cfg, policy=policy), tcfg, tracer=tracer)


def test_step_program_names_packed_launches_and_layout(tmp_path):
    from repro.core.policy import MixerPolicy

    tr = _trainer(tmp_path, policy=MixerPolicy(backends=("packed",)), remat="full")
    batch = {k: jnp.asarray(v) for k, v in _batch(0).items()}
    prog = tr.step_program(batch)
    assert tr.step_program(jax.eval_shape(lambda: batch)) is prog
    assert prog.memory_analysis() is not None
    names = re.findall(r'op_name="([^"]*)"', prog.as_text())
    remat = [n for n in names if "rematted_computation" in n]
    primal = [n for n in names if "rematted_computation" not in n]
    assert any("/flare_packed.layout/" in n for n in names)
    assert any("/flare_packed_fwd/" in n for n in remat)
    assert any("/flare_packed_fwd/" in n for n in primal)
    assert any("/flare_packed_bwd/" in n for n in primal)
    assert not any("/flare_packed_bwd/" in n for n in remat)
    # the scopes name the same program fit runs: one step still trains
    hist = tr.fit(lambda s: _batch(s), steps=1)
    assert np.isfinite(hist[0]["loss"])


def test_fit_records_phases_per_step(tmp_path):
    rec = Tracer()
    tr = _trainer(tmp_path, tracer=rec, every=2)
    hist = tr.fit(lambda s: _batch(s), steps=3)
    spans = rec.events
    steps = [e for e in spans if e.name == "train_step"]
    assert [e.args["step"] for e in steps] == [0, 1, 2]
    for n, step in enumerate(steps):
        mine = [e for e in spans if e.name.startswith("train/") and e.args == {"step": n}]
        want = PHASES + (("train/checkpoint",) if n == 1 else ())
        assert tuple(e.name for e in mine) == want
        assert all(a.ts + a.dur <= b.ts for a, b in zip(mine, mine[1:]))
        # train_step keeps its stamps: from before the data feed to after
        # the metric sync, the step time fit reports, emitted after them
        assert step.ts <= mine[0].ts and mine[2].ts + mine[2].dur <= step.ts + step.dur
        assert step.dur == hist[n]["time"]
        assert spans.index(step) > spans.index(mine[-1])
    assert all(e.cat == "train" and e.ph == "X" for e in spans)


def test_fit_with_null_tracer_records_nothing(tmp_path):
    tr = _trainer(tmp_path)
    assert tr.tracer is NULL_TRACER
    tr.fit(lambda s: _batch(s), steps=2)
    assert NULL_TRACER.events == []


def test_phase_without_tracer_is_a_bare_annotation():
    off = Tracer(enabled=False)
    with phase(off, "train/data", args={"step": 0}):
        pass
    assert off.events == []
    on = Tracer()
    with pytest.raises(ValueError):
        with phase(on, "train/sync", args={"step": 3}):
            raise ValueError
    assert [(e.name, e.args) for e in on.events] == [("train/sync", {"step": 3})]


def test_phase_spans_share_the_profilers_host_clock(tmp_path):
    """A phase's tracer span and the profiler's host event of the same name
    start within 1 ms of each other: the profile's host events sit at its
    ``profile_start_time`` (wall clock, ns) plus their offset."""
    from jax.profiler import ProfileData

    rec = Tracer()
    tr = _trainer(tmp_path, tracer=rec)
    tr.fit(lambda s: _batch(s), steps=1)      # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "prof"), profiler_options=opts)
    try:
        tr.fit(lambda s: _batch(s), steps=3)
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(glob.glob(str(tmp_path / "prof" / "plugins" / "profile"
                                             / "*" / "*.xplane.pb"))[0])
    start = host = None
    for plane in pd.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = stats["profile_start_time"] * 1e-9
        if plane.name.startswith("/host:CPU"):
            host = [(e.name, e.start_ns * 1e-9) for line in plane.lines
                    for e in line.events if e.name.startswith("train/")]
    assert start is not None and host
    for name in PHASES:
        events = sorted(t for n, t in host if n == name)
        spans = [e.ts for e in rec.events if e.name == name and e.args["step"] >= 1]
        assert len(events) == len(spans) == 2
        for t_event, t_span in zip(events, spans):
            assert abs(start + t_event - t_span) < 1e-3
