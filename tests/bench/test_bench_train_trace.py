"""The train-step metrics read through the compiled step's HLO
(``bench/metrics/_hlo.py``, ``train.remat_share``,
``flare_packed.layout_share``): checked by hand count on hand-built traces
and HLO text, and pinned on one traced train step of ``flare_pde`` recorded
on a TPU v5e (``bench/testdata/small_train_trace.xplane.pb`` with
``small_train_trace.hlo.txt``: 8 blocks, C=64, H=8, M=256, a batch of 2 x
32 x 32 points; ``bench/testdata/record_small_train_trace.py``)."""
import copy
import os
import re
import shutil
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import harness  # noqa: E402
from bench.metrics import _hlo, _trace  # noqa: E402

DATA = os.path.join(ROOT, "bench", "testdata")
REMAT = harness.metric_reader("train.remat_share")
LAYOUT = harness.metric_reader("flare_packed.layout_share")

# a module's text as XLA prints it: computations, ROOT lines, an
# instruction without metadata, fused computations' own instructions
HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %pad.1 = f32[8]{0} pad(f32[4]{0} %param_0, f32[] %c), padding=0_4, metadata={op_name="jit(train_step)/jvp(kernels.flare_packed)/flare_packed.layout/jit(_pad)/pad" source_file="x.py" source_line=1}
}

ENTRY %main.9 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %pad_fusion = f32[8]{0} fusion(f32[8]{0} %p0), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/jvp(kernels.flare_packed)/flare_packed.layout/jit(_pad)/pad" source_file="x.py" source_line=1}
  %flare_packed_fwd.16 = (f32[8]{0}, f32[8]{0}) custom-call(f32[8]{0} %pad_fusion), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(kernels.flare_packed)/flare_packed_fwd/pallas_call"}
  %flare_packed_fwd.24 = (f32[8]{0}, f32[8]{0}) custom-call(f32[8]{0} %pad_fusion), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/kernels.flare_packed/flare_packed_fwd/pallas_call"}
  %copy.3 = f32[8]{0} copy(f32[8]{0} %p0), metadata={op_name="jit(train_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/kernels.flare_packed/flare_packed.layout/reshape"}
  %flare_packed_bwd.8 = (f32[8]{0}) custom-call(f32[8]{0} %copy.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(jvp()))/checkpoint/kernels.flare_packed/flare_packed_bwd/flare_packed_bwd/pallas_call"}
  %copy.4 = f32[8]{0} copy(f32[8]{0} %p0)
  ROOT %fusion.2 = f32[8]{0} fusion(f32[8]{0} %copy.4), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/mul"}
}
"""


def test_op_names_from_module_text():
    names = _hlo.op_names(HLO)
    assert names["flare_packed_fwd.24"].endswith(
        "rematted_computation/kernels.flare_packed/flare_packed_fwd/pallas_call")
    assert names["pad.1"] == names["pad_fusion"]
    assert names["copy.4"] == "" and names["fusion.2"] == "jit(train_step)/mul"
    assert "HloModule jit_train_step" not in " ".join(names)


def _ev(instr, s, e):
    return (f"%{instr} = f32[8]{{0}} op(...)", s, e)


def _trace_of(ops, *, other=()):
    """One device; the train step's program over [0, 10], a second program
    over [10, 12] whose ops the text does not name."""
    mods = [("jit_train_step(1)", 0.0, 10.0), ("jit_convert(2)", 10.0, 12.0)]
    return _trace.Trace(0.0, 12.0, ops={0: list(ops) + list(other)},
                        modules={0: mods})


# self seconds: pad_fusion 1, primal fwd 2, recomputed fwd 2, remat layout
# copy 0.5, bwd 3, unnamed copy 0.1, fusion 0.4 (all in the train step);
# busy time 9 + 1 (the other program's op) = 10
STEP = [_ev("pad_fusion", 0.0, 1.0), _ev("flare_packed_fwd.16", 1.0, 3.0),
        _ev("flare_packed_fwd.24", 3.0, 5.0), _ev("copy.3", 5.0, 5.5),
        _ev("flare_packed_bwd.8", 5.5, 8.5), _ev("copy.4", 8.5, 8.6),
        _ev("fusion.2", 8.6, 9.0)]
OTHER = [_ev("convert.1", 10.0, 11.0)]


def _ctx(tr, hlo=HLO):
    return {"trace": tr, "hlo": hlo}


def test_shares_by_hand_count():
    tr = _trace_of(STEP, other=OTHER)
    assert _trace.busy_s(tr) == pytest.approx(10.0)
    # recomputed: the second forward (2) and the remat layout copy (0.5)
    assert REMAT.read(_ctx(tr)) == pytest.approx(100 * 2.5 / 10)
    # layout: the primal pad fusion (1) and the remat copy (0.5)
    assert LAYOUT.read(_ctx(tr)) == pytest.approx(100 * 1.5 / 10)


def test_nested_ops_count_self_time():
    # a loop op over [0, 4] holding the primal forward: its own time is 2
    loop = ("%while.7 = f32[8]{0} while(...)", 0.0, 4.0)
    text = HLO.replace("  %copy.4 =", '  %while.7 = f32[8]{0} while(f32[8]{0} %p0), '
                       'metadata={op_name="jit(train_step)/rematted_computation/while"}\n'
                       "  %copy.4 =")
    tr = _trace_of([loop, _ev("flare_packed_fwd.16", 1.0, 3.0)])
    assert REMAT.read(_ctx(tr, text)) == pytest.approx(100 * 2 / 4)


def test_coverage_below_the_floor_gives_nothing():
    # 0.6 s of 9.6 s in the train step unknown to the text: 93.75% covered
    unknown = [_ev("mystery.1", 9.0, 9.6)]
    tr = _trace_of(STEP + unknown)
    assert _hlo.attribute(tr, HLO) is None
    assert REMAT.read(_ctx(tr)) is None and LAYOUT.read(_ctx(tr)) is None
    # 0.4 s of 9.4 s unknown: 95.7% covered, read
    tr = _trace_of(STEP + [_ev("mystery.1", 9.0, 9.4)])
    assert REMAT.read(_ctx(tr)) == pytest.approx(100 * 2.5 / 9.4)


def test_other_programs_are_not_matched_by_name():
    # an op of another program that happens to share an instruction name
    tr = _trace_of(STEP, other=[_ev("flare_packed_fwd.24", 10.0, 11.0)])
    assert REMAT.read(_ctx(tr)) == pytest.approx(100 * 2.5 / 10)


def test_no_trace_text_or_device_gives_nothing():
    tr = _trace_of(STEP)
    assert REMAT.read(_ctx(tr, hlo=None)) is None
    assert LAYOUT.read({"trace": None}) is None
    # a host-only trace (the CPU) never reaches for a program text
    assert REMAT.read({"trace": _trace.Trace(0.0, 1.0)}) is None


def small_cell():
    c = harness.cell("flare_pde.train_40k")
    cfg = copy.deepcopy(c["config"])
    cfg.update(num_blocks=2, hidden_size=16, num_heads=2, num_latents=16)
    cfg["program"].update(num_layers=2, d_model=16, d_ff=16, flare_heads=2, flare_latents=16)
    mix = dict(c["mix"], batch=2, grid=8, cg_iters=20, distinct_batches=2)
    return dict(c, config=cfg, mix=mix)


def test_reader_compiles_the_program_drive_train_runs():
    """Without a text in ``ctx`` the readers compile the step through the
    program's ``Trainer.step_program`` on abstract batches; the instructions
    and their op_names are those of the step ``drive_train`` runs on its own
    weights and device batches."""
    from bench import drive_train

    c = small_cell()
    text = _hlo._compile_step(c["config"], c["mix"])
    st = drive_train.first_steps(c, 2147483659, drive_train._StepHook())
    try:
        driven = st["trainer"].step_program(st["feed"](0)).as_text()
    finally:
        shutil.rmtree(st["ckpt"], ignore_errors=True)
    assert _hlo.op_names(text) == _hlo.op_names(driven)


def test_program_without_step_program_gives_nothing(monkeypatch):
    from repro.train.trainer import Trainer

    monkeypatch.delattr(Trainer, "step_program")
    assert _hlo._compile_step(small_cell()["config"], small_cell()["mix"]) is None


def test_step_hook_still_stops_fit_after_n_steps(tmp_path):
    """The benchmark's tracer sees the program's phase spans besides
    ``train_step``; it still stops the loop (and would stop the profiler)
    after N steps, once each."""
    from bench import drive_train

    c = small_cell()
    hook = drive_train._StepHook()
    st = drive_train.first_steps(c, 2147483659, hook)
    try:
        stops = []
        start = st["trainer"].step
        hook.arm(time.time(), steps=2, on_stop=lambda: stops.append(1))
        st["trainer"].fit(st["feed"], steps=1 << 30)
        assert st["trainer"].step - start == 2 and len(hook.ends) == 2
        assert stops == [1]
    finally:
        shutil.rmtree(st["ckpt"], ignore_errors=True)


@pytest.fixture(scope="module")
def small_train():
    tr = _trace.load(os.path.join(DATA, "small_train_trace.xplane.pb"))
    with open(os.path.join(DATA, "small_train_trace.hlo.txt")) as f:
        return tr, f.read()


def test_small_train_trace_readings(small_train):
    tr, text = small_train
    got = _hlo.attribute(tr, text)
    step = sum(t for _, t in _trace.self_times(_trace.inside(tr.ops[0], _hlo.main_module(tr))))
    # the fixture's text leaves out the parameters' copy-start/copy-done
    assert sum(t for _, t in got) / step == pytest.approx(0.98007, abs=1e-5)
    ctx = _ctx(tr, text)
    assert REMAT.read(ctx) == pytest.approx(29.333458, rel=1e-6)
    assert LAYOUT.read(ctx) == pytest.approx(1.4393766, rel=1e-6)


def test_small_train_trace_tells_launches_apart(small_train):
    tr, text = small_train
    names = _hlo.op_names(text)
    calls = _trace.matching(tr, r"tpu_custom_call")
    labels = [_trace.op_label(n) for n, _, _ in calls]
    assert labels.count("flare_packed_fwd") == 16 and labels.count("flare_packed_bwd") == 8
    ops = [names[re.match(r"%?([^\s=]+)", n).group(1)] for n, _, _ in calls]
    primal = [o for o in ops if "/flare_packed_fwd/" in o and "rematted_computation" not in o]
    remat = [o for o in ops if "/flare_packed_fwd/" in o and "rematted_computation" in o]
    bwd = [o for o in ops if "/flare_packed_bwd/" in o]
    assert len(primal) == len(remat) == len(bwd) == 8
    assert all(o.startswith("jit(train_step)/transpose(") for o in remat + bwd)
    # the launches keep their output arity: the roofline reads them as before
    kinds = [k for k, _ in harness.metric_reader("flare_packed_roofline").launches(tr)]
    assert kinds.count("fwd") == 16 and kinds.count("bwd") == 8


def test_small_train_trace_host_phases(small_train):
    tr, _ = small_train
    named = sorted(h[0] for h in tr.host)
    assert named == ["bench.trace_window", "train/data", "train/dispatch",
                     "train/step", "train/sync"]
    gaps = _trace.idle_gaps(tr)
    assert gaps and all(name.startswith("train/") for name, _ in gaps)
