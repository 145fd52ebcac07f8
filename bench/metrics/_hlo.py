"""The train step's compiled HLO as the per-layer readers use it: a map from
each instruction's name to its ``op_name``, and the device time of the
traced window attributed through that map.

On the TPU a device op event is named by its HLO instruction
(``%flare_packed_fwd.16 = ...``), the name the compiled program's text
gives it, and the text gives each instruction ``metadata={op_name=...}``:
the JAX name stack of the code that made it. That holds the program's named
scopes (``flare_packed_fwd``, ``flare_packed_bwd``, ``flare_packed.layout``)
and JAX's own marks: ``jvp(...)`` and ``transpose(...)`` for the forward
and backward passes, ``rematted_computation`` for what ``jax.checkpoint``
computes a second time.

The text is ``ctx["hlo"]`` where ``drive_train`` puts it there; otherwise the
program's ``Trainer.step_program`` compiles the step for the cell's shapes
once more (the same program as the window's: the same lowering, so the
same compile or a persistent-cache load of it), and a program without
``step_program`` gives no text. A trace
whose train-step ops the text does not name, to at least ``COVERAGE`` of
their device time, gives no number.
"""
from __future__ import annotations

import re

from bench.metrics import _trace

COVERAGE = 0.95

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=%]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_EVENT = re.compile(r"%?([^\s=]+)")


def op_names(text: str) -> dict:
    """{instruction name: op_name} of every instruction in an HLO module's
    text (``""`` where the instruction carries no op_name)."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            on = _OP_NAME.search(m.group(2))
            out[m.group(1)] = on.group(1) if on else ""
    return out


def main_module(tr):
    """The events of the program that took most device time in the window
    (the train step's), as ``flare_packed_roofline.launches`` picks it."""
    mods = _trace.matching(tr, r".", modules=True)
    if not mods:
        return []
    main = max(set(n for n, _, _ in mods),
               key=lambda x: _trace.time_s([e for e in mods if e[0] == x]))
    return [e for e in mods if e[0] == main]


def attribute(tr, text: str):
    """[(op_name, self seconds averaged over the devices)] of the main
    program's device ops in the window, or None where the text names less
    than ``COVERAGE`` of their time."""
    names = op_names(text)
    outer = main_module(tr)
    if not outer or not tr.ops:
        return None
    got, total, known = [], 0.0, 0.0
    for evs in tr.ops.values():
        for ev, t in _trace.self_times(_trace.inside(
                _trace.clip(evs, tr.t0, tr.t1), outer)):
            instr = _EVENT.match(ev).group(1)
            total += t
            if instr in names:
                known += t
                got.append((names[instr], t / len(tr.ops)))
    if total <= 0 or known < COVERAGE * total:
        return None
    return got


def share(ctx: dict, pattern: str):
    """Percent of the window's device busy time spent in train-step ops
    whose op_name matches ``pattern``; None without a trace, a text, or
    the coverage."""
    tr = ctx.get("trace")
    if tr is None or not tr.ops:
        return None
    text = step_text(ctx)
    got = attribute(tr, text) if text else None
    busy = _trace.busy_s(tr)
    if got is None or busy <= 0:
        return None
    rx = re.compile(pattern)
    return 100.0 * sum(t for name, t in got if rx.search(name)) / busy


def step_text(ctx: dict):
    """The compiled train step's HLO text for the cell (kept in ``ctx`` for
    the next reader), or None."""
    if "hlo" not in ctx:
        ctx["hlo"] = _compile_step(ctx["config"], ctx["mix"])
    return ctx["hlo"]


def _compile_step(cfg: dict, mix: dict):
    """The cell's train step compiled through the program's own
    ``Trainer.step_program``, built as ``drive_train`` builds it (its
    model and optimizer settings) on abstract batches of the mix's shape."""
    import shutil
    import tempfile

    import jax

    from repro.config import TrainConfig
    from repro.models.api import get_model
    from repro.train.trainer import Trainer

    if not hasattr(Trainer, "step_program"):
        return None
    from bench import gen
    from bench.drive_train import model_config

    opt = cfg["optimizer"]
    ckpt = tempfile.mkdtemp(prefix="bench_hlo_")
    try:
        tcfg = TrainConfig(
            steps=opt["steps"], learning_rate=opt["learning_rate"],
            warmup_frac=opt["warmup_frac"], weight_decay=opt["weight_decay"],
            beta1=opt["beta1"], beta2=opt["beta2"], eps=opt["eps"],
            grad_clip=opt["grad_clip"], checkpoint_every=1 << 30,
            checkpoint_dir=ckpt, log_every=1 << 30)
        trainer = Trainer(get_model(model_config(cfg["program"])), tcfg)
        # shapes without a sharding lower as drive_train's device arrays do
        batch = jax.eval_shape(
            lambda k: gen.darcy_batches(dict(mix, distinct_batches=1), k)[0],
            gen.key_from_seed(0))
        return trainer.step_program(batch).as_text()
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
