"""Readings that set the limits of ``correct`` for ``train_lm`` cells, as
``bench/control.py`` reads them for ``train`` cells: per seed, the
program's first steps (the set-up of a run, ``drive_train_lm``) against the
float32 reference's; on the seeds of ``--fault-seeds`` also the control's
(the program's own bfloat16 weights: ``param_dtype`` bfloat16, held and
updated in bfloat16) and the reference fed half of each batch (the fault
"half of the batch left out"). Each reading says whether the cell's limits
would hold (``correct``).

    python bench/control_lm.py --workload <cell> --seeds 1,2 [--fault-seeds 1]

One JSON line per seed. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def _program_steps(c: dict, seed: int):
    """The program's first steps as a run's set-up drives them; its state
    is freed before the reference runs."""
    from bench import drive_train, drive_train_lm

    hook = drive_train._StepHook()
    st = drive_train_lm.first_steps(c, seed, hook)
    shutil.rmtree(st["ckpt"], ignore_errors=True)
    # the Trainer outlives this scope (its signal handlers hold it)
    hook.trainer = st["trainer"].params = st["trainer"].opt_state = None
    batches = st["batches"][: c["mix"]["check_steps"]]
    prog, k_w = st["prog"], st["k_w"]
    del st
    gc.collect()
    return prog, k_w, batches


def readings(c: dict, seed: int, faults: bool) -> dict:
    import jax

    from bench import drive_train

    cfg, ref = c["config"], c["ref"]
    runs = {}
    runs["program"], k_w, batches = _program_steps(c, seed)
    if faults:
        low = copy.deepcopy(cfg)
        low["program"]["param_dtype"] = "bfloat16"
        runs["control"] = _program_steps(dict(c, config=low), seed)[0]
        half = [{k: v[: v.shape[0] // 2] for k, v in b.items()} for b in batches]
        runs["half_batch"] = ref.train_reference(k_w, cfg, half)
    base = ref.train_reference(k_w, cfg, batches)
    out = {"seed": seed, "ref_losses": base["losses"]}
    for name, r in runs.items():
        numbers, where = drive_train.compare(r, base)
        out[name] = dict(numbers, correct=drive_train.verdict(numbers, cfg["limits"])[1],
                         losses=r["losses"], **where)
    jax.clear_caches()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault-seeds", default=None,
                    help="seeds that also read the control and the fault (default: all)")
    args = ap.parse_args(argv)
    c = harness.cell(args.workload)
    harness.setup_env()
    try:
        harness.device(c["cell"]["chips"])
    except harness.NoDevice as e:
        print(e, file=sys.stderr)
        return 2
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = set(seeds if args.fault_seeds is None
                 else (int(s) for s in args.fault_seeds.split(",")))
    for s in seeds:
        print(json.dumps(readings(c, s, s in faults)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
