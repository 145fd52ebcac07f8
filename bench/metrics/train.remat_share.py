"""train.remat_share: the share of the traced window's device busy time
spent on what ``jax.checkpoint`` computes a second time in the train step:
device self time of the step's ops whose ``op_name`` holds JAX's
``rematted_computation`` (the recomputed kernel forwards, ResMLPs and
layout work of ``remat: "full"``), over device busy time. Device trace,
ops named through the compiled step's HLO (``_hlo``). Moves
``train_step_s``."""
from bench.metrics import _hlo


def read(ctx):
    return _hlo.share(ctx, r"(^|/)rematted_computation(/|$)")
