"""moe.routing_share: the share of the traced window's device busy time
spent routing token-slots to experts and back: device self time of the
train step's ops under the MoE layer's ``moe.route`` (router, softmax,
top-k, balance loss), ``moe.dispatch`` (sort and gather) and
``moe.combine`` (inverse permutation and gate-weighted sum) scopes, in the
forward, recomputed and backward passes, over device busy time. Device
trace, ops named through the compiled step's HLO (``_hlo``). Moves
``train_step_s``."""
from bench.metrics import _hlo


def read(ctx):
    return _hlo.share(ctx, r"(^|[/(])moe\.(route|dispatch|combine)([/)]|$)")
