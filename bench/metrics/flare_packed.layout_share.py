"""flare_packed.layout_share: the share of the traced window's device busy
time spent on the packed FLARE wrapper's layout work (head packing, lane
and token padding, unpacking and slicing back: the program's
``flare_packed.layout`` scope) in the forward, recomputed and backward
passes: device self time of the train step's ops whose ``op_name`` lies
under that scope, over device busy time. Layout copies that XLA makes on
its own carry no such scope and are not counted. Device trace, ops named
through the compiled step's HLO (``_hlo``). Moves ``train_step_s``."""
from bench.metrics import _hlo


def read(ctx):
    return _hlo.share(ctx, r"(^|[/(])flare_packed\.layout([/)]|$)")
