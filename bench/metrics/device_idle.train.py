"""device_idle.train: the share of the traced training window in which no
operation ran on the device (1 - union of op intervals / window). Device
trace. Moves ``train_step_s``."""
from bench.metrics import _trace


def read(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.ops:
        return None
    return 100.0 * _trace.idle_share(tr)
