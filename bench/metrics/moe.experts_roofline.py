"""moe.experts_roofline: the held experts' grouped matmuls' share of their
roofline in the training step. Device trace: the train step's ops under the
MoE layer's ``moe.experts`` scope (the gate, up and down grouped matmuls
and the SwiGLU between them, in the forward, recomputed and backward
passes), named through the compiled step's HLO (``_hlo``). The least time
the chip could take is max(FLOPs / bf16 peak, bytes / HBM bandwidth), with
FLOPs and bytes of the rows routed to held experts (``moe.rows_held``, as
the step counts them; no padding) and the published widths; the share is
that over the ops' device time. Moves ``train_step_s``.
"""
import re

from bench.metrics import _hlo

SCOPE = re.compile(r"(^|[/(])moe\.experts([/)]|$)")
# the forward, its recomputation under remat, and the backward (input and
# weight gradients: twice the forward)
PASSES = 4


def expert_cost(cfg: dict, rows: float) -> tuple[float, float]:
    """(FLOPs, bytes) of the held experts' three grouped matmuls in one
    step over ``rows`` routed rows (summed over the layers): each pass
    reads the rows and each layer's held weights in bf16 and writes its
    outputs (gate and up [rows, F], down [rows, C])."""
    c, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    flops = 6.0 * rows * c * f
    weights = layers * 3 * cfg["n_routed_experts"] * c * f
    moved = rows * (2 * c + 2 * f) + rows * (f + c) + weights
    return PASSES * flops, PASSES * 2.0 * moved


def read(ctx):
    tr, rows = ctx.get("trace"), ctx.get("moe.rows_held")
    if tr is None or not tr.ops or not rows:
        return None
    text = _hlo.step_text(ctx)
    got = _hlo.attribute(tr, text) if text else None
    if got is None:
        return None
    spent = sum(t for name, t in got if SCOPE.search(name))
    if spent <= 0:
        return None
    pk = ctx["peaks"]
    least = 0.0
    for r in rows:
        f, by = expert_cost(ctx["config"], r)
        least += max(f / pk["bf16_flops"], by / pk["hbm_bytes_per_s"])
    return 100.0 * least / spent
