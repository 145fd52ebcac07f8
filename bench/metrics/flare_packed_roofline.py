"""flare_packed_roofline: the packed FLARE kernels' share of their roofline
in the training step. Device trace: the TPU custom calls inside the train
step's program are the packed kernel's launches (forward: four outputs
``y, Z, max, den``; backward: three, ``dq, dk, dv``). For each launch the
least time the chip could take is max(FLOPs / bf16 peak, bytes / HBM
bandwidth), with FLOPs and bytes of the real shapes (head size D, N points,
M latents; no lane or token padding, no score recomputation); the share is
the sum of those over the launches' summed device time. Compute bounds
both launches at these shapes. Moves ``train_step_s``.
"""
import re

from bench.metrics import _trace

_OUT = re.compile(r"=\s*\((.*?)\)\s*custom-call\(")


def call_cost(cfg: dict, mix: dict, kind: str):
    """(FLOPs, bytes) of one launch over the whole batch."""
    b, n = mix["batch"], mix["grid"] ** 2
    c, h, m = cfg["hidden_size"], cfg["num_heads"], cfg["num_latents"]
    d, g = c // h, b * h
    if kind == "fwd":   # read q, k, v; write y, Z, row max and sum
        return 6.0 * m * n * d * g, 4.0 * (3 * g * n * d + h * m * d + g * m * d + 2 * g * m)
    # read q, k, v, y, dy, Z, max, sum; write dq (per group), dk, dv
    return 12.0 * m * n * d * g, 4.0 * (6 * g * n * d + h * m * d + 2 * g * m * d + 2 * g * m)


def launches(tr):
    """[(kind, seconds)] of the kernel's launches in the window."""
    mods = _trace.matching(tr, r".", modules=True)
    if not mods:
        return []
    main = max(set(n for n, _, _ in mods),
               key=lambda x: _trace.time_s([e for e in mods if e[0] == x]))
    calls = _trace.inside(_trace.matching(tr, r'tpu_custom_call'),
                          [e for e in mods if e[0] == main])
    out = []
    for name, s, e in calls:
        mt = _OUT.search(name)
        arity = mt.group(1).count("[") if mt else 0
        kind = {4: "fwd", 3: "bwd"}.get(arity)
        if kind is None:
            return []
        out.append((kind, e - s))
    return out


def read(ctx):
    tr, pk = ctx.get("trace"), ctx["peaks"]
    calls = launches(tr) if tr is not None else []
    if not calls:
        return None
    least = 0.0
    for kind, _ in calls:
        f, by = call_cost(ctx["config"], ctx["mix"], kind)
        least += max(f / pk["bf16_flops"], by / pk["hbm_bytes_per_s"])
    return 100.0 * least / sum(t for _, t in calls)
