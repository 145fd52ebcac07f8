"""mfu.train: the training step's model FLOPs over the window, as a share
of the chip's bf16 peak. Host clock (the window's steps and seconds) and
FLOPs counted from the configuration's shapes: forward and backward, no
rematerialization, no padding. Moves ``train_step_s``.

FLOPs of the FLARE PDE surrogate per sample (N points, C = H*D features,
M latents per head): per block, the mixer's three contractions
``S = Q K^T``, ``softmax(S) V`` and ``softmax(S)^T Z`` (6*M*N*D per head),
plus the dense layers of the K and V ResMLPs, the output projection and the
block's ResMLP (2*din*dout per point each); the input and output ResMLPs
once. Backward is twice forward. Matrix FLOPs only: no softmax exps,
norms or activations. The f32 contractions run as bf16 passes at default
precision, so the bf16 peak is the bound.
"""


def _resmlp(din, dh, dout, layers):
    return 2 * (din * dh + layers * dh * dh + dh * dout)


def step_flops(cfg: dict, mix: dict) -> float:
    b, n = mix["batch"], mix["grid"] ** 2
    c, h, m = cfg["hidden_size"], cfg["num_heads"], cfg["num_latents"]
    d = c // h
    per_point = (2 * _resmlp(c, c, c, cfg["kv_proj_layers"]) + 2 * c * c
                 + _resmlp(c, c, c, cfg["mlp_layers"]))
    block = 6 * m * n * d * h + n * per_point
    io = n * (_resmlp(cfg["in_dim"], c, c, cfg["io_layers"])
              + _resmlp(c, c, cfg["out_dim"], cfg["io_layers"]))
    return 3.0 * b * (cfg["num_blocks"] * block + io)


def read(ctx):
    if not ctx.get("steps") or not ctx.get("window_s"):
        return None
    flops = step_flops(ctx["config"], ctx["mix"]) * ctx["steps"]
    return 100.0 * flops / ctx["window_s"] / ctx["peaks"]["bf16_flops"]
