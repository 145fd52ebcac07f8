"""mfu.train_lm: the language-model training step's model FLOPs over the
window, as a share of the chip's bf16 peak. Host clock (the window's steps
and seconds) and FLOPs counted from the configuration's shapes: forward and
backward (twice forward), no rematerialization, no padding. Moves
``train_step_s``.

FLOPs per step (matrix FLOPs only, 2 per multiply-add; B sequences of S
tokens, T = B S): per token and layer, MLA's projections
(``W_q``, ``W_dkv``, ``W_kr``, ``W_uk``, ``W_uv``, ``W_o``) and its causal
attention at S^2/2 score and value products per sequence and head; the
dense layer's SwiGLU; per MoE layer the router (all its experts) and the
shared experts on every token, and the held experts' SwiGLU on the rows
routed to them, which the step counts (``moe.rows_held``, summed over the
layers); the head over the vocabulary slice.
"""


def step_flops(cfg: dict, mix: dict, rows_held: float) -> float:
    b, s = mix["batch"], mix["seq_len"]
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, nope, rope, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    f, n_dense = cfg["moe_intermediate_size"], cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    proj = 2 * (c * h * (nope + rope) + c * r + c * rope + r * h * nope + r * h * dv
                + h * dv * c)
    core = 2 * (s / 2) * h * (nope + rope + dv)
    dense_ffn = 6 * c * cfg["intermediate_size"]
    moe_dense = 2 * c * cfg["router_experts"] + 6 * c * cfg["n_shared_experts"] * f
    per_token = (cfg["num_hidden_layers"] * (proj + core) + n_dense * dense_ffn
                 + n_moe * moe_dense + 2 * c * cfg["vocab_size"])
    return 3.0 * (b * s * per_token + rows_held * 6 * c * f)


def read(ctx):
    rows = ctx.get("moe.rows_held")
    if not rows or not ctx.get("steps") or not ctx.get("window_s"):
        return None
    flops = sum(step_flops(ctx["config"], ctx["mix"], r) for r in rows)
    return 100.0 * flops / ctx["window_s"] / ctx["peaks"]["bf16_flops"]
