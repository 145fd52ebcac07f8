"""Causal / streaming FLARE — the paper's future-work item (4), built out.

Observation: the encode softmax is a per-latent weighted *running* sum:

    z_m = (sum_n e^{q_m.k_n} v_n) / (sum_n e^{q_m.k_n})

so a latent state (m_max, num, den) per head —

    m_max: [H, M]        running max of scores (flash-style stabilizer)
    num:   [H, M, D]     sum of e^{s - m_max} * v
    den:   [H, M]        sum of e^{s - m_max}

— can be updated in O(M*D) per appended token, and the decode of token t
against the state built from tokens <= t is exactly the FLARE decode
restricted to the causal prefix. This turns FLARE into a constant-memory
recurrent LM mixer (state M x D per head), directly analogous to a linear
attention state but with FLARE's softmax routing on both sides.

Entry points:
  - ``stream_init``   : fresh state
  - ``stream_append`` : single-token decode step (serving)
  - ``stream_chunk``  : chunked causal prefill/training (scan over chunks;
                        within a chunk, cumulative sums realize causality)
  - ``stream_insert_slots`` / ``stream_reset_slots``: FlareState-typed
    slot-lane pool ops (a batch row IS a request slot — DESIGN.md §4).
    These are the standalone form for driving a bare state pool; the
    serving engine itself reaches FlareState lanes through the generic
    ``serve.cache`` axis-discovery scatter, which must stay semantically
    identical (reset restores the ``stream_init`` values — m_max back to
    -inf, not zero; both paths are pinned by tests/test_serve_continuous).

Padding mask (serving prefill buckets, DESIGN.md §4): the chunk forms accept
``mask`` [B, T] (True = real token). Masked positions contribute *identity*
to the encode statistics — their scores are -inf on the state side, so the
carried state is exactly the state of the unpadded prefix — while their own
outputs are finite garbage (decode weights use the raw scores) that callers
discard. With right-padding, causality already keeps real positions exact.

Self-inclusion convention: token t's output uses the state INCLUDING token t
(matches standard causal attention where a token attends to itself).

Equivalence to the batch operator with a causal prefix is tested in
tests/test_flare_stream.py.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class FlareState(NamedTuple):
    m_max: jax.Array  # [B, H, M]   fp32
    num: jax.Array    # [B, H, M, D] fp32
    den: jax.Array    # [B, H, M]   fp32


def stream_init(batch: int, num_heads: int, num_latents: int, head_dim: int) -> FlareState:
    return FlareState(
        m_max=jnp.full((batch, num_heads, num_latents), -jnp.inf, jnp.float32),
        num=jnp.zeros((batch, num_heads, num_latents, head_dim), jnp.float32),
        den=jnp.zeros((batch, num_heads, num_latents), jnp.float32),
    )


def stream_append(
    state: FlareState,
    q: jax.Array,  # [H, M, D] latent queries
    k_t: jax.Array,  # [B, H, D] key of the new token
    v_t: jax.Array,  # [B, H, D] value of the new token
) -> tuple[FlareState, jax.Array]:
    """One decode step: append token t, return its mixed output [B, H, D]."""
    qf = q.astype(jnp.float32)
    s = jnp.einsum("hmd,bhd->bhm", qf, k_t.astype(jnp.float32))  # [B, H, M]
    new_max = jnp.maximum(state.m_max, s)
    scale_old = jnp.exp(state.m_max - new_max)
    scale_new = jnp.exp(s - new_max)
    # v_t broadcast over M: [B,H,M,1] * [B,H,1,D]
    num = state.num * scale_old[..., None] + scale_new[..., None] * v_t.astype(jnp.float32)[:, :, None, :]
    den = state.den * scale_old + scale_new
    new_state = FlareState(new_max, num, den)
    z = num / jnp.maximum(den, 1e-30)[..., None]  # [B, H, M, D]
    # Decode: softmax over latents of the SAME scores s (k_t . q_m).
    w = jax.nn.softmax(s, axis=-1)  # [B, H, M]
    y = jnp.einsum("bhm,bhmd->bhd", w, z)
    return new_state, y.astype(v_t.dtype)


def _safe_exp(a, m):
    """exp(a - m) with the -inf/-inf identity case pinned to 0 (all-masked
    prefixes would otherwise produce exp(nan))."""
    return jnp.where(a == -jnp.inf, 0.0, jnp.exp(a - m))


def _combine(a, b):
    """Associative combine of (max, numerator, denominator) softmax states."""
    am, an, ad = a
    bm, bn, bd = b
    m = jnp.maximum(am, bm)
    ea = _safe_exp(am, m)
    eb = _safe_exp(bm, m)
    return m, an * ea[..., None] + bn * eb[..., None], ad * ea + bd * eb


def stream_chunk(
    state: FlareState,
    q: jax.Array,  # [H, M, D]
    k: jax.Array,  # [B, H, T, D] chunk keys
    v: jax.Array,  # [B, H, T, D] chunk values
    mask: jax.Array | None = None,  # [B, T] bool, True = real token
) -> tuple[FlareState, jax.Array]:
    """Causal prefill over a chunk of T tokens. Returns ([B,H,T,D] outputs).

    Exactness note: per-position stabilizers via an associative scan of
    (max, num, den) — a single chunk-wide stabilizer would let a huge FUTURE
    score underflow earlier positions' denominators (a finite-precision
    causality leak; tests/test_flare_stream.py::test_prefix_causality).

    ``mask``: masked positions contribute nothing to the statistics (their
    encode scores are -inf, hence identity elements of the combine); their
    own outputs are finite garbage the caller discards.
    """
    b, h, t, d = k.shape
    m_lat = q.shape[1]
    qf = q.astype(jnp.float32)
    s = jnp.einsum("hmd,bhtd->bhmt", qf, k.astype(jnp.float32))  # [B, H, M, T]
    # masked (padding) values need no zeroing: their -inf scores give them
    # exactly zero combine weight (_safe_exp), so v_b stays a broadcast view
    s_enc = s if mask is None else jnp.where(mask[:, None, None, :], s, -jnp.inf)
    v_b = jnp.broadcast_to(
        v.astype(jnp.float32)[:, :, None, :, :], (b, h, m_lat, t, d))
    ones = jnp.ones_like(s)
    mc, numc, denc = jax.lax.associative_scan(_combine, (s_enc, v_b, ones), axis=3)
    # merge the incoming carry state into every position
    m_t = jnp.maximum(state.m_max[..., None], mc)
    e_carry = _safe_exp(state.m_max[..., None], m_t)  # [B, H, M, T]
    e_cum = _safe_exp(mc, m_t)
    num_t = state.num[..., None, :] * e_carry[..., None] + numc * e_cum[..., None]
    den_t = state.den[..., None] * e_carry + denc * e_cum
    z_t = num_t / jnp.maximum(den_t, 1e-30)[..., None]  # [B, H, M, T, D]
    # Decode each token against its own causal latent state.
    w = jax.nn.softmax(s, axis=-2)  # softmax over M for each token t: [B, H, M, T]
    y = jnp.einsum("bhmt,bhmtd->bhtd", w, z_t)
    new_state = FlareState(
        m_max=m_t[..., -1],
        num=num_t[..., -1, :],
        den=den_t[..., -1],
    )
    return new_state, y.astype(v.dtype)


def stream_chunk_factored(
    state: FlareState,
    q: jax.Array,  # [H, M, D]
    k: jax.Array,  # [B, H, T, D]
    v: jax.Array,  # [B, H, T, D]
    mask: jax.Array | None = None,  # [B, T] bool, True = real token
) -> tuple[FlareState, jax.Array]:
    """Causal chunk prefill via the factored [T, T] token-mixing matrix.

    Derivation: y_t = sum_m w_tm * num_tm / den_tm expands to

        y_t = sum_m F2[t,m] * (carry_num_m e^{cm - REF})
            + sum_{tau<=t} A[t,tau] v_tau,
        A = F2 @ F1^T,   F1[tau,m] = e^{s_tau,m - REF_m}  (<= 1, safe)
        F2[t,m] = w_tm / cden_tm,
        cden_tm = carry_den e^{cm - REF} + cumsum_tau(F1)_t

    with REF_m = max(carry_max, max_tau s) the per-latent chunk stabilizer.
    Memory is O(T*M + T^2) instead of the exact path's O(T*M*D) per-position
    state stack — the flare_lm training path (EXPERIMENTS.md §Perf cell D).

    Bounded-score contract: exact unless a FUTURE in-chunk score exceeds the
    running max by >~85 nats (then cden underflows to the 1e-30 guard). LM
    logits live within tens of nats; `stream_chunk` remains the
    arbitrary-input exact path (used for serving prefill and adversarial
    tests).
    """
    b, h, t, d = k.shape
    qf = q.astype(jnp.float32)
    s = jnp.einsum("hmd,bhtd->bhmt", qf, k.astype(jnp.float32))  # [B, H, M, T]
    # state-side scores: masked (padding) positions are -inf so they are
    # invisible to the carried statistics; the decode softmax below keeps the
    # raw scores (masked positions' outputs are finite garbage, discarded).
    s_enc = s if mask is None else jnp.where(mask[:, None, None, :], s, -jnp.inf)
    ref = jnp.maximum(state.m_max, jnp.max(s_enc, axis=-1))  # [B, H, M]
    f1 = _safe_exp(s_enc, ref[..., None])  # <= 1
    carry_scale = _safe_exp(state.m_max, ref)  # [B, H, M]
    cden = state.den[..., None] * carry_scale[..., None] + jnp.cumsum(f1, axis=-1)
    w = jax.nn.softmax(s, axis=-2)  # decode weights over latents, per token
    f2 = w / jnp.maximum(cden, 1e-30)  # [B, H, M, T]
    # carry contribution: sum_m F2[t,m] * carry_num_m * e^{cm - REF}
    carry_num = state.num * carry_scale[..., None]  # [B, H, M, D]
    y_carry = jnp.einsum("bhmt,bhmd->bhtd", f2, carry_num)
    # intra-chunk: A[t, tau] = sum_m F2[t,m] F1[tau,m], tau <= t
    a = jnp.einsum("bhmt,bhmu->bhtu", f2, f1)
    a = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], a, 0.0)
    y = y_carry + jnp.einsum("bhtu,bhud->bhtd", a, v.astype(jnp.float32))
    # state update (exact — no clamps involved)
    new_num = carry_num + jnp.einsum("bhmt,bhtd->bhmd", f1, v.astype(jnp.float32))
    new_den = cden[..., -1]
    return FlareState(ref, new_num, new_den), y.astype(v.dtype)


def flare_causal_with_state(
    q: jax.Array,  # [H, M, D]
    k: jax.Array,  # [B, H, N, D]
    v: jax.Array,  # [B, H, N, D]
    *,
    chunk_size: int = 256,
    mode: str = "factored",
    mask: jax.Array | None = None,  # [B, N] bool, True = real token
) -> tuple[FlareState, jax.Array]:
    """Causal FLARE over a sequence via a scan of chunked prefills,
    returning the final latent state (serving prefill) and all outputs.

    O(N * M * D) compute. mode="factored" (default) uses the [T,T] matrix
    form (O(T^2 + T*M) memory, bounded-score contract above); mode="exact"
    uses the associative-scan per-position states (O(T*M*D) memory, exact
    for arbitrary inputs). ``mode`` is a numerical-strategy knob *within*
    this backend — backend selection itself is a MixerPolicy concern
    (repro.core.policy).

    ``mask`` marks real tokens (serving prefill buckets right-pad prompts):
    the returned state is exactly the state of the masked prefix; outputs at
    masked positions are garbage the caller discards.
    """
    b, h, n, d = k.shape
    m = q.shape[1]
    chunk_size = min(chunk_size, n)
    while n % chunk_size:
        chunk_size //= 2
    state = stream_init(b, h, m, d)
    nc = n // chunk_size
    kc = k.reshape(b, h, nc, chunk_size, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, h, nc, chunk_size, d).transpose(2, 0, 1, 3, 4)
    step = stream_chunk_factored if mode == "factored" else stream_chunk

    if mask is None:
        def body(carry, inputs):
            kt, vt = inputs
            carry, y = step(carry, q, kt, vt)
            return carry, y

        state, ys = jax.lax.scan(body, state, (kc, vc))  # ys: [C, B, H, T, D]
    else:
        mchunks = mask.reshape(b, nc, chunk_size).transpose(1, 0, 2)

        def body(carry, inputs):
            kt, vt, mt = inputs
            carry, y = step(carry, q, kt, vt, mask=mt)
            return carry, y

        state, ys = jax.lax.scan(body, state, (kc, vc, mchunks))
    return state, ys.transpose(1, 2, 0, 3, 4).reshape(b, h, n, d)


def flare_causal(q, k, v, *, chunk_size: int = 256, mode: str = "factored"):
    """Training-time causal FLARE mixer (the flare_lm architecture and the
    long_500k-capable path). See flare_causal_with_state."""
    return flare_causal_with_state(q, k, v, chunk_size=chunk_size, mode=mode)[1]


def stream_insert_slots(pool: FlareState, part: FlareState,
                        slots: jax.Array) -> FlareState:
    """Write ``part``'s batch lanes into ``pool`` at ``slots`` ([b] int32).

    Admission for a bare FlareState pool (DESIGN.md §4): a prefilled
    per-request state (batch lane i of ``part``) lands in pool slot
    ``slots[i]``; all other slots are untouched. jit-safe (scatter). The
    serving engine's generic path (serve.cache.insert_slots) performs the
    same scatter via axis discovery.
    """
    return FlareState(
        m_max=pool.m_max.at[slots].set(part.m_max),
        num=pool.num.at[slots].set(part.num),
        den=pool.den.at[slots].set(part.den),
    )


def stream_reset_slots(pool: FlareState, slots: jax.Array) -> FlareState:
    """Restore ``slots`` of a state pool to the ``stream_init`` values.

    The retirement op: m_max must return to -inf (not zero — zero is a
    *valid* score) so a reused slot carries no trace of the previous
    request's stream.
    """
    b = slots.shape[0]
    return stream_insert_slots(
        pool, stream_init(b, *pool.num.shape[1:]), slots)


def flare_causal_ref(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """O(N^2) oracle for the causal operator: token t applies the batch FLARE
    operator restricted to the prefix [0..t]. Tests only."""
    b, h, n, d = k.shape
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    s = jnp.einsum("hmd,bhnd->bhmn", qf, kf)  # [B,H,M,N]
    causal = jnp.tril(jnp.ones((n, n), bool))  # [t, n] prefix masks

    def one_token(t_mask, s_t):
        # t_mask: [N] bool prefix; s_t: scores column for token t [B,H,M]
        masked = jnp.where(t_mask[None, None, None, :], s, -jnp.inf)
        w_enc = jax.nn.softmax(masked, axis=-1)  # [B,H,M,N]
        z = jnp.einsum("bhmn,bhnd->bhmd", w_enc, vf)
        w_dec = jax.nn.softmax(s_t, axis=-1)  # [B,H,M]
        return jnp.einsum("bhm,bhmd->bhd", w_dec, z)

    ys = jax.vmap(one_token, in_axes=(0, 2), out_axes=2)(causal, s.transpose(0, 1, 3, 2))
    return ys.astype(v.dtype)
