"""Single-launch packed-head Pallas FLARE mixer with a custom VJP.

Three TPU-shaped optimizations over a plain pair of encode and decode
kernels (DESIGN.md §12):

  * **Packed heads.** The paper's strong configs use many heads with tiny
    head dims (D in {4, 8}). ``pack`` heads form one group: the group's
    latent queries are expanded in-VMEM to a block-diagonal [pack*Mp,
    pack*D] matrix Q_bd, so ONE matmul produces every packed head's score
    block (rows p*Mp..(p+1)*Mp of ``Q_bd @ K^T`` are head p's [Mp, block_n]
    scores — off-head channels are zeroed by the block-diagonal mask,
    keeping per-head dot products disjoint).

  * **Single-launch encode->decode.** Grid (B*Gh, 2, N_blocks): phase 0
    runs the flash-style encode sweep, phase 1 the decode sweep. The latent
    summary Z (only [pack*Mp, pack*D]) never round-trips through HBM — it
    stays in VMEM scratch between the phases — and there is one kernel
    launch instead of two.

  * **Custom VJP.** The backward pass is two more fused sweeps in one
    launch: sweep 1 recomputes the decode weights from K and accumulates
    dZ; sweep 2 recomputes the encode weights from the saved row statistics
    (flash recomputation: softmax max + denominator per latent row) and
    emits dq/dk/dv. Residuals are O(M*D + N*D) — no [M, N] matrix is ever
    stored — so ``jax.grad`` through ``flare_mixer_packed`` runs entirely
    on the Pallas path.

Orientation note: the token operands (K, V, y and, backward, dy, dk, dv)
are channel-major, [B, C, N] with tokens on lanes: the layout the model's
[B, N, C] activations already have in HBM, so XLA folds the wrapper's
transposes into their producers and consumers, and no operand carries
lane padding. A grid step reads its head group's [pack*D, block_n] rows;
where pack*D rows are no legal sublane block of the operand's dtype (8
rows of bfloat16, 4 of float32), it reads the batch element's whole
[C, block_n] slab and takes the group's rows by an exact 0/1 selection
matmul. Every score tile is latent-major, [S, block_n] with S = pack*Mp:
encode's online softmax reduces along lanes, as a flash-attention
kernel's does, and decode's softmax over latents is a *sublane* segmented
softmax (per row-block max/sum). The matmuls keep the [S, block_n] tiles
as a plain (untransposed) operand: ``s = Q_bd K^T``, ``y^T = Z_bd^T w``,
``dK^T = Q_bd^T dS``, ``dV^T = dZ^T A``, and the accumulations over tokens
``num = p V^T``, ``dZ^T = dy w^T``, ``dQ^T = K dS^T``; the decode
softmax's ``dy . y`` per head is a sublane sum over the head's D channel
rows. The choice of each was made by LLO bundle count per grid step.

Padding (head count to a pack multiple, M to the sublane tile) happens in
plain-JAX wrapper code, so JAX autodiff composes the reshapes with the
kernel's custom VJP. N is not padded: a tile narrower than N is a
multiple of 128 tokens, and the last block may be partial; what the
pipeline reads past N there is zeroed before use.

Steady-state sweep steps repeat no per-group setup. The block-diagonal
latent queries (and, in the backward, Z and the transposes the matmuls
above take) are built in VMEM scratch on each group's first grid step and
only read after that; the group axis is the grid's
outermost, so every group, on whatever core runs it, starts there. The
token-padding mask is built only on the last N block, the one block that
can hold padding: both forms of a sweep step come from one body with a
static ``masked`` flag (:func:`_sweep`), and the gauge
``flare_packed.masked_block_share`` records the share of blocks that take
the masked form; ``flare_packed.operand_pad_factor`` records the bytes of
K, V and y the blocks span over their data's. Neither changes a value the
kernels compute. The token-major helpers (``_bd_mask``,
``_expand_block_diag``, ``_scores``, ``_token_ok``, ``_decode_weights``,
``_pack_heads``, ``_unpack_heads``) keep their behaviour and defaults for
``flare_packed_shard``, which still reads lane-padded [G, Np, 128]
operands and builds its invariants on every step.

Per-latent-row softmax statistics (the encode sweep's running max and
denominator; the backward's saved max, denominator and ``delta_enc``) live
in [S, 128] f32 VMEM scratch with each row's value replicated across the
lanes, as flash-attention kernels keep m and l. A reduction over the token
axis keeps that axis, and a tile reads a statistic as whole 128-lane copies
(:func:`_lanes`), so a sweep step never holds a rank-1 [S] value: Mosaic
lays those along lanes, and each step would permute them to and from the
tile's sublanes. The statistics take their compact [G, 1, S] residual form
only once per group, by a transpose, when the encode sweep ends; the
backward widens them back, the same way, in its group's first step.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.obs import scope
from repro.obs.metrics import REGISTRY

NEG_INF = -1e30
LANE = 128
# Scoped-VMEM cap for these launches. The backward sweep keeps ~10 live
# [pack*Mp, block_n] f32 temporaries; at M=2048 and block_n=256 that exceeds
# Mosaic's 16 MiB default, while v5e has 128 MiB of VMEM per core.
VMEM_LIMIT = 64 * 1024 * 1024


def _vmem(shape, dtype):
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.VMEM(shape, dtype)


def _compiler_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def heuristic_pack(heads: int, latents: int, head_dim: int,
                   *, max_rows: int = 2048) -> int:
    """Default head-pack factor: fill the 128-lane dim, but never pack more
    heads than exist and keep the packed latent-row count (pack * padded M)
    within a VMEM-friendly budget."""
    pack = max(1, min(LANE // max(1, head_dim), heads))
    mp = _round_up(max(1, latents), 16)
    while pack > 1 and pack * mp > max_rows:
        pack = (pack + 1) // 2
    return pack


class _PackedCfg(NamedTuple):
    """Static launch config (hashable — custom_vjp nondiff argument)."""

    pack: int
    mp: int          # padded latent count per head
    d: int           # true head dim (for the channel->head mask)
    block_n: int
    n_valid: Optional[int]   # real token count when the last N block is
                             # partial (or N carries tile padding)
    m_valid: Optional[int]   # real latent count when M carries pad rows
    interpret: bool


# ---------------------------------------------------------------------------
# In-kernel helpers (shared by forward and backward so recomputation is
# bitwise-identical to the forward pass)
# ---------------------------------------------------------------------------


def _bd_mask(cfg: _PackedCfg, wl: int) -> jax.Array:
    """[S, Wl] block-diagonal mask: row s (head s // Mp) owns channel c iff
    c // D == s // Mp. Lane padding (c >= pack*D) matches no head."""
    s = cfg.pack * cfg.mp
    rh = jax.lax.broadcasted_iota(jnp.int32, (s, wl), 0) // cfg.mp
    ch = jax.lax.broadcasted_iota(jnp.int32, (s, wl), 1) // cfg.d
    return (rh == ch) & (ch < cfg.pack)


def _expand_block_diag(cfg: _PackedCfg, x: jax.Array, bd: jax.Array) -> jax.Array:
    """[Mp, Wl] packed-compact -> [S, Wl] block-diagonal (head p's columns
    appear in row block p, zeros elsewhere)."""
    tiled = x if cfg.pack == 1 else jnp.concatenate([x] * cfg.pack, axis=0)
    return jnp.where(bd, tiled, 0.0)


def _compact_block_diag(cfg: _PackedCfg, x_bd: jax.Array) -> jax.Array:
    """Inverse of :func:`_expand_block_diag` for an already-masked [S, Wl]
    array: row blocks occupy disjoint lane sets, so summing them is exact."""
    out = x_bd[0:cfg.mp, :]
    for p in range(1, cfg.pack):
        out = out + x_bd[p * cfg.mp:(p + 1) * cfg.mp, :]
    return out


def _scores(cfg: _PackedCfg, qbd: jax.Array, k: jax.Array, n_idx,
            *, token_mask: bool = True) -> jax.Array:
    """[S, bn] latent-major scores with token- and latent-padding masked to
    NEG_INF (exactly the mask the forward statistics were built under).
    ``token_mask=False`` leaves the token mask out, for a block that holds
    no token padding."""
    s = jax.lax.dot_general(qbd, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return _mask_scores(cfg, s, n_idx, token_mask=token_mask)


def _mask_scores(cfg: _PackedCfg, s: jax.Array, n_idx,
                 *, token_mask: bool = True) -> jax.Array:
    """Token- and latent-padding of an [S, bn] score tile to NEG_INF."""
    ok = None
    if token_mask and cfg.n_valid is not None:
        cols = n_idx * cfg.block_n + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = cols < cfg.n_valid
    if cfg.m_valid is not None:
        lat = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) % cfg.mp
        lat_ok = lat < cfg.m_valid
        ok = lat_ok if ok is None else (ok & lat_ok)
    if ok is not None:
        s = jnp.where(ok, s, NEG_INF)
    return s


def _token_ok(cfg: _PackedCfg, shape, n_idx) -> Optional[jax.Array]:
    if cfg.n_valid is None:
        return None
    cols = n_idx * cfg.block_n + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return cols < cfg.n_valid


def _decode_weights(cfg: _PackedCfg, s: jax.Array) -> jax.Array:
    """Segmented decode softmax: per token (lane) and per head (sublane row
    block of Mp rows), normalized over that head's latents. Latent-pad rows
    arrive as NEG_INF in ``s`` and get exactly zero weight. Fully-masked
    token columns (N padding) come out uniform-finite, never NaN."""
    parts = []
    for p in range(cfg.pack):
        seg = s[p * cfg.mp:(p + 1) * cfg.mp, :]          # [Mp, bn]
        mseg = jnp.max(seg, axis=0)                      # [bn]
        eseg = jnp.exp(seg - mseg[None, :])
        parts.append(eseg / jnp.sum(eseg, axis=0)[None, :])
    return parts[0] if cfg.pack == 1 else jnp.concatenate(parts, axis=0)


def _lanes(x: jax.Array, width: int) -> jax.Array:
    """[S, 128] lane-replicated row statistic -> [S, width], every lane
    holding its row's value: whole copies of the 128 lanes, sliced when
    ``width`` is not a multiple of 128 (a whole-N tile of 200 tokens, or
    the pack*D = 8 channels of a numerator)."""
    reps = -(-width // LANE)
    x = x if reps == 1 else jnp.tile(x, (1, reps))
    return x if width == reps * LANE else x[:, :width]


def _sweep(cfg: _PackedCfg, n_idx, n_blocks: int, body) -> None:
    """Run ``body(masked)`` for this grid step of an N sweep. Only the last
    block can reach past the real tokens, so ``masked`` is True there (and
    only when it does); every other block runs the body without the mask.
    A masked body is always the last block's, whose index is static."""
    if cfg.n_valid is None:
        body(False)
        return
    last = n_idx == n_blocks - 1
    pl.when(last)(lambda: body(True))
    pl.when(jnp.logical_not(last))(lambda: body(False))


def _note_masked_share(cfg: _PackedCfg, n_blocks: int) -> None:
    """Gauge the share of each sweep's blocks that build the token mask."""
    REGISTRY.gauge(
        "flare_packed.masked_block_share",
        "share of a packed sweep's N blocks that build the token-padding mask",
    ).set(1.0 / n_blocks if cfg.n_valid is not None else 0.0)


# ---------------------------------------------------------------------------
# Channel-major operands: a group's [pack*D, block_n] rows of [B, C, N]
# ---------------------------------------------------------------------------


def _sublane_tile(dtype) -> int:
    """Rows of one sublane tile of ``dtype`` (8 for 32-bit, 16 for 16-bit)."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _whole_slab(w: int, c: int, *dtypes) -> bool:
    """True where a group's ``w`` = pack*D channel rows are no legal block of
    a [B, c, N] operand (fewer rows than a sublane tile of some dtype, and
    not the whole channel axis): each grid step then reads the batch
    element's whole [c, block_n] slab and selects the group's rows."""
    return w != c and any(w % _sublane_tile(dt) for dt in dtypes)


def _cm_scores(cfg: _PackedCfg, qbd: jax.Array, kt: jax.Array, n_idx,
               *, token_mask: bool) -> jax.Array:
    """[S, bn] latent-major scores ``Q_bd @ K^T`` from channel-major K
    [pack*D, bn], masked exactly as :func:`_scores` masks them."""
    s = jnp.dot(qbd, kt, preferred_element_type=jnp.float32)
    return _mask_scores(cfg, s, n_idx, token_mask=token_mask)


def _group_rows(x: jax.Array, sel_scr) -> jax.Array:
    """A grid step's [rows, bn] block of a channel-major operand as the
    group's [pack*D, bn] rows: the block itself, or (whole-slab blocks) the
    rows a 0/1 selection matrix picks, which is exact."""
    if sel_scr is None:
        return x
    # a float32 operand takes the exact multi-pass product: one bf16 pass
    # would round it
    exact = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    return jnp.dot(sel_scr[...].astype(x.dtype), x, precision=exact,
                   preferred_element_type=jnp.float32).astype(x.dtype)


def _select_rows(sel_scr, group) -> None:
    """Fill the [pack*D, C] 0/1 matrix that picks head group ``group``'s
    channel rows out of a whole [C, bn] slab."""
    w, c = sel_scr.shape
    first = group * w
    rows = jax.lax.broadcasted_iota(jnp.int32, (w, c), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (w, c), 1)
    sel_scr[...] = (cols == first + rows).astype(sel_scr.dtype)


def _zero_padding(cfg: _PackedCfg, x: jax.Array, n_idx) -> jax.Array:
    """Zero the token columns of a partial last block past N: what a
    pipeline reads there is undefined, and a zero weight does not cancel a
    NaN in a matmul."""
    return jnp.where(_token_ok(cfg, x.shape, n_idx), x, 0.0).astype(x.dtype)


def _head_sums(cfg: _PackedCfg, x: jax.Array) -> jax.Array:
    """[pack*D, bn] -> [S, bn]: head p's sum over its D channel rows,
    broadcast over its Mp latent rows (a [1, bn] row that broadcasts for
    free at pack 1)."""
    d, mp = cfg.d, cfg.mp
    sums = [jnp.sum(x[p * d:(p + 1) * d, :], axis=0, keepdims=True)
            for p in range(cfg.pack)]
    if cfg.pack == 1:
        return sums[0]
    return jnp.concatenate(
        [jnp.broadcast_to(r, (mp, x.shape[1])) for r in sums], axis=0)


# ---------------------------------------------------------------------------
# Forward kernel: encode sweep (phase 0) then decode sweep (phase 1)
# ---------------------------------------------------------------------------


def _fused_fwd_kernel(q_ref, k_ref, v_ref, y_ref, z_ref, mx_ref, den_ref,
                      mx_scr, den_scr, num_scr, zt_scr, qbd_scr, *sel, gh: int,
                      cfg: _PackedCfg, n_blocks: int):
    phase = pl.program_id(1)
    n_idx = pl.program_id(2)
    group = pl.program_id(0) % gh
    last = n_blocks - 1
    sel_scr = sel[0] if sel else None

    @pl.when(jnp.logical_and(phase == 0, n_idx == 0))
    def _init():
        # per-group invariants, read by every later step of the group
        bd = _bd_mask(cfg, q_ref.shape[-1])
        qbd_scr[...] = _expand_block_diag(cfg, q_ref[0], bd)  # input dtype
        if sel_scr is not None:
            _select_rows(sel_scr, group)
        mx_scr[...] = jnp.full_like(mx_scr, NEG_INF)
        den_scr[...] = jnp.zeros_like(den_scr)
        num_scr[...] = jnp.zeros_like(num_scr)

    @pl.when(phase == 0)
    def _encode():
        def step(masked):
            kt = _group_rows(k_ref[0], sel_scr)                         # [W, bn]
            vt = _group_rows(v_ref[0], sel_scr)
            if masked:
                vt = _zero_padding(cfg, vt, last)
            s = _cm_scores(cfg, qbd_scr[...], kt, last, token_mask=masked)  # [S, bn]
            m_prev = mx_scr[...]                                       # [S, 128]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - _lanes(m_new, s.shape[1]))
            if masked:
                p = jnp.where(_token_ok(cfg, s.shape, last), p, 0.0)
            den_scr[...] = den_scr[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # num[s, c] += sum_n p[s, n] V[c, n]
            num_scr[...] = num_scr[...] * _lanes(alpha, num_scr.shape[1]) + \
                jax.lax.dot_general(p.astype(vt.dtype), vt, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            mx_scr[...] = m_new

        _sweep(cfg, n_idx, n_blocks, step)

        @pl.when(n_idx == last)
        def _finish_encode():
            w = num_scr.shape[1]
            zbd = jnp.where(_bd_mask(cfg, w),
                            num_scr[...] / _lanes(den_scr[...], w), 0.0)
            zt_scr[...] = zbd.T                      # [W, S] for the decode
            z_ref[0] = _compact_block_diag(cfg, zbd)
            mx_ref[0] = mx_scr[...].T[0:1, :]
            den_ref[0] = den_scr[...].T[0:1, :]

    @pl.when(phase == 1)
    def _decode():
        def step(masked):
            kt = _group_rows(k_ref[0], sel_scr)
            s = _cm_scores(cfg, qbd_scr[...], kt, last, token_mask=masked)
            w = _decode_weights(cfg, s)                      # [S, bn]
            # y^T[c, n] = sum_s Z_bd^T[c, s] w[s, n]
            y_ref[0] = jnp.dot(zt_scr[...], w, preferred_element_type=jnp.float32
                               ).astype(y_ref.dtype)

        _sweep(cfg, n_idx, n_blocks, step)


def _token_specs(cfg: _PackedCfg, gh: int, c: int, slab: bool):
    """BlockSpec makers for the token operands of a (B*Gh, 2, N_blocks) grid,
    given the block index along N as a function of (phase, n):

      ``read``  K/V, [B, C, N]: the group's W rows, or the whole C slab;
      ``rows``  y, dy, dk, dv, [B, C, N], or [B*Gh, W, N] with whole slabs.
    """
    w = cfg.pack * cfg.d
    bn = cfg.block_n

    def read(at):
        if slab:
            return pl.BlockSpec((1, c, bn), lambda g_, p_, n_: (g_ // gh, 0, at(p_, n_)))
        return pl.BlockSpec((1, w, bn), lambda g_, p_, n_: (g_ // gh, g_ % gh, at(p_, n_)))

    def rows(at):
        if slab:
            return pl.BlockSpec((1, w, bn), lambda g_, p_, n_: (g_, 0, at(p_, n_)))
        return read(at)

    return read, rows


def _rows_shape(b: int, gh: int, c: int, w: int, n: int, slab: bool):
    return (b * gh, w, n) if slab else (b, c, n)


def _fwd_launch(cfg: _PackedCfg, gh: int, q_p, kt, vt):
    b, c, n = kt.shape
    w = cfg.pack * cfg.d
    s_rows = cfg.pack * cfg.mp
    n_blocks = -(-n // cfg.block_n)
    mp = cfg.mp
    g = b * gh
    slab = _whole_slab(w, c, kt.dtype, vt.dtype)
    read, rows = _token_specs(cfg, gh, c, slab)
    kernel = functools.partial(_fused_fwd_kernel, cfg=cfg, n_blocks=n_blocks, gh=gh)
    _note_masked_share(cfg, n_blocks)
    # ``name`` also opens jax.named_scope(name) around the launch, so the
    # instruction and its op_name carry it
    y, z, mx, den = pl.pallas_call(
        kernel,
        grid=(g, 2, n_blocks),
        in_specs=[
            # latent queries: one [Mp, W] block per packed head group,
            # shared across the batch through the index_map (never
            # broadcast to [B, ...] in HBM)
            pl.BlockSpec((1, mp, w), lambda g_, p_, n_: (g_ % gh, 0, 0)),
            # K streams in both phases; V only during encode (constant
            # index during decode — the pipeline re-fetches nothing)
            read(lambda p_, n_: n_),
            read(lambda p_, n_: (1 - p_) * n_),
        ],
        out_specs=[
            # y is only written during decode; during encode the out index
            # pins to block 0, which decode's first step overwrites before
            # any flush can happen
            rows(lambda p_, n_: p_ * n_),
            pl.BlockSpec((1, mp, w), lambda g_, p_, n_: (g_, 0, 0)),
            pl.BlockSpec((1, 1, s_rows), lambda g_, p_, n_: (g_, 0, 0)),
            pl.BlockSpec((1, 1, s_rows), lambda g_, p_, n_: (g_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(_rows_shape(b, gh, c, w, n, slab), vt.dtype),  # y
            jax.ShapeDtypeStruct((g, mp, w), jnp.float32),      # Z (compact)
            jax.ShapeDtypeStruct((g, 1, s_rows), jnp.float32),  # encode max
            jax.ShapeDtypeStruct((g, 1, s_rows), jnp.float32),  # encode den
        ],
        scratch_shapes=[
            _vmem((s_rows, LANE), jnp.float32),   # running max (lane-replicated)
            _vmem((s_rows, LANE), jnp.float32),   # running denominator (same)
            _vmem((s_rows, w), jnp.float32),      # running numerator, then Z_bd
            _vmem((w, s_rows), jnp.float32),      # Z_bd^T, across the phase switch
            _vmem((s_rows, w), q_p.dtype),        # block-diagonal Q
        ] + ([_vmem((w, c), jnp.float32)] if slab else []),   # row selection
        compiler_params=_compiler_params(),
        interpret=cfg.interpret,
        name="flare_packed_fwd",
    )(q_p, kt, vt)
    return y.reshape(b, c, n), z, mx, den


# ---------------------------------------------------------------------------
# Backward kernel: dZ sweep (phase 0) then dq/dk/dv sweep (phase 1)
# ---------------------------------------------------------------------------


def _fused_bwd_kernel(q_ref, k_ref, v_ref, z_ref, mx_ref, den_ref, y_ref, dy_ref,
                      dq_ref, dk_ref, dv_ref,
                      dz_scr, dzt_scr, dqa_scr, de_scr, mx_scr, den_scr, qbd_scr,
                      qbdt_scr, zbd_scr, *sel, gh: int,
                      cfg: _PackedCfg, n_blocks: int):
    phase = pl.program_id(1)
    n_idx = pl.program_id(2)
    group = pl.program_id(0) % gh
    last = n_blocks - 1
    sel_scr = sel[0] if sel else None

    @pl.when(jnp.logical_and(phase == 0, n_idx == 0))
    def _init():
        # per-group invariants, read by every later step of the group
        bd = _bd_mask(cfg, q_ref.shape[-1])
        qbd = _expand_block_diag(cfg, q_ref[0], bd)
        qbd_scr[...] = qbd                                     # input dtype
        qbdt_scr[...] = qbd.astype(jnp.float32).T
        zbd_scr[...] = _expand_block_diag(cfg, z_ref[0], bd)   # saved Z, fp32
        if sel_scr is not None:
            _select_rows(sel_scr, group)
        # the saved row statistics, lane-replicated for the grads sweep
        mx_scr[...] = jnp.broadcast_to(mx_ref[0], (LANE, mx_scr.shape[0])).T
        den_scr[...] = jnp.broadcast_to(den_ref[0], (LANE, den_scr.shape[0])).T
        dzt_scr[...] = jnp.zeros_like(dzt_scr)
        dqa_scr[...] = jnp.zeros_like(dqa_scr)

    @pl.when(phase == 0)
    def _sweep_dz():
        # dZ_p = sum_n W_p[n, :]^T dy_p[n, :]: recompute the decode weights
        # from K (no [N, M] residual). Cross-head lanes accumulate too and
        # are zeroed once, by the block-diagonal mask, when the sweep ends:
        # the same values as masking every step's term.
        def step(masked):
            dyt = dy_ref[0].astype(jnp.float32)                 # [W, bn]
            if masked:
                dyt = _zero_padding(cfg, dyt, last)
            kt = _group_rows(k_ref[0], sel_scr)
            s = _cm_scores(cfg, qbd_scr[...], kt, last, token_mask=masked)
            w = _decode_weights(cfg, s)
            # dZ^T[c, s] += sum_n dy[c, n] w[s, n]
            dzt_scr[...] = dzt_scr[...] + jax.lax.dot_general(
                dyt, w, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        _sweep(cfg, n_idx, n_blocks, step)

        @pl.when(n_idx == last)
        def _finish_dz():
            dz = jnp.where(_bd_mask(cfg, dz_scr.shape[1]), dzt_scr[...].T, 0.0)
            dz_scr[...] = dz
            dzt_scr[...] = dz.T
            # flash trick: rowsum(dA ∘ A) == rowsum(dZ ∘ Z) per latent row
            de_scr[...] = jnp.broadcast_to(
                jnp.sum(dz * zbd_scr[...], axis=-1, keepdims=True), de_scr.shape)

    @pl.when(phase == 1)
    def _sweep_grads():
        def step(masked):
            kt = _group_rows(k_ref[0], sel_scr)                 # [W, bn]
            vt = _group_rows(v_ref[0], sel_scr).astype(jnp.float32)
            yt = y_ref[0].astype(jnp.float32)
            dyt = dy_ref[0].astype(jnp.float32)
            if masked:
                kt, vt, yt, dyt = (_zero_padding(cfg, x, last)
                                   for x in (kt, vt, yt, dyt))
            s = _cm_scores(cfg, qbd_scr[...], kt, last, token_mask=masked)
            # encode weights from saved stats (flash recomputation)
            bn = s.shape[1]
            a = jnp.exp(s - _lanes(mx_scr[...], bn)) / _lanes(den_scr[...], bn)
            if masked:
                a = jnp.where(_token_ok(cfg, s.shape, last), a, 0.0)
            w = _decode_weights(cfg, s)
            # decode softmax VJP (per token, per head segment):
            #   dW[s, n]    = sum_c Z_bd[s, c] dy[c, n]
            #   delta[s, n] = sum_{c in head(s)} dy[c, n] y[c, n]  (== dy·y per
            #                 head — the decode flash trick): a sublane sum per
            #                 head, broadcast over the head's latent rows
            dw = jnp.dot(zbd_scr[...], dyt, preferred_element_type=jnp.float32)
            ds_dec = w * (dw - _head_sums(cfg, dyt * yt))
            # encode softmax VJP: dA = dZ V^T, delta_enc = rowsum(dZ ∘ Z)
            da = jnp.dot(dz_scr[...], vt, preferred_element_type=jnp.float32)
            ds_enc = a * (da - _lanes(de_scr[...], bn))
            ds = ds_enc + ds_dec                              # [S, bn]
            # dK^T = Q_bd^T dS and dV^T = dZ^T A, both [W, bn]
            dk_ref[0] = jnp.dot(qbdt_scr[...], ds,
                                preferred_element_type=jnp.float32).astype(dk_ref.dtype)
            dv_ref[0] = jnp.dot(dzt_scr[...], a,
                                preferred_element_type=jnp.float32).astype(dv_ref.dtype)
            # dQ_bd^T[c, s] += sum_n K[c, n] dS[s, n]
            dqa_scr[...] = dqa_scr[...] + jax.lax.dot_general(
                kt.astype(jnp.float32), ds, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        _sweep(cfg, n_idx, n_blocks, step)

        @pl.when(n_idx == last)
        def _finish_dq():
            dq_ref[0] = _compact_block_diag(
                cfg, jnp.where(_bd_mask(cfg, dqa_scr.shape[0]), dqa_scr[...].T, 0.0)
            ).astype(dq_ref.dtype)


def _bwd_launch(cfg: _PackedCfg, gh: int, q_p, kt, vt, z, mx, den, yt, dyt):
    b, c, n = kt.shape
    w = cfg.pack * cfg.d
    s_rows = cfg.pack * cfg.mp
    n_blocks = -(-n // cfg.block_n)
    mp = cfg.mp
    g = b * gh
    slab = _whole_slab(w, c, kt.dtype, vt.dtype)
    read, rows = _token_specs(cfg, gh, c, slab)
    kernel = functools.partial(_fused_bwd_kernel, cfg=cfg, n_blocks=n_blocks, gh=gh)
    _note_masked_share(cfg, n_blocks)
    rows_shape = _rows_shape(b, gh, c, w, n, slab)
    # the ``when`` factor pins a streamed operand's index to block 0 in the
    # phase that does not consume it
    both = lambda p_, n_: n_
    ph1 = lambda p_, n_: p_ * n_
    per_group = lambda shape: pl.BlockSpec(
        (1,) + shape, lambda g_, p_, n_: (g_,) + (0,) * len(shape))
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(g, 2, n_blocks),
        in_specs=[
            pl.BlockSpec((1, mp, w), lambda g_, p_, n_: (g_ % gh, 0, 0)),
            read(both),                   # k: scores recomputed in both sweeps
            read(ph1),                    # v: only dA in sweep 2
            per_group((mp, w)),           # z compact
            per_group((1, s_rows)),       # encode max
            per_group((1, s_rows)),       # encode den
            rows(ph1),                    # y: only delta_dec in sweep 2
            rows(both),                   # dy: dZ in sweep 1, dS_dec in sweep 2
        ],
        out_specs=[
            per_group((mp, w)),           # dq (written once per group)
            rows(ph1),                    # dk
            rows(ph1),                    # dv
        ],
        out_shape=[
            jax.ShapeDtypeStruct((g, mp, w), q_p.dtype),
            jax.ShapeDtypeStruct(rows_shape, kt.dtype),
            jax.ShapeDtypeStruct(rows_shape, vt.dtype),
        ],
        scratch_shapes=[
            _vmem((s_rows, w), jnp.float32),    # dZ accumulator, then dZ_bd
            _vmem((w, s_rows), jnp.float32),    # dZ_bd^T
            _vmem((w, s_rows), jnp.float32),    # dq^T accumulator
            _vmem((s_rows, LANE), jnp.float32), # delta_enc (lane-replicated)
            _vmem((s_rows, LANE), jnp.float32), # encode max (lane-replicated)
            _vmem((s_rows, LANE), jnp.float32), # encode den (lane-replicated)
            _vmem((s_rows, w), q_p.dtype),      # block-diagonal Q
            _vmem((w, s_rows), jnp.float32),    # its transpose, fp32
            _vmem((s_rows, w), jnp.float32),    # block-diagonal Z
        ] + ([_vmem((w, c), jnp.float32)] if slab else []),   # row selection
        compiler_params=_compiler_params(),
        interpret=cfg.interpret,
        name="flare_packed_bwd",
    )(q_p, kt, vt, z, mx, den, yt.reshape(rows_shape), dyt.reshape(rows_shape))
    return dq, dk.reshape(b, c, n), dv.reshape(b, c, n)


# ---------------------------------------------------------------------------
# custom_vjp core: operates on packed [Gh, Mp, W] latent queries and
# channel-major [B, C, N] token operands (C = Gh*W). Everything outside
# (head packing, transposes) is plain JAX and composes with this.
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _packed_core(cfg: _PackedCfg, gh: int, q_p, kt, vt):
    y, _, _, _ = _fwd_launch(cfg, gh, q_p, kt, vt)
    return y


def _packed_core_fwd(cfg: _PackedCfg, gh: int, q_p, kt, vt):
    y, z, mx, den = _fwd_launch(cfg, gh, q_p, kt, vt)
    return y, (q_p, kt, vt, z, mx, den, y)


def _packed_core_bwd(cfg: _PackedCfg, gh: int, res, dy):
    q_p, kt, vt, z, mx, den, y = res
    with scope("flare_packed_bwd"):
        dq_g, dk, dv = _bwd_launch(cfg, gh, q_p, kt, vt, z, mx, den, y, dy)
        # latent queries are shared across the batch: reduce the per-group dq
        g, mp, w = dq_g.shape
        dq = dq_g.reshape(g // gh, gh, mp, w).sum(axis=0).astype(q_p.dtype)
    return dq, dk, dv


_packed_core.defvjp(_packed_core_fwd, _packed_core_bwd)


# ---------------------------------------------------------------------------
# Public wrapper: [H, M, D] x [B, H, N, D] -> [B, H, N, D]
# ---------------------------------------------------------------------------


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _pack_heads(x: jax.Array, gh: int, pack: int, wl: int) -> jax.Array:
    """[..., Hp, N, D] -> [..., Gh, N, pack*D] (lane-padded to ``wl``):
    consecutive heads share the lane dimension of one group."""
    *lead, hp, n, d = x.shape
    x = x.reshape(*lead, gh, pack, n, d)
    x = jnp.moveaxis(x, -3, -2)                      # [..., Gh, N, pack, D]
    x = x.reshape(*lead, gh, n, pack * d)
    if wl > pack * d:
        padw = [(0, 0)] * (x.ndim - 1) + [(0, wl - pack * d)]
        x = jnp.pad(x, padw)
    return x


def _unpack_heads(x: jax.Array, pack: int, d: int) -> jax.Array:
    """[..., Gh, N, Wl] -> [..., Gh*pack, N, D]."""
    *lead, gh, n, _ = x.shape
    x = x[..., :pack * d].reshape(*lead, gh, n, pack, d)
    x = jnp.moveaxis(x, -2, -3)                      # [..., Gh, pack, N, D]
    return x.reshape(*lead, gh * pack, n, d)


def _pad_axis(x: jax.Array, axis: int, size: int) -> jax.Array:
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _channel_major(x: jax.Array, hp: int) -> jax.Array:
    """[B, H, N, D] -> [B, Hp*D, N]: channel c = h*D + j, tokens on lanes.
    The model's [B, N, C] activations already keep N minor in HBM, so XLA
    folds this transpose into their producer."""
    b, _, n, d = x.shape
    return _pad_axis(jnp.swapaxes(x, -1, -2), 1, hp).reshape(b, hp * d, n)


def _token_tile(n: int, block_n: int) -> int:
    """The N tile on lanes: the whole N where ``block_n`` covers it, else a
    multiple of 128 (the last block may be partial; the kernels mask it)."""
    bn = _round_up(max(1, block_n), LANE)
    return n if bn >= n else bn


def _note_pad_factor(k_shape, c: int, bn: int, kv_reads: int) -> None:
    """Gauge the bytes of K, V and y that a launch's blocks span over the
    bytes of their data ([B, H, N, D] each): a [B, C, N] operand swept in
    ``bn``-token blocks, K and V read ``kv_reads`` times a sweep (Gh with
    whole-slab blocks, else once)."""
    b, h, n, d = k_shape
    span = b * c * (-(-n // bn) * bn)
    REGISTRY.gauge(
        "flare_packed.operand_pad_factor",
        "bytes of K, V and y a packed launch's blocks span over their data's",
    ).set((2 * kv_reads + 1) * span / (3 * b * h * n * d))


def flare_mixer_packed(
    q: jax.Array,  # [H, M, D] latent queries
    k: jax.Array,  # [B, H, N, D]
    v: jax.Array,  # [B, H, N, D]
    *,
    pack: Optional[int] = None,
    block_n: int = 256,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Packed-head single-launch FLARE mixer; differentiable (custom VJP).
    ``block_n`` tiles N on lanes: rounded up to a multiple of 128, or the
    whole N where that covers it."""
    if interpret is None:
        interpret = not _on_tpu()
    b, h, n, d = k.shape
    m = q.shape[1]
    if pack is None:
        pack = heuristic_pack(h, m, d)
    pack = max(1, min(pack, h))
    gh = -(-h // pack)
    hp = gh * pack
    mp = _round_up(m, 16)
    bn = _token_tile(n, block_n)

    # the wrapper's layout work (head packing and padding, the transposes
    # to and from channel-major) is named apart from the launches in XLA
    # profiles
    with scope("flare_packed.layout"):
        qp = _pack_heads(_pad_axis(_pad_axis(q.astype(k.dtype), 0, hp), 1, mp),
                         gh, pack, pack * d)             # [Gh, Mp, W]
        kt = _channel_major(k, hp)                       # [B, C, N]
        vt = _channel_major(v, hp)

    cfg = _PackedCfg(
        pack=pack, mp=mp, d=d, block_n=bn,
        n_valid=n if n % bn else None,
        m_valid=m if m < mp else None,
        interpret=bool(interpret),
    )
    slab = _whole_slab(pack * d, hp * d, kt.dtype, vt.dtype)
    _note_pad_factor(k.shape, hp * d, bn, gh if slab else 1)
    yt = _packed_core(cfg, gh, qp, kt, vt)           # [B, C, N]
    with scope("flare_packed.layout"):
        y = jnp.swapaxes(yt.reshape(b, hp, d, n), -1, -2)
        return y[:, :h]
