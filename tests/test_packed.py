"""Packed-head single-launch fused mixer: forward parity, custom-VJP
gradient parity, grad-capability dispatch, pack autotuning, training smoke.

Everything runs in interpret mode (the wrappers auto-select it off-TPU), so
this file is the CI guard for the TPU training fast path (DESIGN.md §12).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch
from repro.core.dispatch import MixerPlan
from repro.core.flare import flare_mixer
from repro.core.policy import MixerPolicy, resolve_policy
from repro.kernels.flare_packed import flare_mixer_packed, heuristic_pack
from repro.obs.metrics import REGISTRY

KEY = jax.random.PRNGKey(7)


def _qkv(h=2, m=8, n=37, d=16, b=2, dtype=jnp.float32, scale=0.5):
    kq, kk, kv = jax.random.split(KEY, 3)
    q = (jax.random.normal(kq, (h, m, d)) * scale).astype(dtype)
    k = (jax.random.normal(kk, (b, h, n, d)) * scale).astype(dtype)
    v = jax.random.normal(kv, (b, h, n, d)).astype(dtype)
    return q, k, v


# odd/prime N, M > N, and the paper's D in {4, 8} alongside a large head dim
SHAPES = [
    {"n": 37, "m": 8, "d": 4, "h": 4},      # tiny D: pack fills 128 lanes
    {"n": 131, "m": 24, "d": 8, "h": 3},    # prime N, head count not a pack multiple
    {"n": 16, "m": 48, "d": 8, "h": 2},     # M > N
    {"n": 64, "m": 16, "d": 64, "h": 2},    # moderate pack (2 heads/lane group)
]


class TestForwardParity:
    @pytest.mark.parametrize("shape", SHAPES,
                             ids=lambda s: f"N{s['n']}M{s['m']}D{s['d']}H{s['h']}")
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
    def test_matches_sdpa(self, shape, dtype):
        q, k, v = _qkv(dtype=dtype, **shape)
        ref = flare_mixer(q, k, v, policy=MixerPlan("sdpa")).astype(jnp.float32)
        out = flare_mixer_packed(q, k, v, block_n=32).astype(jnp.float32)
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol, rtol=tol)

    @pytest.mark.parametrize("pack", [1, 2, 4])
    def test_explicit_pack_factors(self, pack):
        """Packed vs materialized backend across explicit pack factors —
        the layout transform must be invisible at every pack."""
        q, k, v = _qkv(h=4, m=8, n=50, d=8)
        ref = flare_mixer(q, k, v, policy=MixerPlan("materialized"))
        out = flare_mixer_packed(q, k, v, pack=pack, block_n=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_single_tile_and_multi_tile_agree(self):
        q, k, v = _qkv(h=2, m=8, n=300, d=8)
        y1 = flare_mixer_packed(q, k, v, block_n=300)
        y2 = flare_mixer_packed(q, k, v, block_n=128)   # 3 tiles, the last partial
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5, rtol=1e-5)


class TestGradParity:
    @pytest.mark.parametrize("shape", SHAPES,
                             ids=lambda s: f"N{s['n']}M{s['m']}D{s['d']}H{s['h']}")
    def test_custom_vjp_matches_reference_autodiff(self, shape):
        """jax.grad through the fused kernel (custom VJP) vs autodiff through
        the sdpa reference mixer: rtol <= 1e-4 in fp32 (acceptance bar)."""
        q, k, v = _qkv(**shape)
        w = jax.random.normal(jax.random.fold_in(KEY, 11), v.shape)  # cotangent

        def loss_packed(q, k, v):
            return jnp.sum(w * flare_mixer_packed(q, k, v, block_n=32))

        def loss_ref(q, k, v):
            return jnp.sum(w * flare_mixer(q, k, v, policy=MixerPlan("sdpa")))

        gp = jax.grad(loss_packed, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(gp, gr):
            scale = np.abs(np.asarray(want)).max() + 1e-12
            np.testing.assert_allclose(np.asarray(got) / scale,
                                       np.asarray(want) / scale,
                                       atol=1e-4, rtol=1e-4)

    def test_bf16_grads_finite_and_typed(self):
        q, k, v = _qkv(h=4, m=8, n=40, d=8, dtype=jnp.bfloat16)
        g = jax.grad(lambda q, k, v: jnp.sum(
            flare_mixer_packed(q, k, v, block_n=16).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for x in g:
            assert x.dtype == jnp.bfloat16
            assert bool(jnp.isfinite(x.astype(jnp.float32)).all())

    def test_grad_under_jit(self):
        q, k, v = _qkv(h=2, m=8, n=33, d=4)
        f = jax.jit(jax.grad(lambda q: jnp.sum(
            flare_mixer_packed(q, k, v, block_n=16) ** 2)))
        assert bool(jnp.isfinite(f(q)).all())


# grids with several groups (B*Gh >= 3) and N blocks (>= 3 of 128 tokens),
# so that the per-group scratch is rebuilt and the sweep runs first, steady
# and last blocks; a partial last block and not, every pack, latent
# padding, and the shapes whose pack*D channel rows are no legal sublane
# block (bfloat16 at pack*D = 8, float32 at pack*D = 4), which read the
# whole channel slab
SWEEP_CASES = {
    "pack1_Npad": dict(b=2, h=3, m=16, n=300, pack=1),
    "pack1_Neven": dict(b=2, h=3, m=16, n=384, pack=1),
    "pack2_Npad": dict(b=2, h=4, m=16, n=290, pack=2),
    "pack2_Neven": dict(b=2, h=4, m=16, n=384, pack=2),
    "pack4_Npad": dict(b=3, h=4, m=16, n=270, pack=4),
    "pack4_Neven": dict(b=3, h=4, m=16, n=384, pack=4),
    "pack2_Mpad_Npad": dict(b=2, h=3, m=12, n=301, pack=2),
    # token tiles that the lane-replicated row statistics are widened to:
    # the whole N of 208 (block_n 256 covers it; not a multiple of 128)
    # and 128
    "pack2_bn208": dict(b=2, h=2, m=16, n=208, pack=2, block_n=256),
    "pack2_bn128": dict(b=2, h=2, m=16, n=300, pack=2, block_n=128),
    "slab_bf16_pack1": dict(b=2, h=3, m=16, n=300, pack=1, dtype=jnp.bfloat16),
    "slab_D4_pack1": dict(b=2, h=3, m=16, n=300, pack=1, d=4),
}


def _sweep_case(case):
    """(pack, block_n, dtype, q, k, v) of a SWEEP_CASES entry."""
    case = dict(case)
    pack = case.pop("pack")
    block_n = case.pop("block_n", 128)
    case.setdefault("d", 8)
    dtype = case.setdefault("dtype", jnp.float32)
    return pack, block_n, dtype, *_qkv(**case)


class TestSweepInvariants:
    """The packed launches build per-group invariants once per group and the
    token-padding mask only on the last N block; results must not move."""

    @pytest.mark.parametrize("case", SWEEP_CASES.values(), ids=SWEEP_CASES.keys())
    def test_forward_and_grads_match_materialized(self, case):
        pack, block_n, dtype, q, k, v = _sweep_case(case)
        w = jax.random.normal(jax.random.fold_in(KEY, 13), v.shape)
        ref_pol = MixerPolicy(backends=("materialized",))
        f32 = lambda x: np.asarray(x.astype(jnp.float32))
        # fp32 at the acceptance bar; bfloat16 at its bar in TestForwardParity
        tol_y, tol_g = (1e-5, 1e-4) if dtype == jnp.float32 else (5e-2, 5e-2)

        def packed(q, k, v):
            return flare_mixer_packed(q, k, v, pack=pack, block_n=block_n)

        def ref(q, k, v):
            return flare_mixer(q, k, v, policy=ref_pol)

        np.testing.assert_allclose(f32(packed(q, k, v)), f32(ref(q, k, v)),
                                   atol=tol_y, rtol=tol_y)
        loss = lambda f: lambda *a: jnp.sum(w * f(*a).astype(jnp.float32))
        gp = jax.grad(loss(packed), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(gp, gr):
            assert got.dtype == want.dtype == dtype
            scale = np.abs(f32(want)).max() + 1e-12
            np.testing.assert_allclose(f32(got) / scale, f32(want) / scale,
                                       atol=tol_g, rtol=tol_g)

    @pytest.mark.parametrize("launch", ["flare_packed_fwd", "flare_packed_bwd"])
    def test_steady_steps_build_no_masks(self, launch):
        """Walk each launch's kernel jaxpr as one grid step would run it:
        the steady state (a middle N block, either phase) executes no
        integer div/rem and no 2-D iota; the group's first step and the
        last block do (the group-init and last-block ``pl.when``s)."""
        q, k, v = _qkv(h=4, m=16, n=570, d=8)        # 5 blocks of 128, partial
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(flare_mixer_packed(q, k, v, pack=2, block_n=128)),
            argnums=(0, 1, 2)))(q, k, v)
        kernel = _pallas_kernel_jaxpr(jaxpr.jaxpr, launch)
        for phase in (0, 1):
            assert _mask_work(kernel, (1, phase, 2)) == [], (launch, phase)
            assert _mask_work(kernel, (1, phase, 4)), (launch, phase)   # last block
        assert any(p == "div" for p, _ in _mask_work(kernel, (1, 0, 0)))

    @pytest.mark.parametrize("case", SWEEP_CASES.values(), ids=SWEEP_CASES.keys())
    def test_saved_row_statistics(self, case):
        """The forward's saved ``mx`` and ``den`` (compacted once per group
        from the sweep's lane-replicated statistics) are the row max and
        the row sum of exp over the score matrix of the real tokens, read
        from channel-major [B, C, N] K and V."""
        from repro.kernels import flare_packed as fp

        pack, block_n, dtype, q, k, v = _sweep_case(case)
        b, h, n, d = k.shape
        m = q.shape[1]
        gh = -(-h // pack)
        mp = fp._round_up(m, 16)
        bn = fp._token_tile(n, block_n)
        q_pad = fp._pad_axis(fp._pad_axis(q, 0, gh * pack), 1, mp)
        cfg = fp._PackedCfg(pack=pack, mp=mp, d=d, block_n=bn,
                            n_valid=n if n % bn else None,
                            m_valid=m if m < mp else None, interpret=True)
        _, (*_, mx, den, _) = fp._packed_core_fwd(
            cfg, gh, fp._pack_heads(q_pad, gh, pack, pack * d),
            fp._channel_major(k, gh * pack), fp._channel_major(v, gh * pack))

        k_pad = fp._pad_axis(k, 1, gh * pack)
        s = jnp.einsum("hid,bhjd->bhij", q_pad.astype(jnp.float32),
                       k_pad.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
        s = jnp.where((jnp.arange(mp) < m)[:, None], s, fp.NEG_INF)
        want_mx = jnp.max(s, axis=-1)                          # [B, Hp, Mp]
        want_den = jnp.sum(jnp.exp(s - want_mx[..., None]), axis=-1)
        as_saved = lambda x: x.reshape(b * gh, 1, pack * mp)  # head g*pack+p
        np.testing.assert_allclose(np.asarray(mx), np.asarray(as_saved(want_mx)),
                                   rtol=1e-6)
        np.testing.assert_allclose(np.asarray(den),
                                   np.asarray(as_saved(want_den)), rtol=1e-6)

    @pytest.mark.parametrize("launch, phase",
                             [("flare_packed_fwd", 0), ("flare_packed_bwd", 1)],
                             ids=["encode", "grads"])
    def test_steady_steps_keep_row_statistics_lane_replicated(self, launch, phase):
        """A steady step (a middle N block) of the forward's encode sweep
        and of the backward's grads sweep holds its per-latent-row
        statistics as [S, 128] lanes: every reduction over the token axis
        keeps its axis, no rank-1 [S] value is broadcast across a tile, and
        no other rank-1 [S] value (such as a read of [S] scratch) exists."""
        q, k, v = _qkv(h=4, m=16, n=570, d=8)        # 5 blocks of 128, partial
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(flare_mixer_packed(q, k, v, pack=2, block_n=128)),
            argnums=(0, 1, 2)))(q, k, v)
        kernel = _pallas_kernel_jaxpr(jaxpr.jaxpr, launch)
        rows, bn = 2 * 16, 128                         # S = pack * Mp
        reductions, dropped, broadcasts, other = 0, [], [], []
        for body, eqn in _step_eqns(kernel, (1, phase, 2)):
            name = eqn.primitive.name
            if name == "broadcast_in_dim" and \
                    eqn.invars[0].aval.shape == (rows,) and \
                    eqn.params["shape"] == (rows, bn):
                broadcasts.append(eqn)
            for out in eqn.outvars:
                if getattr(out.aval, "shape", None) != (rows,):
                    continue
                if name in ("reduce_max", "reduce_sum") and \
                        eqn.params["axes"] == (1,):
                    reductions += 1
                    users = [e for e in body.eqns
                             if any(x is out for x in e.invars)]
                    if not users or any(
                            u.primitive.name != "broadcast_in_dim" or
                            u.params["shape"] != (rows, 1) for u in users) or \
                            any(x is out for x in body.outvars):
                        dropped.append(eqn)
                else:
                    other.append(eqn)
        assert reductions == (2 if phase == 0 else 0)  # encode: max and sum
        assert dropped == [], dropped
        assert broadcasts == [], broadcasts
        assert other == [], other

    @pytest.mark.parametrize("n, share", [(570, 1 / 5), (640, 0.0)],
                             ids=["Npad", "Neven"])
    def test_masked_block_share_gauge(self, n, share):
        q, k, v = _qkv(h=2, m=16, n=n, d=8)
        for fn in (lambda q, k, v: flare_mixer_packed(q, k, v, block_n=128),
                   jax.grad(lambda q, k, v: jnp.sum(
                       flare_mixer_packed(q, k, v, block_n=128)))):
            REGISTRY.gauge("flare_packed.masked_block_share").set(-1.0)
            jax.make_jaxpr(fn)(q, k, v)                # builds the launch(es)
            assert REGISTRY.get("flare_packed.masked_block_share").value == share

    # the flare_pde.train_40k mixer (B 8, H 8, N 40,000, M 2,048, D 8, pack
    # 1, 256-token tiles): K, V and y cross HBM at their own size, 157
    # blocks spanning 40,192 tokens; and a whole-slab shape (D 4 at pack 1,
    # 8 groups), whose K and V each group reads in full
    @pytest.mark.parametrize("shape, factor", [
        (dict(b=8, h=8, n=40000, d=8, m=2048, dtype=jnp.float32), 40192 / 40000),
        (dict(b=2, h=8, n=1000, d=4, m=64, dtype=jnp.float32, pack=1),
         (2 * 8 + 1) / 3 * 1024 / 1000),
    ], ids=["flare_pde_cell", "slab_D4_pack1"])
    def test_operand_pad_factor_gauge(self, shape, factor):
        shape = dict(shape)
        pack = shape.pop("pack", None)
        b, h, n, d, m, dt = (shape[x] for x in ("b", "h", "n", "d", "m", "dtype"))
        q = jax.ShapeDtypeStruct((h, m, d), dt)
        k = jax.ShapeDtypeStruct((b, h, n, d), dt)
        REGISTRY.gauge("flare_packed.operand_pad_factor").set(-1.0)
        jax.make_jaxpr(lambda q, k, v: flare_mixer_packed(q, k, v, pack=pack))(q, k, k)
        got = REGISTRY.get("flare_packed.operand_pad_factor").value
        assert got == pytest.approx(factor, rel=1e-12)


def _pallas_kernel_jaxpr(jaxpr, name):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and \
                eqn.params["name"] == name:
            return eqn.params["jaxpr"]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found = _pallas_kernel_jaxpr(sub, name)
            if found is not None:
                return found
    return None


def _step_eqns(jaxpr, program_ids, env=None):
    """(jaxpr, equation) for every equation that one grid step at
    ``program_ids`` executes, with the jaxpr that holds it. Scalar integer
    and boolean equations are evaluated, so each ``pl.when`` takes the
    branch that step would; a branch whose index is not known counts as
    taken."""
    from jax.extend.core import Literal

    env = dict(env or {})
    read = lambda v: v.val if isinstance(v, Literal) else env.get(v)
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ins = [read(v) for v in eqn.invars]
        yield jaxpr, eqn
        if name == "program_id":
            env[eqn.outvars[0]] = np.int32(program_ids[eqn.params["axis"]])
        elif name == "cond":
            branches = eqn.params["branches"]
            taken = branches if ins[0] is None else [branches[int(ins[0])]]
            for br in taken:
                inner = {bv: x for bv, x in zip(br.jaxpr.invars, ins[1:])
                         if x is not None}
                yield from _step_eqns(br.jaxpr, program_ids, inner)
        elif name in ("jit", "pjit", "closed_call"):
            sub = eqn.params.get("jaxpr") or eqn.params["call_jaxpr"]
            sub = getattr(sub, "jaxpr", sub)
            inner = {sv: x for sv, x in zip(sub.invars, ins) if x is not None}
            yield from _step_eqns(sub, program_ids, inner)
        elif eqn.outvars and eqn.outvars[0].aval.shape == () and \
                all(x is not None for x in ins) and \
                not jnp.issubdtype(eqn.outvars[0].aval.dtype, jnp.floating):
            env[eqn.outvars[0]] = np.asarray(eqn.primitive.bind(*ins, **eqn.params))


def _mask_work(jaxpr, program_ids):
    """(primitive, shape) of every integer div/rem and every 2-D iota that
    one grid step at ``program_ids`` executes (:func:`_step_eqns`)."""
    found = []
    for _, eqn in _step_eqns(jaxpr, program_ids):
        name = eqn.primitive.name
        out = eqn.outvars[0].aval if eqn.outvars else None
        if name in ("div", "rem") and out.shape and \
                jnp.issubdtype(out.dtype, jnp.integer):
            found.append((name, out.shape))
        elif name == "iota" and len(out.shape) >= 2:
            found.append((name, out.shape))
    return found


class TestDispatch:
    def test_registered_with_grad_capability(self):
        b = dispatch.get_backend("packed")
        assert b.caps.grads and b.caps.bidirectional and not b.caps.causal
        assert "tpu" in b.caps.device_kinds

    def test_auto_grad_excludes_forward_only(self):
        """Training resolution ("auto", grad=True) must never land on a
        backend without a VJP, on any device kind."""
        shape = dispatch.MixerShape(2, 4, 100, 16, 8)
        for dev in ("cpu", "tpu"):
            cands = [b for b in dispatch.backends(causal=False, sharded=False)
                     if dispatch.eligible(b, causal=False, dtype=jnp.float32,
                                          device=dev, grad=True)]
            assert cands and all(b.caps.grads for b in cands)
            best = max(cands, key=lambda b: b.score(shape, dev))
            assert best.name == ("packed" if dev == "tpu" else "sdpa")

    def test_auto_on_tpu_prefers_packed_for_small_d(self):
        """Acceptance: the bare policy on TPU resolves to the packed backend
        at every head dim (scored, not device-run — CPU CI has no TPU)."""
        for d, expect in ((4, "packed"), (8, "packed"), (64, "packed"),
                          (128, "packed")):
            shape = dispatch.MixerShape(2, 4, 1024, 64, d)
            cands = [b for b in dispatch.backends(causal=False, sharded=False)
                     if dispatch.eligible(b, causal=False, dtype=jnp.float32,
                                          device="tpu")]
            best = max(cands, key=lambda b: b.score(shape, "tpu"))
            assert best.name == expect, (d, best.name)

    def test_named_forward_only_backend_errors_under_grad(self):
        shape = dispatch.MixerShape(1, 2, 32, 8, 8)
        with pytest.raises(ValueError, match="forward-only"):
            dispatch.resolve("paged", shape=shape, dtype=jnp.float32, grad=True)
        with pytest.raises(ValueError, match="forward-only"):
            dispatch.resolve("causal_pallas", shape=shape, dtype=jnp.float32,
                             causal=True, grad=True)
        # grad-capable names resolve fine
        b, _ = dispatch.resolve("packed", shape=shape, dtype=jnp.float32, grad=True)
        assert b.name == "packed"

    def test_plan_describe_includes_pack(self):
        shape = dispatch.MixerShape(1, 4, 300, 16, 8)
        desc = resolve_policy(MixerPolicy(backends=("packed",)), shape).describe()
        assert desc.startswith("packed(") and "pack=" in desc


class TestPackAutotune:
    def test_heuristic_pack_bounds(self):
        assert heuristic_pack(32, 64, 4) == 32          # fills 128 lanes
        assert heuristic_pack(2, 64, 4) == 2            # capped by head count
        assert heuristic_pack(8, 64, 64) == 2           # 2 * 64 = 128 lanes
        assert heuristic_pack(8, 64, 128) == 1          # nothing to pack
        assert heuristic_pack(32, 2048, 4) <= 2048 // 64  # VMEM row budget

    @pytest.mark.parametrize("tokens, tiles", [
        (100, [100]), (300, [128, 256, 300]),
        (40000, [128, 256, 512, 1024])])
    def test_candidate_tiles_are_lane_tiles(self, tokens, tiles):
        """With tokens on lanes a tile narrower than N is a multiple of 128,
        else it is the whole N: every candidate is a tile the wrapper runs
        as given."""
        from repro.backends import autotune
        from repro.kernels.flare_packed import _token_tile

        shape = dispatch.MixerShape(1, 8, tokens, 64, 8)
        got = sorted({c["block_n"] for c in autotune.packed_candidates(shape)})
        assert got == tiles
        assert all(_token_tile(tokens, bn) == bn for bn in got)

    def test_packed_kind_cache_roundtrip(self, tmp_path, monkeypatch):
        from repro.backends import autotune

        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tiles.json"))
        autotune._MEM_CACHE.clear()
        shape = dispatch.MixerShape(1, 8, 300, 16, 8)

        def runner(params):
            # pretend pack=8 with 128-wide tiles wins
            return 0.001 if (params["pack"], params["block_n"]) == (8, 128) else 0.002

        best = autotune.measure_tiles(shape, jnp.float32, "tpu", runner, kind="packed")
        assert best == {"block_n": 128, "pack": 8}
        autotune._MEM_CACHE.clear()
        got = autotune.best_params(shape, jnp.float32, "tpu", kind="packed")
        assert got == {"block_n": 128, "pack": 8}
        # a per-shard (mesh) lookup must not hit the single-device entry
        meshed = autotune.best_params(shape, jnp.float32, "tpu", kind="packed",
                                      autotune=False, mesh=(2, 2))
        assert meshed == autotune.default_packed(shape)

    def test_store_merges_concurrent_writers(self, tmp_path, monkeypatch):
        """Another process's entries written between our load and store must
        survive the read-modify-write (temp-file + os.replace merge)."""
        import json

        from repro.backends import autotune

        path = tmp_path / "tiles.json"
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        autotune._MEM_CACHE.clear()
        shape = dispatch.MixerShape(1, 2, 300, 16, 8)
        autotune.measure_tiles(shape, jnp.float32, "cpu", lambda t: 0.001,
                               kind="packed")
        # simulate a concurrent process appending its own key directly
        data = json.loads(path.read_text())
        data["other|proc|key"] = {"block_m": 1, "block_n": 2}
        path.write_text(json.dumps(data))
        # our next store (stale in-memory view) must keep the foreign key
        shape2 = dispatch.MixerShape(1, 2, 600, 32, 8)
        autotune.measure_tiles(shape2, jnp.float32, "cpu", lambda t: 0.001,
                               kind="packed")
        final = json.loads(path.read_text())
        assert "other|proc|key" in final
        assert autotune.cache_key(shape, jnp.float32, "cpu", "packed") in final
        assert autotune.cache_key(shape2, jnp.float32, "cpu", "packed") in final

    def test_corrupt_cache_falls_back_to_heuristic(self, tmp_path, monkeypatch):
        from repro.backends import autotune

        path = tmp_path / "tiles.json"
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        path.write_text("{ not json !!")
        autotune._MEM_CACHE.clear()
        shape = dispatch.MixerShape(1, 2, 37, 8, 16)
        packed = autotune.best_params(shape, jnp.float32, "cpu", kind="packed")
        assert packed["pack"] >= 1 and packed["block_n"] >= 128
        # a store over the corrupt file recovers it
        autotune.measure_tiles(shape, jnp.float32, "cpu", lambda t: 0.001,
                               kind="packed")
        import json

        assert autotune.cache_key(shape, jnp.float32, "cpu", "packed") in \
            json.loads(path.read_text())

    def test_malformed_entry_is_a_miss(self, tmp_path, monkeypatch):
        import json

        from repro.backends import autotune

        path = tmp_path / "tiles.json"
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        shape = dispatch.MixerShape(1, 2, 37, 8, 16)
        path.write_text(json.dumps(
            {autotune.cache_key(shape, jnp.float32, "cpu", "packed"):
             {"block_n": "??"}}))
        autotune._MEM_CACHE.clear()
        params = autotune.best_params(shape, jnp.float32, "cpu", kind="packed")
        assert params["block_n"] >= 128  # heuristic, not a crash


class TestTrainingSmoke:
    def test_flare_block_trains_on_packed_path(self):
        """Training smoke on the Pallas path (acceptance): a few AdamW steps
        through flare_block on the packed backend must run and reduce the
        loss. The grad requirement is the policy's requires_grad field."""
        from repro.core.flare import flare_block, init_flare_block
        from repro.core.policy import MixerPolicy
        from repro.optim.adamw import adamw_update, init_adamw

        dim, heads, latents, n = 16, 4, 8, 24
        params = init_flare_block(jax.random.fold_in(KEY, 1), dim, heads, latents)
        x = jax.random.normal(jax.random.fold_in(KEY, 2), (2, n, dim))
        target = jax.random.normal(jax.random.fold_in(KEY, 3), (2, n, dim)) * 0.1
        pol = MixerPolicy(backends=("packed",), requires_grad=True)

        def loss_fn(p):
            out = flare_block(p, x, policy=pol)
            return jnp.mean((out - target) ** 2)

        opt = init_adamw(params)

        @jax.jit
        def step(p, o):
            l, g = jax.value_and_grad(loss_fn)(p)
            p, o, _ = adamw_update(p, g, o, lr=1e-2)
            return p, o, l

        losses = []
        for _ in range(4):
            params, opt, l = step(params, opt)
            losses.append(float(l))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]

    def test_surrogate_loss_grad_path_resolves(self):
        """models/pde.py scopes the loss under mixer_policy(requires_grad=
        True); on CPU this stays on sdpa but must go through the grad-aware
        resolution without error."""
        from repro.models import pde

        params = pde.init_surrogate(jax.random.fold_in(KEY, 5), "flare",
                                    in_dim=3, out_dim=1, dim=16,
                                    num_heads=2, num_latents=4, num_blocks=1)
        batch = {"x": jax.random.normal(KEY, (2, 12, 3)),
                 "y": jax.random.normal(KEY, (2, 12, 1))}
        g = jax.grad(lambda p: pde.surrogate_loss(p, batch))(params)
        assert bool(jnp.isfinite(jax.tree.leaves(g)[0]).all())
