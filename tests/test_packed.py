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
from repro.core.flare import flare_mixer
from repro.core.policy import MixerPolicy
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
        ref = flare_mixer(q, k, v, impl="sdpa").astype(jnp.float32)
        out = flare_mixer_packed(q, k, v, block_n=32).astype(jnp.float32)
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol, rtol=tol)

    @pytest.mark.parametrize("pack", [1, 2, 4])
    def test_explicit_pack_factors(self, pack):
        """Packed vs materialized backend across explicit pack factors —
        the layout transform must be invisible at every pack."""
        q, k, v = _qkv(h=4, m=8, n=50, d=8)
        ref = flare_mixer(q, k, v, impl="materialized")
        out = flare_mixer_packed(q, k, v, pack=pack, block_n=16)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_single_tile_and_multi_tile_agree(self):
        q, k, v = _qkv(h=2, m=8, n=96, d=8)
        y1 = flare_mixer_packed(q, k, v, block_n=96)
        y2 = flare_mixer_packed(q, k, v, block_n=16)
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
            return jnp.sum(w * flare_mixer(q, k, v, impl="sdpa"))

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


# grids with several groups (B*Gh >= 3) and N blocks (>= 3), so that the
# per-group scratch is rebuilt and the sweep runs first, steady and last
# blocks; N padded to the block and not, every pack, and latent padding
SWEEP_CASES = {
    "pack1_Npad": dict(b=2, h=3, m=16, n=70, pack=1),
    "pack1_Neven": dict(b=2, h=3, m=16, n=64, pack=1),
    "pack2_Npad": dict(b=2, h=4, m=16, n=50, pack=2),
    "pack2_Neven": dict(b=2, h=4, m=16, n=48, pack=2),
    "pack4_Npad": dict(b=3, h=4, m=16, n=40, pack=4),
    "pack4_Neven": dict(b=3, h=4, m=16, n=48, pack=4),
    "pack2_Mpad_Npad": dict(b=2, h=3, m=12, n=45, pack=2),
    # token tiles that the lane-replicated row statistics are widened to:
    # 208 lanes (block_n 256 over N = 200, not a multiple of 128) and 128
    "pack2_bn208": dict(b=2, h=2, m=16, n=200, pack=2, block_n=256),
    "pack2_bn128": dict(b=2, h=2, m=16, n=300, pack=2, block_n=128),
}


class TestSweepInvariants:
    """The packed launches build per-group invariants once per group and the
    token-padding mask only on the last N block; results must not move."""

    @pytest.mark.parametrize("case", SWEEP_CASES.values(), ids=SWEEP_CASES.keys())
    def test_forward_and_grads_match_materialized(self, case):
        case = dict(case)
        pack = case.pop("pack")
        block_n = case.pop("block_n", 16)
        q, k, v = _qkv(d=8, **case)
        w = jax.random.normal(jax.random.fold_in(KEY, 13), v.shape)
        ref_pol = MixerPolicy(backends=("materialized",))

        def packed(q, k, v):
            return flare_mixer_packed(q, k, v, pack=pack, block_n=block_n)

        def ref(q, k, v):
            return flare_mixer(q, k, v, policy=ref_pol)

        np.testing.assert_allclose(np.asarray(packed(q, k, v)),
                                   np.asarray(ref(q, k, v)),
                                   atol=1e-5, rtol=1e-5)
        gp = jax.grad(lambda *a: jnp.sum(w * packed(*a)), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda *a: jnp.sum(w * ref(*a)), argnums=(0, 1, 2))(q, k, v)
        for got, want in zip(gp, gr):
            scale = np.abs(np.asarray(want)).max() + 1e-12
            np.testing.assert_allclose(np.asarray(got) / scale,
                                       np.asarray(want) / scale,
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("launch", ["flare_packed_fwd", "flare_packed_bwd"])
    def test_steady_steps_build_no_masks(self, launch):
        """Walk each launch's kernel jaxpr as one grid step would run it:
        the steady state (a middle N block, either phase) executes no
        integer div/rem and no 2-D iota; the group's first step and the
        last block do (the group-init and last-block ``pl.when``s)."""
        q, k, v = _qkv(h=4, m=16, n=70, d=8)         # 5 blocks of 16, padded
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(flare_mixer_packed(q, k, v, pack=2, block_n=16)),
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
        the row sum of exp over the padded score matrix."""
        from repro.kernels import flare_packed as fp

        case = dict(case)
        pack = case.pop("pack")
        block_n = case.pop("block_n", 16)
        q, k, v = _qkv(d=8, **case)
        b, h, n, d = k.shape
        m = q.shape[1]
        gh = -(-h // pack)
        mp = fp._round_up(m, 16)
        wl = fp._round_up(pack * d, fp.LANE)
        bn = min(block_n, fp._round_up(n, 16))
        np_ = fp._round_up(n, bn)
        q_pad = fp._pad_axis(fp._pad_axis(q, 0, gh * pack), 1, mp)
        k_pad = fp._pad_axis(fp._pad_axis(k, 1, gh * pack), 2, np_)
        v_pad = fp._pad_axis(fp._pad_axis(v, 1, gh * pack), 2, np_)
        cfg = fp._PackedCfg(pack=pack, mp=mp, d=d, block_n=bn,
                            n_valid=n if n < np_ else None,
                            m_valid=m if m < mp else None, interpret=True)
        _, (*_, mx, den, _) = fp._packed_core_fwd(
            cfg, gh, fp._pack_heads(q_pad, gh, pack, wl),
            fp._pack_heads(k_pad, gh, pack, wl).reshape(b * gh, np_, wl),
            fp._pack_heads(v_pad, gh, pack, wl).reshape(b * gh, np_, wl))

        s = jnp.einsum("hid,bhjd->bhij", q_pad, k_pad,
                       precision=jax.lax.Precision.HIGHEST)
        ok = (jnp.arange(mp) < m)[:, None] & (jnp.arange(np_) < n)[None, :]
        s = jnp.where(ok, s, fp.NEG_INF)
        want_mx = jnp.max(s, axis=-1)                          # [B, Hp, Mp]
        want_den = jnp.sum(jnp.where(jnp.arange(np_) < n,
                                     jnp.exp(s - want_mx[..., None]), 0.0),
                           axis=-1)
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
        q, k, v = _qkv(h=4, m=16, n=70, d=8)         # 5 blocks of 16, padded
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(flare_mixer_packed(q, k, v, pack=2, block_n=16)),
            argnums=(0, 1, 2)))(q, k, v)
        kernel = _pallas_kernel_jaxpr(jaxpr.jaxpr, launch)
        rows, bn = 2 * 16, 16                          # S = pack * Mp
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

    @pytest.mark.parametrize("n, share", [(70, 1 / 5), (64, 0.0)],
                             ids=["Npad", "Neven"])
    def test_masked_block_share_gauge(self, n, share):
        q, k, v = _qkv(h=2, m=16, n=n, d=8)
        for fn in (lambda q, k, v: flare_mixer_packed(q, k, v, block_n=16),
                   jax.grad(lambda q, k, v: jnp.sum(
                       flare_mixer_packed(q, k, v, block_n=16)))):
            REGISTRY.gauge("flare_packed.masked_block_share").set(-1.0)
            jax.make_jaxpr(fn)(q, k, v)                # builds the launch(es)
            assert REGISTRY.get("flare_packed.masked_block_share").value == share


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
        """Acceptance: impl="auto" on TPU resolves to the packed backend for
        D < 128 (scored, not device-run — CPU CI has no TPU)."""
        for d, expect in ((4, "packed"), (8, "packed"), (64, "packed"),
                          (128, "pallas")):
            shape = dispatch.MixerShape(2, 4, 1024, 64, d)
            cands = [b for b in dispatch.backends(causal=False, sharded=False)
                     if dispatch.eligible(b, causal=False, dtype=jnp.float32,
                                          device="tpu")]
            best = max(cands, key=lambda b: b.score(shape, "tpu"))
            assert best.name == expect, (d, best.name)

    def test_named_forward_only_backend_errors_under_grad(self):
        shape = dispatch.MixerShape(1, 2, 32, 8, 8)
        with pytest.raises(ValueError, match="forward-only"):
            dispatch.resolve("pallas", shape=shape, dtype=jnp.float32, grad=True)
        with pytest.raises(ValueError, match="forward-only"):
            dispatch.resolve("causal_pallas", shape=shape, dtype=jnp.float32,
                             causal=True, grad=True)
        # grad-capable names resolve fine
        b, _ = dispatch.resolve("packed", shape=shape, dtype=jnp.float32, grad=True)
        assert b.name == "packed"

    def test_plan_describe_includes_pack(self):
        shape = dispatch.MixerShape(1, 4, 300, 16, 8)
        desc = dispatch.describe("packed", shape=shape)
        assert desc.startswith("packed(") and "pack=" in desc


class TestPackAutotune:
    def test_heuristic_pack_bounds(self):
        assert heuristic_pack(32, 64, 4) == 32          # fills 128 lanes
        assert heuristic_pack(2, 64, 4) == 2            # capped by head count
        assert heuristic_pack(8, 64, 64) == 2           # 2 * 64 = 128 lanes
        assert heuristic_pack(8, 64, 128) == 1          # nothing to pack
        assert heuristic_pack(32, 2048, 4) <= 2048 // 64  # VMEM row budget

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
        # the packed and tiles kinds must not collide in the cache
        tiles = autotune.best_params(shape, jnp.float32, "tpu", kind="tiles")
        assert "pack" not in tiles

    def test_store_merges_concurrent_writers(self, tmp_path, monkeypatch):
        """Another process's entries written between our load and store must
        survive the read-modify-write (temp-file + os.replace merge)."""
        import json

        from repro.backends import autotune

        path = tmp_path / "tiles.json"
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        autotune._MEM_CACHE.clear()
        shape = dispatch.MixerShape(1, 2, 300, 16, 8)
        autotune.measure_tiles(shape, jnp.float32, "cpu", lambda t: 0.001)
        # simulate a concurrent process appending its own key directly
        data = json.loads(path.read_text())
        data["other|proc|key"] = {"block_m": 1, "block_n": 2}
        path.write_text(json.dumps(data))
        # our next store (stale in-memory view) must keep the foreign key
        shape2 = dispatch.MixerShape(1, 2, 600, 32, 8)
        autotune.measure_tiles(shape2, jnp.float32, "cpu", lambda t: 0.001)
        final = json.loads(path.read_text())
        assert "other|proc|key" in final
        assert autotune.cache_key(shape, jnp.float32, "cpu") in final
        assert autotune.cache_key(shape2, jnp.float32, "cpu") in final

    def test_corrupt_cache_falls_back_to_heuristic(self, tmp_path, monkeypatch):
        from repro.backends import autotune

        path = tmp_path / "tiles.json"
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        path.write_text("{ not json !!")
        autotune._MEM_CACHE.clear()
        shape = dispatch.MixerShape(1, 2, 37, 8, 16)
        tiles = autotune.best_tiles(shape, jnp.float32, "cpu")
        assert tiles["block_m"] >= 8 and tiles["block_n"] >= 128
        packed = autotune.best_params(shape, jnp.float32, "cpu", kind="packed")
        assert packed["pack"] >= 1
        # a store over the corrupt file recovers it
        autotune.measure_tiles(shape, jnp.float32, "cpu", lambda t: 0.001)
        import json

        assert autotune.cache_key(shape, jnp.float32, "cpu") in json.loads(path.read_text())

    def test_malformed_entry_is_a_miss(self, tmp_path, monkeypatch):
        import json

        from repro.backends import autotune

        path = tmp_path / "tiles.json"
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        shape = dispatch.MixerShape(1, 2, 37, 8, 16)
        path.write_text(json.dumps(
            {autotune.cache_key(shape, jnp.float32, "cpu"): {"block_m": "??"}}))
        autotune._MEM_CACHE.clear()
        tiles = autotune.best_tiles(shape, jnp.float32, "cpu")
        assert tiles["block_n"] >= 128  # heuristic, not a crash


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
