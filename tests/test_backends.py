"""Mixer-backend registry: capability dispatch, parity, autotune.

Parity contract: every registered non-sharded bidirectional backend must
agree with the ``sdpa`` reference within tolerance across awkward shapes —
odd/prime N (the unstructured-mesh sizes the paper targets, and exactly the
case the old tile-halving degenerated on), M > N, and bf16 as well as fp32;
every one that claims ``grads`` must agree with ``sdpa``'s gradients too.
The causal backends are checked against the O(N^2) causal oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch
from repro.core.dispatch import MixerPlan
from repro.core.flare import flare_mixer
from repro.core.flare_stream import flare_causal_ref
from repro.core.policy import MixerPolicy, resolve_policy, run_plan

KEY = jax.random.PRNGKey(0)


def _qkv(h=2, m=8, n=37, d=16, b=2, dtype=jnp.float32, scale=0.5):
    kq, kk, kv = jax.random.split(KEY, 3)
    q = (jax.random.normal(kq, (h, m, d)) * scale).astype(dtype)
    k = (jax.random.normal(kk, (b, h, n, d)) * scale).astype(dtype)
    v = jax.random.normal(kv, (b, h, n, d)).astype(dtype)
    return q, k, v


def _local_backends(causal):
    return [b.name for b in dispatch.backends(causal=causal, sharded=False)]


def _grad_backends():
    """Grad-capable non-sharded bidirectional backends, the reference aside."""
    return [b.name for b in dispatch.backends(causal=False, sharded=False)
            if b.caps.grads and b.name != "sdpa"]


SHAPES = [
    {"n": 37, "m": 8},            # odd/prime N
    {"n": 64, "m": 16},           # aligned
    {"n": 16, "m": 48},           # M > N
    {"n": 131, "m": 24},          # prime N > default small tiles
]


class TestParity:
    @pytest.mark.parametrize("name", _local_backends(causal=False))
    @pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"N{s['n']}M{s['m']}")
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["fp32", "bf16"])
    def test_bidirectional_matches_sdpa(self, name, shape, dtype):
        backend = dispatch.get_backend(name)
        if not dispatch._dtype_ok(backend.caps, dtype):
            pytest.skip(f"{name} does not declare {jnp.dtype(dtype).name}")
        q, k, v = _qkv(dtype=dtype, **shape)
        ref = flare_mixer(q, k, v, policy=MixerPlan("sdpa")).astype(jnp.float32)
        out = flare_mixer(q, k, v, policy=MixerPolicy(backends=(name,)))
        tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
        np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                                   np.asarray(ref), atol=tol, rtol=tol)

    @pytest.mark.parametrize("name", _grad_backends())
    @pytest.mark.parametrize("shape", SHAPES, ids=lambda s: f"N{s['n']}M{s['m']}")
    def test_grad_capable_backends_match_sdpa_grads(self, name, shape):
        """A backend that claims ``grads`` gives sdpa's gradients (fp32)."""
        q, k, v = _qkv(**shape)
        w = jax.random.normal(jax.random.fold_in(KEY, 5), v.shape)  # cotangent
        pol = MixerPolicy(backends=(name,), requires_grad=True)

        def loss(policy):
            return lambda q, k, v: jnp.sum(w * flare_mixer(q, k, v, policy=policy))

        got = jax.grad(loss(pol), argnums=(0, 1, 2))(q, k, v)
        want = jax.grad(loss(MixerPlan("sdpa")), argnums=(0, 1, 2))(q, k, v)
        for g, r in zip(got, want):
            scale = np.abs(np.asarray(r)).max() + 1e-12
            np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(r) / scale,
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("name", _local_backends(causal=True))
    @pytest.mark.parametrize("shape", [{"n": 37, "m": 8}, {"n": 16, "m": 48}],
                             ids=lambda s: f"N{s['n']}M{s['m']}")
    def test_causal_matches_oracle(self, name, shape):
        q, k, v = _qkv(**shape)
        ref = flare_causal_ref(q, k, v)
        plan = resolve_policy(MixerPolicy(backends=(name,), chunk_size=8),
                              dispatch.MixerShape.from_qkv(q, k), k.dtype,
                              causal=True)
        out = run_plan(plan, q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestResolution:
    def test_auto_resolves_on_cpu(self):
        q, k, v = _qkv()
        backend, plan = dispatch.resolve(
            "auto", shape=dispatch.MixerShape.from_qkv(q, k), dtype=k.dtype)
        assert backend.name == plan.backend
        # "auto" must never pick a sharded backend without a mesh
        assert not backend.caps.sharded
        y = flare_mixer(q, k, v, policy=MixerPolicy())
        assert y.shape == v.shape

    def test_sharded_plan_decision(self):
        mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(1, 1), ("data", "model"))
        assert dispatch.sharded_plan(mesh, ("data", "model")).backend == "seqparallel"
        assert dispatch.sharded_plan(mesh, ("data",)).backend == "seqlat"

    def test_causal_capability_respected(self):
        shape = dispatch.MixerShape(1, 2, 16, 4, 8)
        backend, _ = dispatch.resolve("auto", shape=shape, dtype=jnp.float32, causal=True)
        assert backend.caps.causal
        with pytest.raises(ValueError, match="unknown mixer backend"):
            dispatch.resolve("not_a_backend", shape=shape, dtype=jnp.float32)

    def test_contract_enforced_for_named_backends(self):
        """A bidirectional backend on the causal path would leak future
        tokens — explicit names must hard-error, not silently run."""
        q, k, v = _qkv(n=16)
        shape = dispatch.MixerShape.from_qkv(q, k)
        for name in ("sdpa", "paged", "materialized"):
            with pytest.raises(ValueError, match="not causal"):
                dispatch.resolve(name, shape=shape, dtype=jnp.float32, causal=True)
            with pytest.raises(ValueError, match="not causal"):
                resolve_policy(MixerPolicy(backends=(name,)), shape, k.dtype,
                               causal=True)
        # and the reverse: causal-only backends can't serve the set mixer
        with pytest.raises(ValueError, match="causal contract"):
            dispatch.resolve("causal_stream", shape=shape, dtype=jnp.float32)
        # pre-built plans go through the same check
        with pytest.raises(ValueError, match="not causal"):
            dispatch.resolve(dispatch.MixerPlan("sdpa"), shape=shape,
                             dtype=jnp.float32, causal=True)

    def test_auto_with_mesh_picks_runnable_sharded_plan(self):
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("x",))
        q, k, v = _qkv(n=16)
        backend, plan = dispatch.resolve(
            "auto", shape=dispatch.MixerShape.from_qkv(q, k), dtype=k.dtype, mesh=mesh)
        assert backend.caps.sharded and plan.params["seq_axes"] == ("x",)
        y = run_plan(resolve_policy(MixerPolicy(), dispatch.MixerShape.from_qkv(q, k),
                                    k.dtype, mesh=mesh), q, k, v)
        ref = flare_mixer(q, k, v, policy=MixerPlan("sdpa"))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-5)

    def test_plan_describe_round_trips(self):
        shape = dispatch.MixerShape(1, 2, 300, 16, 8)
        desc = resolve_policy(MixerPolicy(backends=("packed",)), shape).describe()
        assert desc.startswith("packed(") and "block_n=" in desc


class TestAutotune:
    def test_cache_roundtrip(self, tmp_path, monkeypatch):
        from repro.backends import autotune

        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tiles.json"))
        autotune._MEM_CACHE.clear()
        shape = dispatch.MixerShape(1, 2, 300, 16, 8)

        calls = []

        def runner(params):
            calls.append(params)
            # pretend 256-wide N tiles are fastest
            return 0.001 if params["block_n"] == 256 else 0.002

        best = autotune.measure_tiles(shape, jnp.float32, "cpu", runner,
                                      kind="packed")
        assert best["block_n"] == 256 and calls
        # a fresh lookup (memory cache cleared) reads the JSON file
        autotune._MEM_CACHE.clear()
        got = autotune.best_params(shape, jnp.float32, "cpu", kind="packed")
        assert got == {"block_n": 256, "pack": best["pack"]}
        # and the packed backend plan consumes it
        _, plan = dispatch.resolve("packed", shape=shape, dtype=jnp.float32)
        assert plan.params["block_n"] == 256

    def test_heuristic_without_cache(self, tmp_path, monkeypatch):
        from repro.backends import autotune

        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "none.json"))
        monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
        autotune._MEM_CACHE.clear()
        shape = dispatch.MixerShape(1, 2, 37, 8, 16)
        params = autotune.best_params(shape, jnp.float32, "cpu", kind="packed")
        assert params["pack"] >= 1 and params["block_n"] >= 128
