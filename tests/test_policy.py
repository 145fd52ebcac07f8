"""Plan-first MixerPolicy API (DESIGN.md §13): the policy stack, build-time
resolution, hashability (jit-static), the one selector vocabulary, and the
requires_grad safety contract (a training policy can never resolve onto a
forward-only kernel, bidirectional or causal).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dispatch
from repro.core.dispatch import MixerPlan, MixerShape
from repro.core.flare import flare_mixer
from repro.core.policy import (
    MixerPolicy,
    current_policy,
    ensure_plan,
    mixer_policy,
    resolve_policy,
    run_plan,
)

KEY = jax.random.PRNGKey(0)
SHAPE = MixerShape(batch=2, heads=2, tokens=64, latents=8, head_dim=16)


def _qkv(h=2, m=8, n=64, d=16, b=2):
    kq, kk, kv = jax.random.split(KEY, 3)
    return (jax.random.normal(kq, (h, m, d)),
            jax.random.normal(kk, (b, h, n, d)),
            jax.random.normal(kv, (b, h, n, d)))


class TestPolicyStack:
    def test_default_policy(self):
        pol = current_policy()
        assert pol.backends == ("auto",) and not pol.requires_grad

    def test_nested_override_and_restore(self):
        base = current_policy()
        with mixer_policy(backends=("sdpa",)) as outer:
            assert current_policy() is outer
            assert current_policy().backends == ("sdpa",)
            with mixer_policy(requires_grad=True) as inner:
                # inner layers on top of outer, not on the base
                assert current_policy() is inner
                assert inner.backends == ("sdpa",) and inner.requires_grad
            assert current_policy() is outer and not outer.requires_grad
        assert current_policy() is base

    def test_restore_on_exception(self):
        base = current_policy()
        with pytest.raises(RuntimeError):
            with mixer_policy(backends=("materialized",)):
                raise RuntimeError("boom")
        assert current_policy() is base

    def test_explicit_policy_plus_overrides(self):
        pol = MixerPolicy(backends=("sdpa", "materialized"))
        with mixer_policy(pol, requires_grad=True) as active:
            assert active.backends == ("sdpa", "materialized")
            assert active.requires_grad

    def test_ambient_policy_drives_flare_mixer(self):
        q, k, v = _qkv()
        with mixer_policy(backends=("materialized",)):
            y = flare_mixer(q, k, v)
        ref = flare_mixer(q, k, v, policy=MixerPolicy(backends=("materialized",)))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-6)


class TestHashability:
    def test_hash_and_dict_key(self):
        a = MixerPolicy(backends=("sdpa",), requires_grad=True)
        b = MixerPolicy(backends=("sdpa",), requires_grad=True)
        assert a == b and hash(a) == hash(b)
        assert {a: 1}[b] == 1

    def test_string_backends_normalized(self):
        assert MixerPolicy(backends="sdpa") == MixerPolicy(backends=("sdpa",))
        assert MixerPolicy(seq_axes="data").seq_axes == ("data",)

    def test_usable_as_jit_static_arg(self):
        calls = []

        @functools.partial(jax.jit, static_argnums=1)
        def f(x, pol: MixerPolicy):
            calls.append(pol)
            return x * (2.0 if pol.requires_grad else 1.0)

        x = jnp.ones(3)
        p1 = MixerPolicy(requires_grad=True)
        np.testing.assert_allclose(np.asarray(f(x, p1)), 2.0 * np.ones(3))
        # equal policy -> cache hit, no retrace
        n = len(calls)
        f(x, MixerPolicy(requires_grad=True))
        assert len(calls) == n
        # different policy -> retrace with the new static value
        np.testing.assert_allclose(np.asarray(f(x, MixerPolicy())), np.ones(3))

    def test_pytree_static_registration(self):
        # a policy inside a pytree is aux data (no leaves), so it can ride
        # through jax.tree.map and jit closures untouched
        tree = {"pol": MixerPolicy(backends=("sdpa",)), "x": jnp.ones(2)}
        leaves = jax.tree.leaves(tree)
        assert len(leaves) == 1  # only x — the policy is static structure


def _pde_cfg():
    from repro.config import AttnConfig, ModelConfig

    return ModelConfig(name="t", family="pde", num_layers=1, d_model=32,
                       d_ff=32, vocab=0, attn=AttnConfig(kind="none"),
                       flare_heads=4, flare_latents=8)


def _one_device_mesh():
    return jax.sharding.Mesh(np.array(jax.devices()).reshape(1, 1), ("s", "l"))


def _get_model_with_string():
    from repro.models.api import get_model

    get_model(_pde_cfg(), policy="sdpa")


@pytest.mark.parametrize("call", [
    lambda: resolve_policy("sdpa", SHAPE, jnp.float32),
    lambda: resolve_policy(("sp", _one_device_mesh(), "s"), SHAPE, jnp.float32),
    lambda: flare_mixer(*_qkv(), policy="sdpa"),
    _get_model_with_string,
    lambda: dispatch.resolve(("sp", _one_device_mesh(), "s"), shape=SHAPE,
                             dtype=jnp.float32),
    lambda: dispatch.get_backend("sp"),
], ids=["resolve_policy-str", "resolve_policy-tuple", "flare_mixer-str",
        "get_model-str", "dispatch.resolve-tuple", "get_backend-alias"])
def test_removed_selectors_are_rejected(call):
    """A selector is MixerPolicy | MixerPlan | None: the removed string and
    tuple spellings, and the old backend aliases, are errors that name the
    spelling to use."""
    with pytest.raises((TypeError, ValueError),
                       match="MixerPolicy|unknown mixer backend"):
        call()


class TestResolution:
    def test_plan_passthrough(self):
        plan = MixerPlan("sdpa")
        assert resolve_policy(plan, SHAPE, jnp.float32) is plan

    def test_preference_order_falls_through(self):
        # causal_pallas fails the bidirectional contract; sdpa picks it up
        pol = MixerPolicy(backends=("causal_pallas", "sdpa"))
        assert resolve_policy(pol, SHAPE, jnp.float32).backend == "sdpa"

    def test_single_name_contract_error_is_hard(self):
        with pytest.raises(ValueError, match="not causal"):
            resolve_policy(MixerPolicy(backends=("sdpa",)), SHAPE, jnp.float32,
                           causal=True)

    def test_exhausted_preference_reports_reasons(self):
        pol = MixerPolicy(backends=("paged", "causal_pallas"),
                          requires_grad=True)
        with pytest.raises(ValueError, match="preference order"):
            resolve_policy(pol, SHAPE, jnp.float32)

    def test_policy_dtype_overrides_data_dtype(self):
        pol = MixerPolicy(dtype="bfloat16")
        assert pol.dtype == "bfloat16"
        plan = resolve_policy(pol, SHAPE, jnp.float32)
        assert plan.backend  # resolves under the override without error

    def test_causal_chunk_size_override(self):
        pol = MixerPolicy(chunk_size=32)
        plan = resolve_policy(pol, SHAPE, jnp.float32, causal=True)
        assert plan.params["chunk_size"] == 32

    def test_sharded_hints_resolve_via_mesh(self):
        mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(1, 1),
                                 ("data", "model"))
        pol = MixerPolicy(seq_axes=("data", "model"))
        plan = resolve_policy(pol, SHAPE, jnp.float32, mesh=mesh)
        assert plan.backend == "seqparallel"
        pol2d = MixerPolicy(seq_axes=("data",), lat_axes=("model",))
        plan2d = resolve_policy(pol2d, SHAPE, jnp.float32, mesh=mesh)
        assert plan2d.backend == "seqlat"
        # a matching explicit name is fine; a conflicting one is an error,
        # never a silent override
        named = MixerPolicy(backends=("seqparallel",), seq_axes=("data", "model"))
        assert resolve_policy(named, SHAPE, jnp.float32,
                              mesh=mesh).backend == "seqparallel"
        clash = MixerPolicy(backends=("sdpa",), seq_axes=("data", "model"))
        with pytest.raises(ValueError, match="axis hints"):
            resolve_policy(clash, SHAPE, jnp.float32, mesh=mesh)

    def test_describe_distinguishes_non_defaults(self):
        assert MixerPolicy().describe() == "MixerPolicy(auto)"
        assert "autotune=False" in MixerPolicy(autotune=False).describe()
        assert "requires_grad=True" in MixerPolicy(requires_grad=True).describe()
        assert MixerPolicy(autotune=False).describe() != MixerPolicy().describe()

    def test_run_plan_matches_reference(self):
        q, k, v = _qkv()
        plan = resolve_policy(MixerPolicy(backends=("materialized",)),
                              MixerShape.from_qkv(q, k), k.dtype)
        y = run_plan(plan, q, k, v)
        ref = flare_mixer(q, k, v, policy=MixerPolicy(backends=("sdpa",)))
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


class TestRequiresGradContract:
    """Regression: a requires_grad=True policy can NEVER resolve the
    forward-only kernels, on either contract, on any device kind."""

    @pytest.mark.parametrize("name,causal", [("paged", False),
                                             ("causal_pallas", True)])
    def test_named_forward_only_backend_is_an_error(self, name, causal):
        pol = MixerPolicy(backends=(name,), requires_grad=True)
        with pytest.raises(ValueError, match="forward-only"):
            resolve_policy(pol, SHAPE, jnp.float32, causal=causal)

    @pytest.mark.parametrize("causal", [False, True], ids=["bidi", "causal"])
    def test_auto_never_lands_forward_only(self, causal):
        pol = MixerPolicy(requires_grad=True)
        for dev in ("cpu", "gpu", "tpu"):
            cands = [b for b in dispatch.backends(causal=causal, sharded=False)
                     if dispatch.eligible(b, causal=causal, dtype=jnp.float32,
                                          device=dev, grad=True)]
            assert cands, dev
            assert all(b.caps.grads for b in cands)
            assert "paged" not in {b.name for b in cands}
            assert "causal_pallas" not in {b.name for b in cands}
        # and the actual resolution on this device
        plan = resolve_policy(pol, SHAPE, jnp.float32, causal=causal)
        assert dispatch.get_backend(plan.backend).caps.grads

    def test_preference_order_skips_forward_only_under_grad(self):
        pol = MixerPolicy(backends=("paged", "sdpa"), requires_grad=True)
        assert resolve_policy(pol, SHAPE, jnp.float32).backend == "sdpa"
        polc = MixerPolicy(backends=("causal_pallas", "causal_stream"),
                           requires_grad=True)
        assert resolve_policy(polc, SHAPE, jnp.float32,
                              causal=True).backend == "causal_stream"

    def test_ensure_plan_rechecks_grad_contract(self):
        plan = MixerPlan("paged")
        with mixer_policy(requires_grad=True):
            with pytest.raises(ValueError, match="forward-only"):
                ensure_plan(plan, SHAPE, jnp.float32)
        # outside the training scope the same plan is fine
        assert ensure_plan(plan, SHAPE, jnp.float32) is plan

    def test_loss_paths_use_grad_capable_plans(self):
        """get_model resolves the loss plan with requires_grad=True even if
        the policy did not ask for it."""
        from repro.config import AttnConfig, ModelConfig
        from repro.models.api import get_model

        cfg = ModelConfig(name="t", family="pde", num_layers=1, d_model=32,
                          d_ff=32, vocab=0, attn=AttnConfig(kind="none"),
                          flare_heads=4, flare_latents=8)
        model = get_model(cfg, policy=MixerPolicy())
        assert dispatch.get_backend(model.plans["train"].backend).caps.grads

        lm = ModelConfig(name="lm", family="flare_lm", num_layers=1,
                         d_model=32, d_ff=64, vocab=64,
                         attn=AttnConfig(kind="flare_stream", num_heads=4,
                                         head_dim=8, flare_latents=4,
                                         flare_chunk=8))
        model = get_model(lm, policy=MixerPolicy(), seq_len_hint=32)
        train = model.plans["train"]
        assert dispatch.get_backend(train.backend).caps.grads
        assert dispatch.get_backend(train.backend).caps.causal
        assert train.params["chunk_size"] == 8  # cfg chunk baked at build


class TestBuildTimeResolution:
    def test_model_plans_are_exposed_and_run(self):
        from repro.config import AttnConfig, ModelConfig
        from repro.models.api import get_model

        cfg = ModelConfig(name="lm", family="flare_lm", num_layers=1,
                          d_model=32, d_ff=64, vocab=64,
                          attn=AttnConfig(kind="flare_stream", num_heads=4,
                                          head_dim=8, flare_latents=4,
                                          flare_chunk=8), remat="none")
        model = get_model(cfg, seq_len_hint=16)
        assert set(model.plans) == {"train", "infer"}
        params = model.init(jax.random.PRNGKey(0))
        batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
                 "labels": jnp.zeros((2, 16), jnp.int32)}
        loss = model.loss(params, batch)
        assert jnp.isfinite(loss)
        g = jax.grad(lambda p: model.loss(p, batch))(params)
        assert all(jnp.all(jnp.isfinite(x)) for x in jax.tree.leaves(g))

    def test_inference_only_policy_builds_and_serves(self):
        """A policy naming only a forward-only backend must still build a
        servable model; only model.loss errors (with the resolve reason)."""
        from repro.config import AttnConfig, ModelConfig
        from repro.models.api import get_model

        cfg = ModelConfig(name="lm", family="flare_lm", num_layers=1,
                          d_model=32, d_ff=64, vocab=64,
                          attn=AttnConfig(kind="flare_stream", num_heads=4,
                                          head_dim=8, flare_latents=4,
                                          flare_chunk=8), remat="none")
        model = get_model(cfg, policy=MixerPolicy(backends=("causal_pallas",)),
                          seq_len_hint=16)
        assert model.plans["infer"].backend == "causal_pallas"
        assert "train" not in model.plans
        params = model.init(jax.random.PRNGKey(0))
        logits, _ = model.forward(params, {"tokens": jnp.zeros((1, 16), jnp.int32)})
        assert jnp.all(jnp.isfinite(logits))
        batch = {"tokens": jnp.zeros((1, 16), jnp.int32),
                 "labels": jnp.zeros((1, 16), jnp.int32)}
        with pytest.raises(ValueError, match="inference-only"):
            model.loss(params, batch)

    def test_serve_engine_reports_build_plan(self):
        from repro.config import AttnConfig, ModelConfig
        from repro.models.api import get_model
        from repro.serve.engine import ServeEngine

        cfg = ModelConfig(name="lm", family="flare_lm", num_layers=1,
                          d_model=32, d_ff=64, vocab=64,
                          attn=AttnConfig(kind="flare_stream", num_heads=4,
                                          head_dim=8, flare_latents=4,
                                          flare_chunk=8), remat="none")
        model = get_model(cfg, seq_len_hint=32)
        params = model.init(jax.random.PRNGKey(0))
        engine = ServeEngine(model, params, capacity=32)
        assert engine.stats["mixer_backend"] == model.plans["infer"].describe()


class TestAutotuneVersionedKeys:
    def test_cache_key_carries_runtime_version(self):
        from repro.backends import autotune

        key = autotune.cache_key(SHAPE, jnp.float32, "cpu", "packed")
        legacy = autotune.legacy_cache_key(SHAPE, jnp.float32, "cpu", "packed")
        assert key.startswith(legacy) and autotune.runtime_version() in key
        assert "jax" in autotune.runtime_version()

    def test_legacy_unversioned_entry_still_hits(self, tmp_path, monkeypatch):
        import json

        from repro.backends import autotune

        path = tmp_path / "tiles.json"
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        autotune._MEM_CACHE.clear()
        legacy_key = autotune.legacy_cache_key(SHAPE, jnp.float32, "cpu", "packed")
        path.write_text(json.dumps({legacy_key: {"block_n": 384, "pack": 2}}))
        got = autotune.best_params(SHAPE, jnp.float32, "cpu", kind="packed")
        assert got == {"block_n": 384, "pack": 2}

    def test_new_measurements_store_versioned(self, tmp_path, monkeypatch):
        import json

        from repro.backends import autotune

        path = tmp_path / "tiles.json"
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        autotune._MEM_CACHE.clear()
        autotune.measure_tiles(SHAPE, jnp.float32, "cpu",
                               lambda t: 0.001 if t["pack"] == 2 else 0.002,
                               kind="packed")
        data = json.loads(path.read_text())
        assert list(data) == [autotune.cache_key(SHAPE, jnp.float32, "cpu", "packed")]
        # versioned winner is read back after a cold start
        autotune._MEM_CACHE.clear()
        assert autotune.best_params(SHAPE, jnp.float32, "cpu",
                                    kind="packed")["pack"] == 2

    def test_versioned_entry_wins_over_legacy(self, tmp_path, monkeypatch):
        import json

        from repro.backends import autotune

        path = tmp_path / "tiles.json"
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
        autotune._MEM_CACHE.clear()
        path.write_text(json.dumps({
            autotune.legacy_cache_key(SHAPE, jnp.float32, "cpu", "packed"):
                {"block_n": 128, "pack": 1},
            autotune.cache_key(SHAPE, jnp.float32, "cpu", "packed"):
                {"block_n": 512, "pack": 2},
        }))
        assert autotune.best_params(SHAPE, jnp.float32, "cpu", kind="packed") == {
            "block_n": 512, "pack": 2}

    def test_policy_autotune_optin_scopes_enablement(self, monkeypatch):
        from repro.backends import autotune

        monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
        assert not autotune.autotune_enabled()
        with autotune.forced(True):
            assert autotune.autotune_enabled()
            with autotune.forced(False):
                assert not autotune.autotune_enabled()
            assert autotune.autotune_enabled()
        assert not autotune.autotune_enabled()
