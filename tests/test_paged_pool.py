"""Paged state-pool subsystem (DESIGN.md §4 "Paged pool"): allocator units,
quantization bounds, paged-vs-dense engine parity (bit-identical under
lossless storage, bounded under int8), OOM admission backpressure, the
gather-decode Pallas kernel, and the `paged` mixer backend."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models.api import get_model
from repro.serve.engine import ServeEngine
from repro.serve.pool import BlockAllocator, PagedModelCache, get_quant
from repro.serve.pool.quant import dequantize, quantize

KEY = jax.random.PRNGKey(0)

_MODELS = {}


@pytest.fixture(autouse=True)
def _sanitize_engines(monkeypatch):
    """Every engine built in this module gets the allocator/page-table
    sanitizer run at teardown — each pool test doubles as a sanitizer run
    (DESIGN.md §14). Bare BlockAllocator units are NOT auto-checked: some
    deliberately corrupt state to exercise the underflow detectors; valid
    ones call check_invariants() explicitly."""
    engines = []
    orig = ServeEngine.__init__

    def recording_init(self, *a, **k):
        orig(self, *a, **k)
        engines.append(self)

    monkeypatch.setattr(ServeEngine, "__init__", recording_init)
    yield
    for eng in engines:
        eng.check_invariants()


def _model(arch):
    if arch not in _MODELS:
        model = get_model(get_smoke_config(arch))
        _MODELS[arch] = (model, model.init(KEY))
    return _MODELS[arch]


def _requests(vocab, n=5, seed=0, lo=3, hi=14):
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi, n)
    max_new = rng.integers(3, 11, n)
    return [(rng.integers(0, vocab, lens[i]).astype(np.int32), int(max_new[i]))
            for i in range(n)]


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


def test_allocator_reserve_map_release():
    a = BlockAllocator(6, 8)
    assert a.can_reserve(6) and not a.can_reserve(7)
    lease = a.reserve(4)
    assert a.available() == 2  # reservations count against admission
    ids = a.map(lease, 2)
    assert ids == [0, 1]  # lowest ids first — deterministic
    assert a.mapped_blocks() == 2 and lease.reserved == 2
    a.check_invariants(external_refs={0: 1, 1: 1})
    a.release(lease)
    assert a.available() == 6 and a.mapped_blocks() == 0
    a.check_invariants(external_refs={})


def test_allocator_append_and_stats():
    a = BlockAllocator(4, 8)
    lease = a.reserve(3)
    a.map(lease, 1)
    a.append(lease)
    assert a.pages_appended == 1 and lease.mapped == [0, 1]
    assert a.stats()["blocks_peak_mapped"] == 2
    a.check_invariants()


def test_allocator_no_double_free():
    a = BlockAllocator(4, 8)
    l1 = a.reserve(2)
    a.map(l1, 2)
    a.release(l1)
    with pytest.raises(RuntimeError, match="free"):
        # a stale lease whose blocks already went back
        import dataclasses

        a.release(dataclasses.replace(l1, mapped=[0, 1], reserved=0))


def test_allocator_overmap_and_oom():
    a = BlockAllocator(2, 8)
    with pytest.raises(RuntimeError, match="exhausted"):
        a.reserve(3)
    lease = a.reserve(1)
    with pytest.raises(RuntimeError, match="reserved"):
        a.map(lease, 2)


# ---------------------------------------------------------------------------
# quantization
# ---------------------------------------------------------------------------


def test_quant_none_is_lossless():
    spec = get_quant("none")
    x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)), jnp.bfloat16)
    q, s = quantize(spec, x)
    assert s is None
    np.testing.assert_array_equal(np.asarray(dequantize(spec, q, s, x.dtype)),
                                  np.asarray(x))


def test_quant_int8_error_bound():
    spec = get_quant("int8")
    x = jnp.asarray(np.random.default_rng(1).normal(size=(16, 32)) * 50,
                    jnp.float32)
    q, s = quantize(spec, x)
    assert q.dtype == jnp.int8 and s.shape == (16,)
    err = np.abs(np.asarray(dequantize(spec, q, s, jnp.float32)) - np.asarray(x))
    amax = np.max(np.abs(np.asarray(x)), axis=-1, keepdims=True)
    # symmetric int8 with per-row scale: |err| <= scale/2 = amax/254
    assert np.all(err <= amax / 254 + 1e-6)


def test_quant_zero_row_safe():
    spec = get_quant("int8")
    q, s = quantize(spec, jnp.zeros((3, 8), jnp.float32))
    assert np.all(np.asarray(dequantize(spec, q, s, jnp.float32)) == 0)


@pytest.mark.skipif(not hasattr(jnp, "float8_e4m3fn"),
                    reason="no fp8 dtype in this jax build")
def test_quant_fp8_roundtrip():
    spec = get_quant("fp8")
    x = jnp.asarray(np.random.default_rng(2).normal(size=(8, 16)), jnp.float32)
    q, s = quantize(spec, x)
    back = np.asarray(dequantize(spec, q, s, jnp.float32))
    np.testing.assert_allclose(back, np.asarray(x), rtol=0.13, atol=1e-3)


# ---------------------------------------------------------------------------
# discovery
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch,n_paged", [("qwen2_1_5b", 2), ("minicpm3_4b", 2),
                                          ("flare_lm", 0), ("rwkv6_3b", 0)])
def test_token_axis_discovery(arch, n_paged):
    """gqa pages k/v, mla pages the compressed latents; FLARE's O(M) stream
    state and rwkv recurrences have no token axis and stay dense."""
    model, _ = _model(arch)
    pc = PagedModelCache(model.init_caches, 32, pool_tokens=32, block=8)
    assert len(pc.spec.paged) == n_paged
    for meta in pc.spec.paged:
        assert meta.view == 32
    if n_paged:
        assert pc.token_bytes_paged() > 0


# ---------------------------------------------------------------------------
# engine parity: paged == dense, bit-identical under lossless storage
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", [
    "qwen2_1_5b", "minicpm3_4b", "flare_lm",
    pytest.param("zamba2_7b", marks=pytest.mark.slow),  # hybrid: paged KV +
    pytest.param("rwkv6_3b", marks=pytest.mark.slow),   # dense mamba/rwkv state
])
def test_paged_engine_bit_identical(arch):
    """Greedy decode through the block-paged pool is bit-identical to the
    dense pool (quant='none'), across retire/admit churn and block-boundary
    crossings; retirement returns every page."""
    model, params = _model(arch)
    reqs = _requests(model.cfg.vocab, n=5)
    dense = ServeEngine(model, params, capacity=32, slots=2)
    paged = ServeEngine(model, params, capacity=32, slots=2,
                        pool_tokens=96, block_size=8)
    for prompt, mn in reqs:
        dense.submit(prompt, max_new_tokens=mn)
        paged.submit(prompt, max_new_tokens=mn)
    out_d, out_p = dense.run_all(), paged.run_all()
    for i, (a, b) in enumerate(zip(out_d, out_p)):
        assert a.tolist() == b.tolist(), f"request {i} diverged"
    if paged._has_paged:
        assert paged.stats["pool"]["pages_appended"] > 0  # decode crossed boundaries
        st = paged.stats["pool"]
        assert st["blocks_free"] == st["blocks_total"]  # all pages returned
        assert st["blocks_reserved"] == 0


@pytest.mark.parametrize("arch", [
    "qwen2_1_5b", "minicpm3_4b",
    pytest.param("zamba2_7b", marks=pytest.mark.slow),  # stacked-lead leaves
])
def test_kernel_decode_bit_identical(arch):
    """decode_backend='paged' (Pallas gather-decode kernel reading block
    storage in place) is bit-identical to decode_backend='gather' (jnp dense
    gather) under quant='none' greedy decode, and both match the dense
    engine — the fused route changes where the read runs, not what it
    computes. Every engine's stats report the resolved decode backend."""
    model, params = _model(arch)
    reqs = _requests(model.cfg.vocab, n=5)
    engines = {
        "dense": ServeEngine(model, params, capacity=32, slots=2),
        "gather": ServeEngine(model, params, capacity=32, slots=2,
                              pool_tokens=96, block_size=8,
                              decode_backend="gather"),
        "kernel": ServeEngine(model, params, capacity=32, slots=2,
                              pool_tokens=96, block_size=8,
                              decode_backend="paged"),
    }
    for prompt, mn in reqs:
        for eng in engines.values():
            eng.submit(prompt, max_new_tokens=mn)
    outs = {name: eng.run_all() for name, eng in engines.items()}
    for i in range(len(reqs)):
        assert outs["gather"][i].tolist() == outs["dense"][i].tolist(), \
            f"request {i}: gather route diverged"
        assert outs["kernel"][i].tolist() == outs["dense"][i].tolist(), \
            f"request {i}: kernel route diverged"
    assert engines["dense"].stats["decode_backend"] == "dense"
    assert engines["gather"].stats["decode_backend"] == "paged-gather"
    assert engines["kernel"].stats["decode_backend"].startswith("paged(")
    # fused step: token ids are the only per-step device->host transfer
    assert engines["kernel"].stats["sample_host_syncs"] == 0
    st = engines["kernel"].stats["pool"]
    assert st["blocks_free"] == st["blocks_total"]  # kernel writeback leaks nothing


def test_kernel_decode_flare_falls_back():
    """flare_lm decode state is fixed-size latents — no paged token leaves,
    so 'auto' resolves to the dense step and forcing 'paged' fails loudly."""
    model, params = _model("flare_lm")
    eng = ServeEngine(model, params, capacity=32, slots=2,
                      pool_tokens=96, block_size=8)
    for prompt, mn in _requests(model.cfg.vocab, n=3):
        eng.submit(prompt, max_new_tokens=mn)
    eng.run_all()
    assert eng.stats["decode_backend"] == "dense"
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(model, params, capacity=32, slots=2,
                    pool_tokens=96, block_size=8, decode_backend="paged")


def test_paged_int8_logits_rtol():
    """int8 storage: first-decode-step logits stay within the quantization
    error envelope of the dense pool (measured ~0.05 absolute on the smoke
    configs; bound set to 3x that)."""
    model, params = _model("qwen2_1_5b")
    reqs = _requests(model.cfg.vocab, n=3, lo=6)
    captured = {}
    for name, kw in (("dense", {}),
                     ("int8", dict(pool_tokens=96, block_size=8,
                                   kv_quant="int8"))):
        eng = ServeEngine(model, params, capacity=32, slots=2, **kw)
        for prompt, mn in reqs:
            eng.submit(prompt, max_new_tokens=mn)
        eng.step()  # admit + one decode step across the pool
        captured[name] = np.asarray(eng.last_logits)  # device stash, [S, V]
        eng.run_all()
    np.testing.assert_allclose(captured["int8"], captured["dense"],
                               atol=0.15, rtol=0.05)


def test_oom_admission_backpressure():
    """A pool smaller than the aggregate working set throttles admission
    (peak concurrency < slots) but every request still completes, and the
    pool drains back to fully free."""
    model, params = _model("qwen2_1_5b")
    reqs = _requests(model.cfg.vocab, n=6)
    dense = ServeEngine(model, params, capacity=32, slots=3)
    tiny = ServeEngine(model, params, capacity=32, slots=3,
                       pool_tokens=32, block_size=8)
    for prompt, mn in reqs:
        dense.submit(prompt, max_new_tokens=mn)
        tiny.submit(prompt, max_new_tokens=mn)
    out_d, out_t = dense.run_all(), tiny.run_all()
    assert tiny.stats["finished"] == len(reqs)
    assert tiny.stats["admitted_peak"] < 3  # tokens, not slots, gated entry
    for a, b in zip(out_d, out_t):
        assert a.tolist() == b.tolist()
    st = tiny.stats["pool"]
    assert st["blocks_free"] == st["blocks_total"]


def test_paged_needs_family_prefill():
    """The paged insert feeds block storage from the RAW family prefill; a
    model shipping only prefill_into fails at construction with a clear
    error, not an opaque trace-time crash on first admission."""
    import dataclasses

    model, params = _model("qwen2_1_5b")
    nopre = dataclasses.replace(model, prefill=None)
    with pytest.raises(ValueError, match="model.prefill"):
        ServeEngine(nopre, params, capacity=32, slots=2,
                    pool_tokens=64, block_size=8)
    # the dense engine keeps serving prefill_into-only models
    ServeEngine(nopre, params, capacity=32, slots=2)


def test_impossible_request_rejected_loudly():
    model, params = _model("qwen2_1_5b")
    eng = ServeEngine(model, params, capacity=64, slots=2,
                      pool_tokens=16, block_size=8)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(np.arange(20, dtype=np.int32) % model.cfg.vocab,
                   max_new_tokens=40)


def test_admitted_concurrency_2x_at_fixed_bytes():
    """The acceptance claim behind the BENCH_pr5 paged row: at the byte
    budget of a 2-slot dense pool, the (int8, block-paged) pool admits at
    least 2x the concurrent slots on short-request traffic."""
    model, params = _model("qwen2_1_5b")
    cap, dense_slots = 64, 2
    acct = PagedModelCache(model.init_caches, cap, pool_tokens=8, block=8,
                           quant="int8")
    budget = dense_slots * cap * acct.token_bytes_dense()
    pool_tokens = int(budget // acct.token_bytes_paged()) // 8 * 8
    reqs = _requests(model.cfg.vocab, n=8, lo=4, hi=9)
    dense = ServeEngine(model, params, capacity=cap, slots=dense_slots)
    paged = ServeEngine(model, params, capacity=cap, slots=8,
                        pool_tokens=pool_tokens, block_size=8, kv_quant="int8")
    for prompt, mn in reqs:
        dense.submit(prompt, max_new_tokens=mn)
        paged.submit(prompt, max_new_tokens=mn)
    dense.run_all(), paged.run_all()
    assert paged.stats["admitted_peak"] >= 2 * dense.stats["admitted_peak"], (
        paged.stats["admitted_peak"], dense.stats["admitted_peak"])


def test_block_boundary_appends():
    """Decode across block boundaries maps pages lazily: prompt 5 + 10 new
    tokens on block=4 crosses at positions 8 and 12."""
    model, params = _model("qwen2_1_5b")
    eng = ServeEngine(model, params, capacity=32, slots=1,
                      pool_tokens=32, block_size=4)
    eng.submit(np.arange(5, dtype=np.int32) % model.cfg.vocab,
               max_new_tokens=10)
    eng.run_all()
    assert eng.stats["pool"]["pages_appended"] >= 2


# ---------------------------------------------------------------------------
# coalesced prefill + legacy compat (engine satellites)
# ---------------------------------------------------------------------------


def test_coalesced_prefill_counts_and_determinism():
    model, params = _model("qwen2_1_5b")
    reqs = _requests(model.cfg.vocab, n=6, lo=3, hi=8)  # one shared bucket

    def run():
        eng = ServeEngine(model, params, capacity=32, slots=3,
                          coalesce_prefill=True)
        for prompt, mn in reqs:
            eng.submit(prompt, max_new_tokens=mn)
        return [o.tolist() for o in eng.run_all()], eng.stats

    outs1, stats1 = run()
    outs2, stats2 = run()
    assert stats1["coalesced_prefills"] >= 1  # >=2 same-bucket admissions
    assert stats1["finished"] == len(reqs)
    assert outs1 == outs2  # coalescing stays deterministic


def test_coalesced_prefill_on_paged_pool():
    model, params = _model("qwen2_1_5b")
    reqs = _requests(model.cfg.vocab, n=6, lo=3, hi=8)
    eng = ServeEngine(model, params, capacity=32, slots=3, pool_tokens=128,
                      block_size=8, coalesce_prefill=True)
    for prompt, mn in reqs:
        eng.submit(prompt, max_new_tokens=mn)
    eng.run_all()
    assert eng.stats["coalesced_prefills"] >= 1
    assert eng.stats["finished"] == len(reqs)
    st = eng.stats["pool"]
    assert st["blocks_free"] == st["blocks_total"]


def test_legacy_prefill_compat_warns_and_serves():
    """A model exposing only the legacy full-batch `prefill` still serves,
    through the deprecated compat adapter the warning text points past."""
    import dataclasses

    model, params = _model("qwen2_1_5b")
    legacy = dataclasses.replace(model, prefill_into=None)
    with pytest.warns(DeprecationWarning, match="prefill_into"):
        eng = ServeEngine(legacy, params, capacity=32, slots=2)
    ref = ServeEngine(model, params, capacity=32, slots=2)
    prompt = np.arange(6, dtype=np.int32) % model.cfg.vocab
    eng.submit(prompt, max_new_tokens=5)
    ref.submit(prompt, max_new_tokens=5)
    assert eng.run_all()[0].tolist() == ref.run_all()[0].tolist()


# ---------------------------------------------------------------------------
# the gather-decode kernel + `paged` mixer backend
# ---------------------------------------------------------------------------


def test_paged_attention_kernel_matches_oracle():
    from repro.kernels.paged_attention import paged_attention, paged_attention_ref

    rng = np.random.default_rng(0)
    nb, block, h, d = 9, 8, 2, 16
    b, g, p = 3, 4, 4
    k = jnp.asarray(rng.normal(size=(nb, h, block, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(nb, h, block, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(b, h, g, d)), jnp.float32)
    pt = jnp.asarray(rng.integers(0, nb, (b, p)), jnp.int32)  # incl. trash row
    lengths = jnp.asarray([0, 13, 32], jnp.int32)  # empty lane, partial page
    out = paged_attention(q, k, v, pt, lengths, scale=0.5)
    ref = paged_attention_ref(q, k, v, pt, lengths, scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    assert np.all(np.asarray(out[0]) == 0)  # zero-length lane


def test_paged_attention_single_query_decode_shape():
    """G=1 is the gqa/mla decode-read case the serve pool targets."""
    from repro.kernels.paged_attention import paged_attention, paged_attention_ref

    rng = np.random.default_rng(1)
    k = jnp.asarray(rng.normal(size=(5, 2, 4, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(5, 2, 4, 8)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(2, 2, 1, 8)), jnp.float32)
    pt = jnp.asarray([[0, 1], [2, 3]], jnp.int32)
    lengths = jnp.asarray([7, 3], jnp.int32)
    out = paged_attention(q, k, v, pt, lengths, scale=1.0)
    ref = paged_attention_ref(q, k, v, pt, lengths, scale=1.0)
    assert out.shape == (2, 2, 1, 8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-6)


def test_paged_backend_registered_and_matches_sdpa():
    from repro.core.dispatch import MixerPlan, backends, get_backend
    from repro.core.flare import flare_mixer
    from repro.core.policy import MixerPolicy

    assert any(b.name == "paged" for b in backends())
    b = get_backend("paged")
    assert b.caps.bidirectional and not b.caps.causal and not b.caps.grads
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, 8, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, 19, 16)), jnp.float32)  # odd N pads
    v = jnp.asarray(rng.normal(size=(2, 2, 19, 16)), jnp.float32)
    paged = flare_mixer(q, k, v, policy=MixerPolicy(backends=("paged",)))
    ref = flare_mixer(q, k, v, policy=MixerPlan("sdpa"))
    np.testing.assert_allclose(np.asarray(paged), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_paged_backend_resolves_by_policy_not_grad():
    from repro.core.dispatch import MixerShape
    from repro.core.policy import MixerPolicy, resolve_policy

    shape = MixerShape(batch=1, heads=2, tokens=64, latents=8, head_dim=16)
    plan = resolve_policy(MixerPolicy(backends=("paged",)), shape, jnp.float32)
    assert plan.backend == "paged" and "block" in plan.params
    with pytest.raises(ValueError, match="forward-only"):
        resolve_policy(MixerPolicy(backends=("paged",), requires_grad=True),
                       shape, jnp.float32)
