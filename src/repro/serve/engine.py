"""Continuous-batching serving engine: slot-pool state caches, per-request
insertion prefill, retire-and-admit decode loop (DESIGN.md §4).

The engine owns a **fixed pool of `slots` cache lanes** allocated once and
persisting across its lifetime. Requests are prefilled (prompt right-padded
to a power-of-two bucket, true length carried in ``batch["lengths"]`` so
padding never enters the caches) and *inserted* into a free slot; every
decode step advances all slots at once (static shapes, one compiled step
function) and finished sequences retire immediately — their slot is reset
and handed to the next queued request on the very next step.

Two pool layouts (DESIGN.md §4):

  - **dense** (default): ``model.init_caches(slots, capacity)`` — every
    slot's KV/stream cache at the full capacity. Pool memory scales as
    slots x capacity.
  - **paged** (``pool_tokens=...``): token-axis leaves live in
    block-granular, optionally int8/fp8-quantized storage sized in TOKENS
    (`serve.pool`); a request is admitted only when the allocator can stake
    its worst-case page count (its prompt bucket is mapped immediately,
    further pages are appended as decode crosses block boundaries), and
    retirement returns its pages to the free list. Decode reads route
    through the ``serve.pool.views.PagedCacheView`` adapter handed to the
    unchanged ``model.decode_step``. Admission backpressure is therefore in
    tokens, not slots — the gqa/mla concurrency fix.

**Fused decode step** (DESIGN.md §4): a decode step is ONE compiled device
program — model decode (through the kernel-backed paged view when the
engine's MixerPolicy resolution picks the ``paged`` backend for the pool's
decode-read shape) plus on-device sampling — returning int32 token ids;
the only per-step host<->device traffic is the fed tokens and the sampled
ids. ``decode_backend=`` pins the route ("paged" forces the Pallas kernel,
"gather" the jnp dense-gather view, "auto" resolves).

**Slot-sharded pool** (``mesh=...``, DESIGN.md §15): with a device mesh
the paged pool's block storage, page tables and per-slot dense leaves
shard over the flattened mesh — slot ``s`` lives entirely on shard
``s // (slots/shards)``, with a per-shard allocator and a per-shard trash
sink. The fused decode step runs under ``shard_map`` (each device decodes
its own slots against its local storage partition; the resolved decode
plan is the ``paged_shard`` backend) and all-gathers the sampled token
ids — the ONLY cross-shard communication per step. Everything host-side
stays global and unchanged: admission, the FIFO scheduler, prefix
matching (against the target slot's shard at the gate), COW, and the
plain-jit prefill, which addresses the one global storage array.

Scheduling (FIFO admission with an optional block-availability gate, free
list, deadlines, latency percentiles) is `serve.scheduler.SlotScheduler`.
Compilation is bounded: prompt buckets are powers of two and decode is a
single specialization; ``stats["prefill_compiles"]`` counts the distinct
(bucket, lanes) prefill variants traced, ``stats["decode_compiles"]`` the
decode-step traces, and :meth:`ServeEngine.warmup` front-loads all of them
(keyed on (bucket, lanes), the MaxText offline-inference idiom) so steady
state never recompiles.

Prefill coalescing (``coalesce_prefill=True``): admissions that share a
bucket in the same scheduling cycle run as ONE batched prefill launch
(``stats["coalesced_prefills"]``). Off by default: batching changes XLA's
bf16 reduction grouping, so coalesced lanes are no longer bit-identical to
a solo run — the default preserves the pinned greedy-parity contract;
throughput-oriented callers (launch/serve.py --coalesce, bench_serve)
opt in.

Sampling: greedy or temperature (deterministic per-engine seed). Greedy
outputs are bit-identical to a solo run of each request on the same engine
geometry — for the paged pool too, storage permitting (``kv_quant="none"``;
int8/fp8 trade exactness for ~2-4x more resident tokens) — pinned by
tests/test_serve_continuous.py and tests/test_paged_pool.py.

Observability (DESIGN.md §16): the engine owns a per-engine
:class:`repro.obs.metrics.MetricsRegistry` (shared with its scheduler and
allocators) and an optional :class:`repro.obs.trace.Tracer`. Every span is
recorded host-side from timestamps the stats bookkeeping already takes —
enqueue/admit/retire instants, prefill launches, per-N decode-step
aggregates — so tracing adds no device work and no host<->device syncs:
``host_syncs_per_step`` stays 0.0 and greedy outputs stay bit-identical
with tracing on (pinned by tests/test_obs.py, asserted by scripts/ci.sh).
"""
from __future__ import annotations

import dataclasses
import os
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import annotate, scope
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER, TID_ENGINE
from repro.serve.cache import ModelSlotCache
from repro.serve.pool.blocks import chain_hashes
from repro.serve.scheduler import ServeRequest, SlotScheduler


@dataclasses.dataclass
class Request:
    """Legacy submit record (kept for API compatibility)."""
    prompt: np.ndarray           # [S] int32
    max_new_tokens: int = 32
    eos_id: int = -1             # -1: never stops early


class ServeEngine:
    def __init__(self, model, params, *, capacity: int = 512, slots: int = 8,
                 temperature: float = 0.0, seed: int = 0, min_bucket: int = 8,
                 pool_tokens: Optional[int] = None, kv_quant: str = "none",
                 block_size: int = 16, coalesce_prefill: bool = False,
                 sample: str = "greedy", top_k: int = 0,
                 decode_backend: str = "auto", prefix_cache: bool = False,
                 mesh=None, tracer=None, metrics=None):
        if decode_backend not in ("auto", "paged", "gather"):
            raise ValueError(f"unknown decode_backend {decode_backend!r} "
                             "(auto | paged | gather)")
        # observability (DESIGN.md §16): a per-engine registry (shared with
        # the scheduler and the allocators, so their counters land in one
        # place) and an optional span tracer; the defaults — a live private
        # registry, the disabled null tracer — keep uninstrumented engines
        # paying one enabled-check per event and nothing else
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.mesh = mesh
        self._shards = 1
        if mesh is not None:
            if pool_tokens is None:
                raise ValueError(
                    "mesh=... needs the paged pool (pool_tokens=...) — slot "
                    "sharding partitions block storage (DESIGN.md §15)")
            for a in mesh.axis_names:
                self._shards *= int(mesh.shape[a])
            if slots % self._shards:
                raise ValueError(f"slots={slots} not divisible by mesh size "
                                 f"{self._shards}")
        self._slots_per_shard = slots // self._shards
        prefill_into = model.prefill_into
        if prefill_into is None and model.prefill is not None \
                and model.init_caches is not None:
            # legacy compat: a model that ships only the full-batch `prefill`
            # contract still serves, through the generic scatter adapter
            warnings.warn(
                f"{model.cfg.name}: model has no prefill_into — falling back "
                "to the legacy full-prefill + slot-scatter compat path; "
                "expose prefill_into (models.api.make_prefill_into) instead "
                "(DESIGN.md §4)", DeprecationWarning, stacklevel=2)
            from repro.models.api import make_prefill_into

            prefill_into = make_prefill_into(model.prefill, model.init_caches)
        if prefill_into is None or model.init_caches is None:
            raise ValueError(
                f"{model.cfg.name} (family={model.cfg.family}) has no slot-pool "
                "serving path (needs init_caches + prefill_into or prefill)")
        self.model = model
        self.params = params
        self.capacity = capacity
        self.slots = slots
        self.temperature = temperature
        self.sample_mode = sample
        self.top_k = top_k
        self.min_bucket = min_bucket
        self.coalesce = coalesce_prefill
        self.key = jax.random.PRNGKey(seed)
        from repro.serve.sampling import make_sampler

        self._sampler, self._needs_key = make_sampler(temperature, sample, top_k)
        self._sample_dev = jax.jit(self._sampler)  # prefill logits sampler

        self.paged = pool_tokens is not None
        if self.paged:
            from repro.serve.pool import PagedModelCache

            if model.prefill is None:
                # the paged insert needs the RAW family prefill (its token
                # leaves go to block storage, not slot lanes) — the
                # prefill_into adapter alone cannot feed a paged pool
                raise ValueError(
                    f"{model.cfg.name}: the paged pool (pool_tokens=...) "
                    "needs the family prefill contract (model.prefill)")
            self.block = block_size
            self.slot_cache = PagedModelCache(
                model.init_caches, capacity, pool_tokens=pool_tokens,
                block=block_size, quant=kv_quant, shards=self._shards)
            self._has_paged = bool(self.slot_cache.spec.paged)
            if self._shards > 1 and not self._has_paged:
                raise ValueError(
                    f"{model.cfg.name}: slot sharding (mesh=...) needs "
                    "token-paged leaves; this family's state is all-dense "
                    "(already O(1) in capacity) — serve it unsharded")
            # one allocator PER SHARD (shard-local ids; shards=1 == the
            # historical single global allocator, bit-for-bit)
            self._allocs = [self.slot_cache.allocator()
                            for _ in range(self._shards)]
            for a in self._allocs:
                # shards share the metric handles (get-or-create), so the
                # counters read as pool-wide sums
                a.bind_metrics(self.metrics)
            self.alloc = self._allocs[0]
            self.pool = self.slot_cache.init(slots)
            self._pool_specs = None
            if self._shards > 1:
                from repro.distributed.sharding import shard_slot_pool

                self._pool_specs = self.slot_cache.pool_pspecs(
                    tuple(mesh.axis_names))
                self.pool = shard_slot_pool(self.pool, mesh, self._pool_specs)
            self._pt = np.empty((slots, self.slot_cache.max_pages), np.int32)
            for s in range(slots):
                self._pt[s] = self._trash_of(s)
            self._pt_dev = jnp.asarray(self._pt)  # device mirror, re-uploaded
            self._pt_dirty = False                # only when the table changed
            self._lengths = np.zeros(slots, np.int64)
            self._leases: dict = {}
            self._const_view_args = (self._pt_dev, jnp.zeros(slots, jnp.int32))
            self._prefill_into = jax.jit(
                self.slot_cache.make_prefill_into(model.prefill))
            # prefix caching (DESIGN.md §4 "Prefix cache"): needs paged
            # token leaves AND a family suffix-prefill path (unwindowed
            # gqa/mla); silently off otherwise so the flag is safe to pass
            # for any arch (flare/rwkv stay cold-path, hit rate 0)
            self._prefix_enabled = bool(
                prefix_cache and self._has_paged
                and getattr(model, "prefill_suffix", None) is not None)
            if self._prefix_enabled:
                self._prefill_suffix = jax.jit(
                    self.slot_cache.make_prefill_suffix(model.prefill_suffix))
                self._copy_block = jax.jit(self.slot_cache.copy_block)
        else:
            self.slot_cache = ModelSlotCache(model.init_caches, capacity)
            self.pool = self.slot_cache.init(slots)
            self._prefix_enabled = False
            self._prefill_into = jax.jit(
                lambda p, b, c, s: prefill_into(p, b, c, s, capacity=capacity))
        self._reset_slot = jax.jit(self.slot_cache.reset)
        self._decode_backend_opt = decode_backend
        self._decode_plan = None
        if self.paged and self._has_paged and decode_backend != "gather":
            self._decode_plan = self._resolve_decode_plan()
        if decode_backend == "paged" and self._decode_plan is None:
            raise ValueError(
                f"{model.cfg.name}: decode_backend='paged' but the paged "
                "kernel route is not eligible (no paged token leaves, or "
                "leaf shapes / backend contract reject the kernel)")
        if self.paged:
            spec = self.slot_cache.spec
            self._view_spec = (dataclasses.replace(spec, kernel=True)
                               if self._decode_plan is not None else spec)
        self._decode_compiles = 0
        self._decode_step = jax.jit(self._make_decode_step())

        self.sched = SlotScheduler(slots, registry=self.metrics)
        self._match_on_admit = True
        # queued requests can hold prefix refcounts from enqueue-time
        # matching; a deadline drop must hand them back — and every drop
        # is an "expire" trace instant. _on_drop guards the prefix part,
        # so the always-installed hook is safe for dense engines too.
        self.sched.on_drop = self._on_drop
        self._pins: list = []            # blocks held alive by pin_prefix
        # REPRO_SANITIZE=1: cross-check allocator/page-table/lease state at
        # every admission and retirement (DESIGN.md §14) — debug tax, off by
        # default; test fixtures call check_invariants() directly instead
        self._sanitize = os.environ.get("REPRO_SANITIZE", "0") not in ("", "0")
        self._prefix_hit_tokens = 0      # prompt tokens NOT re-prefilled
        self._prefix_prompt_tokens = 0   # prompt tokens admitted (hit + cold)
        self._cow_copies = 0
        m = self.metrics
        self._m_prefill_s = m.histogram(
            "engine.prefill_s", "wall seconds per prefill launch")
        self._m_step_s = m.histogram(
            "engine.decode_step_s", "wall seconds per fused decode step")
        self._m_tokens_out = m.counter(
            "engine.tokens_out", "generated tokens on retired requests")
        self._m_cow = m.counter(
            "engine.cow_copies", "copy-on-write block copies")
        self._m_hit_tokens = m.counter(
            "engine.prefix_hit_tokens",
            "prompt tokens served from the prefix cache")
        self._m_g_prefill_compiles = m.gauge(
            "engine.prefill_compiles",
            "distinct (bucket, lanes) prefill program variants traced")
        self._m_g_decode_compiles = m.gauge(
            "engine.decode_compiles", "fused decode-step traces")
        # decode-step trace aggregation window: ONE "decode" span per
        # _trace_every steps (flushed early at pool idle), never per step —
        # the tracer's cost on the hot loop stays O(1/N) appends and the
        # span stream stays readable at long generations
        self._trace_every = 16
        self._win_t0: Optional[float] = None
        self._win_end = 0.0
        self._win_steps = 0
        self._win_toks = 0
        self.tracer.set_track_name(TID_ENGINE, "engine")
        for s in range(slots):
            self.tracer.set_track_name(s + 1, f"slot{s}")
        self._next_rid = 0
        self._cur_tok = np.zeros(slots, np.int32)  # next token fed per slot
        self._buckets_used: set = set()            # (bucket, lanes) traced
        self.last_logits = None  # device-side stash of the last step's logits
        self.stats = {
            "requests": 0, "tokens_generated": 0, "prefill_s": 0.0,
            "decode_s": 0.0, "decode_steps": 0, "prefill_compiles": 0,
            "slot_utilization": 0.0, "coalesced_prefills": 0,
            "admitted_peak": 0, "mixer_backend": self._mixer_backend(),
            "cache": self.slot_cache.describe(),
            "decode_backend": self._describe_decode_backend(),
            "decode_compiles": 0, "warmup_compiles": 0, "warmup_s": 0.0,
            "sample_host_syncs": 0, "host_syncs_per_step": 0.0,
            "prefix_cache": self._prefix_enabled,
            "prefix_hit_rate": 0.0, "shared_pages": 0, "cow_copies": 0,
            "shards": self._shards, "mesh_shape": self._mesh_shape(),
        }

    def _mesh_shape(self) -> Optional[str]:
        if self.mesh is None:
            return None
        from repro.backends.packed_shard import mesh_shape_tag

        return mesh_shape_tag(self.mesh)

    # ------------------------------------------------------------------
    # slot -> shard bookkeeping (DESIGN.md §15; all identity when shards=1)
    # ------------------------------------------------------------------
    def _shard_of(self, slot: int) -> int:
        return slot // self._slots_per_shard

    def _alloc_for(self, slot: int):
        return self._allocs[self._shard_of(slot)]

    def _goff(self, shard: int) -> int:
        """Global storage row of the shard's local block 0 (page tables
        store global ids; allocators speak shard-local ones)."""
        return self.slot_cache.global_offset(shard)

    def _trash_of(self, slot: int) -> int:
        return self.slot_cache.trash_row(self._shard_of(slot))

    def _repin(self) -> None:
        """Re-pin the pool onto its slot sharding after a plain-jit mutation
        (prefill, COW, reset) so the shard_map'd decode step always sees its
        canonical input shardings — no-op placement when already correct,
        and a no-op entirely when unsharded."""
        if self._shards > 1:
            from repro.distributed.sharding import shard_slot_pool

            self.pool = shard_slot_pool(self.pool, self.mesh, self._pool_specs)

    # ------------------------------------------------------------------
    # the fused decode step (DESIGN.md §4 "Fused decode step")
    # ------------------------------------------------------------------
    def _resolve_decode_plan(self):
        """MixerPolicy resolution for the pool's decode-read shape. The
        shape has ``latents=1`` — one query row per head over the token
        axis, the decode-read signature only serving produces — which the
        ``paged`` backend scores far above every dense backend, so "auto"
        routes kernel-shaped pools through it. Returns the resolved plan
        (annotated with the pool's block/quant) or None when the kernel
        route is not eligible (odd leaf shapes, contract failure) — the
        jnp gather view stays as the fallback."""
        spec = self.slot_cache.spec
        tails = []
        for j, meta in enumerate(spec.paged):
            shape = self.pool["data"][j].shape       # [rows, *mid, block, D]
            tail = (shape[1:-2] + shape[-1:])[meta.lead:]
            if len(tail) not in (1, 2):
                return None  # no [H, block, D] kernel layout for this leaf
            tails.append(tail)
        from repro.core.dispatch import MixerPlan, MixerShape
        from repro.core.policy import MixerPolicy, resolve_policy

        shape = MixerShape(
            batch=self.slots,
            heads=max(t[0] if len(t) == 2 else 1 for t in tails),
            tokens=self.capacity, latents=1,
            head_dim=max(t[-1] for t in tails))
        want = "paged_shard" if self._shards > 1 else "paged"
        policy = (MixerPolicy(backends=(want,))
                  if self._decode_backend_opt == "paged" else MixerPolicy())
        try:
            plan = resolve_policy(policy, shape,
                                  jnp.dtype(spec.paged[0].dtype), causal=False,
                                  mesh=self.mesh if self._shards > 1 else None)
        except Exception:
            return None
        if plan.backend not in ("paged", "paged_shard"):
            return None
        return MixerPlan(plan.backend, {**plan.params, "block": spec.block,
                                        "quant": spec.quant.name})

    def _describe_decode_backend(self) -> str:
        """The decode-step route, recorded per bench row (the satellite fix
        for BENCH rows carrying backend: None)."""
        if not self.paged:
            return "dense"
        if self._decode_plan is not None:
            return self._decode_plan.describe()
        return "paged-gather" if self._has_paged else "dense"

    def _make_decode_step(self):
        """Build the fused step: model decode + on-device sampling in ONE
        compiled program returning (tokens int32[S], logits, pool). The
        host sees only the sampled ids — no per-token logits round-trip.
        The python body runs once per signature, so counting its calls
        counts compiles (``stats["decode_compiles"]``)."""
        if self.paged and self._shards > 1:
            return self._make_decode_step_sharded()
        if self.paged:
            spec = self._view_spec

            def _fused(params, toks, pool, pt, write_pos, key):
                from repro.serve.pool import PagedCacheView

                self._decode_compiles += 1  # trace-time only
                # named_scope is trace-time jaxpr/HLO metadata (the ONE obs
                # construct legal inside jitted code — OB001): XLA profiles
                # show the decode/sample split under these names
                view = PagedCacheView(pool, pt, write_pos, spec)
                with scope("serve.decode"):
                    logits, out = self.model.decode_step(params, toks, view)
                with scope("serve.sample"):
                    tok = self._sampler(logits, key)
                return tok, logits, out.pool
        else:

            def _fused(params, toks, pool, key):
                self._decode_compiles += 1  # trace-time only
                with scope("serve.decode"):
                    logits, new_pool = self.model.decode_step(params, toks, pool)
                with scope("serve.sample"):
                    tok = self._sampler(logits, key)
                return tok, logits, new_pool

        return _fused

    def _make_decode_step_sharded(self):
        """The fused step under ``shard_map`` (DESIGN.md §15): every device
        decodes its own slots against its LOCAL storage partition — page
        tables arrive global and are localized by subtracting the shard's
        row offset — then samples on device and all-gathers the token ids
        (and logits, for ``last_logits``) back to global slot order. That
        gather is the step's only cross-shard communication; pool state
        goes in sharded and comes out sharded, untouched by any collective."""
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from repro.serve.pool import PagedCacheView

        spec = self._view_spec
        mesh = self.mesh
        names = tuple(mesh.axis_names)
        el = names[0] if len(names) == 1 else names
        rows = self.slot_cache.shard_blocks + 1  # per-shard rows incl. trash

        def _body(params, toks, pool, pt, write_pos, key):
            self._decode_compiles += 1  # trace-time only
            idx = None  # flattened shard index, row-major over mesh axes
            for name in names:
                ax = lax.axis_index(name)
                idx = ax if idx is None else idx * mesh.shape[name] + ax
            view = PagedCacheView(pool, pt - idx * rows, write_pos, spec)
            with scope("serve.decode"):
                logits, out = self.model.decode_step(params, toks, view)
            with scope("serve.sample"):
                tok = self._sampler(logits, key)
            # the ONE cross-shard sync of the step: host-visible outputs
            # gather to global slot order (innermost mesh axis first keeps
            # the flattened-shard-index contiguity of the slot layout)
            for name in reversed(names):
                tok = lax.all_gather(tok, name, axis=0, tiled=True)
                logits = lax.all_gather(logits, name, axis=0, tiled=True)
            return tok, logits, out.pool

        return jax.shard_map(
            _body, mesh=mesh,
            in_specs=(P(), P(el), self._pool_specs, P(el), P(el), P()),
            out_specs=(P(), P(), self._pool_specs),
            check_vma=False)  # no replication rule exists for pallas_call

    def _next_key(self) -> jax.Array:
        """Per-sampling-call PRNG key: split exactly like the legacy host
        ``_sample`` so stochastic runs stay reproducible (and comparable)
        across the host/device paths. Greedy consumes no entropy."""
        if self._needs_key:
            self.key, sub = jax.random.split(self.key)
            return sub
        return self.key

    def _mixer_backend(self) -> Optional[str]:
        """The FLARE plan get_model resolved at build (for observability in
        serving stats) — not a re-derivation. None for non-FLARE mixers.
        NB: this is the *full-sequence* (forward/loss) plan; the flare_lm
        prefill/decode loop itself is pinned to the stateful streaming path
        (stream state must survive into decode), which is the causal_stream
        recurrence regardless of plan."""
        try:
            plans = getattr(self.model, "plans", None) or {}
            plan = plans.get("infer") or plans.get("train")
            return plan.describe() if plan is not None else None
        except Exception:  # pragma: no cover — stats must never break serving
            return None

    # ------------------------------------------------------------------
    # queueing
    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 32, eos_id: int = -1,
               deadline_s: Optional[float] = None, on_token=None) -> int:
        """Queue a request; returns its request id. ``on_token`` streams each
        generated token as ``on_token(rid, token)`` the step it is sampled."""
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim != 1 or prompt.size < 1:
            raise ValueError("prompt must be a non-empty 1-D token array")
        if prompt.size > self.capacity:
            # loud rather than silently evicting from a capacity-bounded KV
            # pool mid-prefill; capacity is the engine's context budget
            raise ValueError(f"prompt length {prompt.size} exceeds engine "
                             f"capacity {self.capacity}")
        holds: list = []
        holds_shard = None
        walk = None
        if self.paged and self._has_paged:
            if (self._prefix_enabled and self._shards == 1
                    and prompt.size + max_new_tokens <= self.capacity):
                # enqueue-time matching: walk the content index now so the
                # blocks stay alive (refcounted) while the request queues;
                # _can_admit re-walks for blocks registered since. Sharded
                # pools skip this — the target shard is unknown until a slot
                # is in hand, so matching happens at the admission gate
                w0 = time.time() if self.tracer.enabled else 0.0
                holds = self._acquire_prefix(self.alloc, prompt)
                if self.tracer.enabled:
                    walk = (w0, time.time() - w0)
                holds_shard = 0
            # Feasibility is ALWAYS the full-prompt worst case: prefix hits
            # only help admission (suffix-sized stake), never become
            # load-bearing — a dropped hold (deadline, deadlock fallback)
            # must not leave a request that can never stake at the FIFO head
            need = self._need_pages(prompt.size, max_new_tokens)
            if need > self.alloc.num_blocks:
                # would deadlock the FIFO queue: the head could never stake
                # its reservation no matter how much retires
                for b in holds:
                    self.alloc.release_ref(b)
                raise ValueError(
                    f"request needs {need} pages but the pool only has "
                    f"{self.alloc.num_blocks} blocks; raise pool_tokens or "
                    "lower max_new_tokens")
        rid = self._next_rid
        self._next_rid += 1
        now = time.time()
        self.sched.submit(ServeRequest(
            rid=rid, prompt=prompt, max_new_tokens=max_new_tokens,
            eos_id=eos_id, deadline_s=deadline_s, on_token=on_token,
            submit_t=now, prefix_blocks=holds,
            prefix_shard=holds_shard))
        if self.tracer.enabled:
            if walk is not None:
                self.tracer.complete(
                    "prefix_walk", walk[0], walk[1],
                    args={"rid": rid, "hit_blocks": len(holds)})
            self.tracer.instant("enqueue", ts=now,
                                args={"rid": rid,
                                      "prompt_len": int(prompt.size)})
        return rid

    # ------------------------------------------------------------------
    # paged-pool bookkeeping (all host-side; device work stays in pool/)
    # ------------------------------------------------------------------
    def _pages(self, tokens: int) -> int:
        return -(-min(tokens, self.capacity) // self.block)

    def _need_pages(self, prompt_len: int, max_new: int) -> int:
        """A request's worst-case page count: its prompt bucket (mapped at
        admission) or its full decode horizon, whichever is larger. The ONE
        definition submit's feasibility check, the admission gate and the
        actual reservation all share — if they ever disagreed, reserve()
        could raise mid-admission, the OOM the design promises away."""
        return max(self._pages(self._bucket(prompt_len)),
                   self._pages(prompt_len + max_new))

    def _can_admit(self, req: ServeRequest) -> bool:
        """Block-aware admission gate: the allocator must be able to stake
        the request's worst-case page count (prompt bucket now, decode
        appends later — the reservation guarantees appends never OOM).
        Families with no token-axis leaves (flare_lm's O(M) stream state,
        rwkv) need no pages: their concurrency stays slot-bound.

        ``_pending_pages`` accounts for earlier admissions of the SAME
        scheduling cycle, whose reservations are taken only after
        ``sched.admit`` returns — a True here is a commitment.

        With prefix caching the gate first extends the request's hit walk
        (blocks registered since enqueue — e.g. by the donor that just
        prefilled) and then stakes only the distinct suffix's pages: shared
        prefixes directly raise admitted slots."""
        if not self._has_paged:
            return True
        # the scheduler admits into the lowest free slot, so the head of the
        # preview list IS the slot this request gets on a True — which pins
        # the shard whose allocator must stake (and match) it
        shard = self._shard_of(self._free_preview[0])
        if (self._prefix_enabled and self._match_on_admit
                and len(req.prompt) + req.max_new_tokens <= self.capacity):
            if (req.prefix_blocks and req.prefix_shard is not None
                    and req.prefix_shard != shard):
                # holds from an earlier gate attempt reference another
                # shard's blocks — useless for this slot, hand them back
                self._drop_prefix_holds(req)
            req.prefix_shard = shard
            req.prefix_blocks = self._acquire_prefix(
                self._allocs[shard], req.prompt, held=req.prefix_blocks,
                margin=self._pending_pages[shard])
        if req.prefix_blocks:
            offset, slen = self._split_point(req)
            if offset + self._bucket(slen) > self.capacity:
                # the suffix bucket would overrun capacity (clamped write);
                # rare — take the cold path instead of corrupting rows
                self._drop_prefix_holds(req)
        need = self._suffix_need(req)
        if self._allocs[shard].available() - self._pending_pages[shard] < need:
            return False
        self._pending_pages[shard] += need
        self._free_preview.pop(0)
        return True

    def _stake_pages(self, req: ServeRequest, slot: int, bucket: int) -> np.ndarray:
        """Reserve the request's horizon, map its bucket's pages, point the
        slot's page table at them. Returns the mapped ids (for the prefill
        scatter)."""
        self._lengths[slot] = len(req.prompt)
        alloc = self._alloc_for(slot)
        if not self._has_paged:
            self._leases[slot] = alloc.reserve(0)
            return np.zeros(0, np.int32)
        bucket_pages = self._pages(bucket)
        lease = alloc.reserve(
            self._need_pages(len(req.prompt), req.max_new_tokens))
        # allocator ids are shard-local; page tables carry GLOBAL rows
        ids = (np.asarray(alloc.map(lease, bucket_pages), np.int32)
               + self._goff(self._shard_of(slot)))
        self._leases[slot] = lease
        self._pt[slot, :bucket_pages] = ids
        self._pt_dirty = True
        return ids

    # ------------------------------------------------------------------
    # prefix cache (DESIGN.md §4 "Prefix cache")
    # ------------------------------------------------------------------
    def _acquire_prefix(self, alloc, tokens, held=(), margin: int = 0) -> list:
        """Walk the prompt's chain hashes against ``alloc``'s content index
        (the target shard's), taking one reference per hit block (monotone:
        stops at the first miss). ``held`` = blocks this request already
        references (extension re-walk at admission); ``margin`` = pages
        committed to earlier admissions in the same cycle, which a
        cached-free resurrection must not eat."""
        hashes = chain_hashes(tokens, self.block)
        out = list(held)
        for h in hashes[len(out):]:
            b = alloc.lookup(h)
            if b is None or not alloc.acquire(b, margin=margin):
                break
            out.append(b)
        return out

    def _drop_prefix_holds(self, req: ServeRequest) -> None:
        """Release the refcounts a queued request holds from matching —
        the scheduler's on_drop hook (deadline expiry), submit's rejection
        path, and the deadlock fallback all route here."""
        alloc = self._allocs[req.prefix_shard
                             if req.prefix_shard is not None else 0]
        for b in req.prefix_blocks:
            alloc.release_ref(b)
        req.prefix_blocks = []

    def _on_drop(self, req: ServeRequest) -> None:
        """Scheduler drop hook (deadline expiry while still queued): hand
        back any enqueue-time prefix holds, then mark the expiry on the
        trace. Installed unconditionally — the prefix part is guarded, so
        dense/unpaged engines (no ``_allocs``) never touch allocator state."""
        if req.prefix_blocks:
            self._drop_prefix_holds(req)
        self.tracer.instant("expire", ts=req.finish_t,
                            args={"rid": req.rid})

    def _kept_shared(self, req: ServeRequest) -> int:
        """How many of the request's hit blocks stay SHARED in its page
        table. Full coverage (the whole prompt is hit full blocks) keeps
        k-1: the last block is copy-on-written so the recomputed final
        token has a private write target (and supplies first-token logits)."""
        k = len(req.prefix_blocks)
        if k == 0:
            return 0
        return k - 1 if k * self.block >= len(req.prompt) else k

    def _split_point(self, req: ServeRequest):
        """(offset, suffix_len): where recompute starts. Partial coverage
        resumes at the first un-hit block boundary; full coverage recomputes
        only the final token (into its COW'd block)."""
        length = len(req.prompt)
        k = len(req.prefix_blocks)
        if k * self.block >= length:
            return length - 1, 1
        return k * self.block, length - k * self.block

    def _suffix_need(self, req: ServeRequest) -> int:
        """Pages the admission gate must stake: the full horizon minus the
        shared blocks the request keeps — the O(distinct-suffix) admission
        claim. Cold requests fall back to the worst-case `_need_pages`."""
        if not req.prefix_blocks:
            return self._need_pages(len(req.prompt), req.max_new_tokens)
        horizon = self._pages(len(req.prompt) + req.max_new_tokens)
        return horizon - self._kept_shared(req)

    def _register_blocks(self, req: ServeRequest, slot: int) -> None:
        """Content-index the prompt's full blocks once their rows are in
        block storage (host bookkeeping; device ordering is program order).
        Only wrap-free requests register: a sequence that can exceed
        capacity overwrites its low pages in place, which would poison the
        index. Keep-first registration makes concurrent identical prompts
        converge on the first prefiller's blocks."""
        if not self._prefix_enabled:
            return
        if len(req.prompt) + req.max_new_tokens > self.capacity:
            return
        alloc = self._alloc_for(slot)
        goff = self._goff(self._shard_of(slot))
        for i, h in enumerate(chain_hashes(req.prompt, self.block)):
            alloc.register(int(self._pt[slot, i]) - goff, h)

    def _stake_suffix(self, req: ServeRequest, slot: int) -> None:
        """Map an admitted prefix-hit's pages: shared blocks become logical
        pages [0, kept) (reference ownership moves from the request's holds
        into the slot's lease), private pages cover the rest of the prompt;
        on full coverage the final hit block is device-copied into the
        first private page (copy-on-write) so the last token's row — and
        every decode append after it — lands privately. Decode appends can
        never touch a shared block: shared pages cover only positions
        < offset, and all writes happen at >= offset."""
        length = len(req.prompt)
        kept = self._kept_shared(req)
        alloc = self._alloc_for(slot)
        goff = self._goff(self._shard_of(slot))
        lease = alloc.reserve(self._suffix_need(req))
        shared = req.prefix_blocks[:kept]    # shard-local ids
        cow_src = req.prefix_blocks[kept:]   # [] or [the full-coverage block]
        alloc.adopt(lease, shared)
        priv = alloc.map(lease, self._pages(length) - kept)
        self._leases[slot] = lease
        self._lengths[slot] = length
        self._pt[slot, :kept] = [b + goff for b in shared]
        self._pt[slot, kept:self._pages(length)] = [b + goff for b in priv]
        self._pt_dirty = True
        if cow_src:
            # the device copy addresses global storage rows (plain jit)
            self.pool = self._copy_block(
                self.pool, jnp.asarray(cow_src[0] + goff, jnp.int32),
                jnp.asarray(priv[0] + goff, jnp.int32))
            self._repin()
            alloc.release_ref(cow_src[0])  # the hold on the source
            self._cow_copies += 1
            self._m_cow.inc()
            self.tracer.instant("cow_copy", tid=slot + 1,
                                args={"rid": req.rid})
        req.prefix_blocks = []  # references now live in the lease

    def _prefill_suffix_one(self, req: ServeRequest, slot: int) -> None:
        """Admission path for a prefix-cache hit: stake shared + private
        pages, then run the suffix-only insertion prefill — the model
        extends the gathered prefix context by the suffix rows; only rows
        [offset, prompt_len) are scattered back (masked, so bucket padding
        lands in the trash sink). Never coalesced: hit admissions are
        per-request launches at the (suffix bucket, 1) key."""
        offset, slen = self._split_point(req)
        t0 = time.time()
        self._stake_suffix(req, slot)
        self._prefix_hit_tokens += offset
        self._m_hit_tokens.inc(offset)
        self._prefix_prompt_tokens += len(req.prompt)
        bucket = self._bucket(slen)
        tokens = np.zeros((1, bucket), np.int32)
        tokens[0, :slen] = req.prompt[offset:]
        batch = {"tokens": jnp.asarray(tokens),
                 "lengths": jnp.asarray([slen], jnp.int32),
                 "offsets": jnp.asarray([offset], jnp.int32)}
        with annotate(f"serve/prefill_sfx_b{bucket}"):
            logits, self.pool = self._prefill_suffix(
                self.params, batch, self.pool, jnp.asarray([slot]),
                jnp.asarray(self._pt[slot:slot + 1]))
        self._repin()
        self._buckets_used.add(("sfx", bucket, 1))
        toks = np.asarray(self._sample_dev(logits, self._next_key()))
        now = time.time()
        self.stats["prefill_s"] += now - t0
        self._m_prefill_s.observe(now - t0)
        if self.tracer.enabled:
            self.tracer.instant("prefix_hit", ts=t0, tid=slot + 1,
                                args={"rid": req.rid, "hit_tokens": offset})
            self.tracer.complete(
                "prefill", t0, now - t0, tid=slot + 1,
                args={"rid": req.rid, "kind": "suffix", "bucket": bucket,
                      "offset": offset})
        self.stats["requests"] += 1
        self._register_blocks(req, slot)
        if self._emit(req, int(toks[0]), now):
            self._retire(slot, now)
        else:
            self._cur_tok[slot] = int(toks[0])

    def pin_prefix(self, tokens) -> int:
        """Pin a hot template's full blocks in the content index so they
        survive pool churn: the engine holds one reference per block until
        :meth:`release_pins`, so retirement can never recycle them. When
        the template is not yet cached it is prefilled through the normal
        request path (a max_new=1 probe — numerically identical to any
        cold admission), then each full block's reference is taken.
        Returns the number of blocks pinned (0 when prefix caching is off
        or the template fits no full block)."""
        if not self._prefix_enabled:
            return 0
        tokens = np.asarray(tokens, np.int32)
        hashes = chain_hashes(tokens, self.block)
        if not hashes:
            return 0
        if not any(all(a.lookup(h) is not None for h in hashes)
                   for a in self._allocs):
            rid = self.submit(tokens, max_new_tokens=1)
            while any(r.rid == rid for r in self.sched.waiting) or any(
                    r.rid == rid for r in self.sched.running.values()):
                self.step()
        pinned = 0
        for shard, alloc in enumerate(self._allocs):
            for h in hashes:
                b = alloc.lookup(h)
                if b is None or not alloc.acquire(b):
                    break
                self._pins.append((shard, b))
                pinned += 1
        return pinned

    def release_pins(self) -> None:
        """Drop every pin reference (pinned blocks become cached-free —
        still indexed, reclaimable under pressure)."""
        for shard, b in self._pins:
            self._allocs[shard].release_ref(b)
        self._pins.clear()

    # ------------------------------------------------------------------
    # the continuous loop
    # ------------------------------------------------------------------
    def _bucket(self, n: int) -> int:
        b = self.min_bucket
        while b < n:
            b *= 2
        return b

    def _sample(self, logits: jax.Array) -> np.ndarray:
        """Legacy host-side sampler — the per-token device->host round-trip
        the fused step removed from the hot loop. Kept as the parity
        reference for the device samplers (pinned by tests); each call is
        a counted host sync."""
        self.stats["sample_host_syncs"] += 1
        if self.sample_mode == "topk":
            self.key, sub = jax.random.split(self.key)
            t = self.temperature if self.temperature > 0 else 1.0
            kth = jax.lax.top_k(logits, self.top_k)[0][..., -1:]
            masked = jnp.where(logits < kth, -jnp.inf, logits)
            # flarecheck: disable=HS003 -- legacy host sampler, counted above
            return np.asarray(jax.random.categorical(sub, masked / t), np.int32)
        if self.temperature <= 0.0:
            # flarecheck: disable=HS003 -- legacy host sampler, counted above
            return np.asarray(jnp.argmax(logits, axis=-1), np.int32)
        self.key, sub = jax.random.split(self.key)
        # flarecheck: disable=HS003 -- legacy host sampler, counted above
        return np.asarray(
            jax.random.categorical(sub, logits / self.temperature), np.int32)

    def _emit(self, req: ServeRequest, token: int, now: float) -> bool:
        """Record one generated token; returns True when the request is done."""
        req.tokens.append(token)
        if req.first_token_t is None:
            req.first_token_t = now
        if req.on_token is not None:
            req.on_token(req.rid, token)
        self.stats["tokens_generated"] += 1
        return token == req.eos_id or len(req.tokens) >= req.max_new_tokens

    def _retire(self, slot: int, now: float) -> None:
        req = self.sched.retire(slot, now)
        self._m_tokens_out.inc(len(req.tokens))
        self.tracer.instant("retire", ts=now, tid=slot + 1,
                            args={"rid": req.rid, "tokens": len(req.tokens)})
        # leave NO state behind for the slot's next tenant (FlareState.m_max
        # must return to -inf etc.); a single-lane reset compiles once
        self.pool = self._reset_slot(self.pool, jnp.asarray([slot]))
        self._repin()
        self._cur_tok[slot] = 0
        if self.paged:
            # pages (mapped + unused reservation) back to the free list; the
            # page-table row goes back to the slot's shard's trash sink
            self._alloc_for(slot).release(self._leases.pop(slot))
            self._pt[slot] = self._trash_of(slot)
            self._pt_dirty = True
            self._lengths[slot] = 0
            if self._sanitize:
                self.check_invariants()

    def _prefill_group(self, bucket: int, group) -> None:
        """One prefill launch for ``group`` = [(req, slot), ...] admissions
        sharing a bucket (len > 1 only under coalesce_prefill)."""
        g = len(group)
        tokens = np.zeros((g, bucket), np.int32)
        lens = np.empty(g, np.int32)
        for i, (req, _) in enumerate(group):
            tokens[i, : len(req.prompt)] = req.prompt  # right-padded: exact
            lens[i] = len(req.prompt)
        batch = {"tokens": jnp.asarray(tokens),
                 "lengths": jnp.asarray(lens, jnp.int32)}
        slots_arr = jnp.asarray([slot for _, slot in group])
        t0 = time.time()
        if self.paged:
            bids = np.stack([self._stake_pages(req, slot, bucket)
                             for req, slot in group])
            with annotate(f"serve/prefill_b{bucket}x{g}"):
                logits, self.pool = self._prefill_into(
                    self.params, batch, self.pool, slots_arr,
                    jnp.asarray(bids))
        else:
            with annotate(f"serve/prefill_b{bucket}x{g}"):
                logits, self.pool = self._prefill_into(
                    self.params, batch, self.pool, slots_arr)
        self._repin()
        self._buckets_used.add((bucket, g))
        if g > 1:
            self.stats["coalesced_prefills"] += 1
        # device sampler (same ops as the fused step); the transfer below
        # blocks until prefill has executed
        toks = np.asarray(self._sample_dev(logits, self._next_key()))
        now = time.time()
        self.stats["prefill_s"] += now - t0
        self._m_prefill_s.observe(now - t0)
        if self.tracer.enabled:
            self.tracer.complete(
                "prefill", t0, now - t0, tid=group[0][1] + 1,
                args={"rids": [r.rid for r, _ in group], "bucket": bucket,
                      "lanes": g})
        self.stats["requests"] += g
        for req, slot in group:
            if self.paged and self._prefix_enabled:
                # cold prompts become donors: index their full blocks (and
                # count their tokens in the hit-rate denominator)
                self._register_blocks(req, slot)
                self._prefix_prompt_tokens += len(req.prompt)
        for i, (req, slot) in enumerate(group):
            if self._emit(req, int(toks[i]), now):
                self._retire(slot, now)
            else:
                self._cur_tok[slot] = int(toks[i])

    def _admit(self) -> None:
        self._pending_pages = [0] * self._shards
        self._free_preview = list(self.sched.free)
        self._match_on_admit = True
        now = time.time()
        admitted = self.sched.admit(
            now, can_admit=self._can_admit if self.paged else None)
        if (not admitted and self._prefix_enabled and not self.sched.running
                and self.sched.waiting):
            # Deadlock fallback: queued holds (and resurrections the gate
            # itself takes) can pin enough blocks that the idle pool can't
            # stake the FIFO head — and nothing will ever retire to free
            # them. Drop every queued hold (submit guaranteed worst-case
            # feasibility without them) and retry once COLD, matching
            # disabled so the gate can't re-acquire what it just dropped.
            for r in self.sched.waiting:
                self._drop_prefix_holds(r)
            self._pending_pages = [0] * self._shards
            self._free_preview = list(self.sched.free)
            self._match_on_admit = False
            try:
                admitted = self.sched.admit(now, can_admit=self._can_admit)
            finally:
                self._match_on_admit = True
            if not admitted and not self.sched.running and self.sched.waiting:
                raise RuntimeError(
                    "pool wedged: the queue head cannot stake its pages even "
                    "with every prefix hold dropped and nothing running — "
                    "pinned blocks exceed the pool's headroom (release_pins "
                    "or raise pool_tokens)")
        if not admitted:
            return
        if self.tracer.enabled:
            for req, slot in admitted:
                self.tracer.instant(
                    "admit", ts=req.admit_t, tid=slot + 1,
                    args={"rid": req.rid,
                          "queue_s": round(req.admit_t - req.submit_t, 6)})
        cold = [(r, s) for r, s in admitted if not r.prefix_blocks]
        hits = [(r, s) for r, s in admitted if r.prefix_blocks]
        if self.coalesce:
            groups: dict = {}
            for req, slot in cold:
                groups.setdefault(self._bucket(len(req.prompt)), []).append(
                    (req, slot))
            for bucket, group in groups.items():
                self._prefill_group(bucket, group)
        else:
            for req, slot in cold:
                self._prefill_group(self._bucket(len(req.prompt)), [(req, slot)])
        for req, slot in hits:
            self._prefill_suffix_one(req, slot)
        if self.paged and self._sanitize:
            self.check_invariants()

    def check_invariants(self) -> None:
        """Runtime sanitizer (DESIGN.md §14): every allocator refcount must
        be accounted for by a known holder — slot leases, prefix pins, or
        queued requests' enqueue-time prefix holds — and every slot's page
        table row must mirror its lease's mapped pages exactly (unmapped
        tail pointing at the trash sink). No-op for unpaged engines. Called
        from the pool-test fixtures, and at every admission/retire under
        ``REPRO_SANITIZE=1``."""
        if not self.paged:
            return
        refs: list = [dict() for _ in self._allocs]
        for slot, lease in self._leases.items():
            r = refs[self._shard_of(slot)]
            for b in lease.mapped:
                r[b] = r.get(b, 0) + 1
        for shard, b in self._pins:
            refs[shard][b] = refs[shard].get(b, 0) + 1
        for req in self.sched.waiting:
            r = refs[req.prefix_shard if req.prefix_shard is not None else 0]
            for b in (req.prefix_blocks or []):
                r[b] = r.get(b, 0) + 1
        for alloc, r in zip(self._allocs, refs):
            alloc.check_invariants(external_refs=r)
        for slot in range(self._pt.shape[0]):
            goff = self._goff(self._shard_of(slot))
            trash = self._trash_of(slot)
            lease = self._leases.get(slot)
            mapped = [b + goff for b in lease.mapped] if lease is not None else []
            row = self._pt[slot]
            got = [int(x) for x in row[:len(mapped)]]
            if got != mapped:
                raise RuntimeError(
                    f"sanitizer: slot {slot} page table row {got} disagrees "
                    f"with its lease's mapped pages {mapped}")
            if not (row[len(mapped):] == trash).all():
                stray = [int(x) for x in row[len(mapped):] if x != trash]
                raise RuntimeError(
                    f"sanitizer: slot {slot} has page-table entries past its "
                    f"lease ({stray}) — writes would land in foreign blocks")

    def _decode_pool(self, toks: jax.Array) -> jax.Array:
        """One fused decode step over the whole pool — model decode AND
        sampling in one compiled program; returns the sampled token ids
        (device array, not yet synced). The paged pool goes through the
        PagedCacheView adapter (kernel or gather route per the resolved
        plan): pages are appended BEFORE the step when a slot's next write
        position lands in an unmapped block (reservation guarantees
        success), idle lanes write into the trash sink. The device page
        table is re-uploaded only when the host table actually changed."""
        key = self._next_key()
        if not self.paged:
            toks_out, logits, self.pool = self._decode_step(
                self.params, toks, self.pool, key)
            self.last_logits = logits
            return toks_out
        if self._has_paged:
            for slot in self.sched.running:
                p = int(self._lengths[slot] % self.capacity)
                j = p // self.block
                if self._pt[slot, j] == self._trash_of(slot):
                    self._pt[slot, j] = (
                        self._goff(self._shard_of(slot))
                        + self._alloc_for(slot).append(self._leases[slot]))
                    self._pt_dirty = True
            if self._pt_dirty:
                self._pt_dev = jnp.asarray(self._pt)
                self._pt_dirty = False
            pt = self._pt_dev
            write_pos = jnp.asarray(
                (self._lengths % self.capacity).astype(np.int32))
        else:
            # degenerate pool (no token-axis leaves): page table and write
            # positions are all-trash constants — reuse the cached device
            # arrays instead of re-transferring them every step (the view's
            # gather/write-back trace to identity under jit)
            pt, write_pos = self._const_view_args
        toks_out, logits, self.pool = self._decode_step(
            self.params, toks, self.pool, pt, write_pos, key)
        self.last_logits = logits
        if self._has_paged:
            for slot in self.sched.running:
                self._lengths[slot] += 1
        return toks_out

    def step(self) -> bool:
        """Admit queued work into free slots, run ONE decode step across the
        pool, retire finished sequences. Returns True while work remains."""
        self._admit()
        self.stats["admitted_peak"] = max(self.stats["admitted_peak"],
                                          len(self.sched.running))
        if self.sched.running:
            t0 = time.time()
            toks_dev = self._decode_pool(jnp.asarray(self._cur_tok[:, None]))
            # the ONLY device->host transfer of the step: S int32 token ids
            # flarecheck: disable=HS003 -- the one sanctioned per-step sync
            toks = np.asarray(toks_dev)
            now = time.time()
            active = len(self.sched.running)
            self.stats["decode_s"] += now - t0
            self.stats["decode_steps"] += 1
            self.sched.note_decode_step()
            self._note_step(t0, now, active)
            for slot, req in list(self.sched.running.items()):
                tok = int(toks[slot])
                if self._emit(req, tok, now):
                    self._retire(slot, now)
                else:
                    self._cur_tok[slot] = tok
        if self._win_t0 is not None and not self.sched.running:
            self._flush_window()  # pool idle: close the partial window
        self._refresh_stats()
        return self.sched.has_work()

    def _note_step(self, t0: float, now: float, active: int) -> None:
        """Per-step obs bookkeeping, from the two stamps ``step`` already
        took — no extra clock reads, no device traffic. Lives OUTSIDE the
        hot-scope names (OB001/HS001 boundary) on purpose: ``step`` itself
        only calls here."""
        self._m_step_s.observe(now - t0)
        if not self.tracer.enabled:
            return
        if self._win_t0 is None:
            self._win_t0 = t0
        self._win_end = now
        self._win_steps += 1
        self._win_toks += active
        if self._win_steps >= self._trace_every:
            self._flush_window()

    def _flush_window(self) -> None:
        """Emit the aggregated "decode" span for the open step window."""
        if self._win_t0 is None:
            return
        self.tracer.complete(
            "decode", self._win_t0, self._win_end - self._win_t0,
            args={"steps": self._win_steps, "tokens": self._win_toks})
        self._win_t0 = None
        self._win_steps = 0
        self._win_toks = 0

    def warmup(self, max_prompt_len: Optional[int] = None,
               max_lanes: Optional[int] = None) -> int:
        """Front-load every compile the steady-state loop can hit (the
        MaxText offline-inference warmup idiom): one prefill trace per
        (bucket, lanes) key up to ``max_prompt_len`` / ``max_lanes``, plus
        one fused decode-step trace, all against throwaway inputs — the
        results are discarded and pool state is untouched (everything is
        functional). Warmed keys land in the same (bucket, lanes) cache
        the live loop consults, so they never retrace; after warmup,
        ``stats["decode_compiles"]`` must not grow in steady state
        (asserted by scripts/ci.sh). Returns the number of program
        variants compiled."""
        t0 = time.time()
        top = min(max_prompt_len or self.capacity, self.capacity)
        buckets = [self.min_bucket]
        while buckets[-1] < top:
            buckets.append(buckets[-1] * 2)
        lanes = range(1, (max_lanes or (self.slots if self.coalesce else 1)) + 1)
        compiled = 0
        for g in lanes:
            for bucket in buckets:
                if (bucket, g) in self._buckets_used:
                    continue
                batch = {"tokens": jnp.zeros((g, bucket), jnp.int32),
                         "lengths": jnp.ones((g,), jnp.int32)}
                slots_arr = jnp.zeros((g,), jnp.int32)
                if self.paged:
                    bids = jnp.full((g, self._pages(bucket)),
                                    self.slot_cache.trash, jnp.int32)
                    out = self._prefill_into(self.params, batch, self.pool,
                                             slots_arr, bids)
                else:
                    out = self._prefill_into(self.params, batch, self.pool,
                                             slots_arr)
                jax.block_until_ready(out[0])
                self._buckets_used.add((bucket, g))
                compiled += 1
        if self.paged and self._prefix_enabled:
            # prefix-hit admissions launch (suffix bucket, 1 lane) programs
            # — usually SMALLER buckets than any full prompt uses — plus the
            # COW block copy; trace them all so a hit never compiles in
            # steady state (--max-decode-compiles 0 must keep holding)
            for bucket in buckets:
                key2 = ("sfx", bucket, 1)
                if key2 in self._buckets_used:
                    continue
                batch = {"tokens": jnp.zeros((1, bucket), jnp.int32),
                         "lengths": jnp.ones((1,), jnp.int32),
                         "offsets": jnp.zeros((1,), jnp.int32)}
                pt_row = jnp.full((1, self.slot_cache.max_pages),
                                  self.slot_cache.trash, jnp.int32)
                out = self._prefill_suffix(self.params, batch, self.pool,
                                           jnp.zeros((1,), jnp.int32), pt_row)
                jax.block_until_ready(out[0])
                self._buckets_used.add(key2)
                compiled += 1
            trash = jnp.asarray(self.slot_cache.trash, jnp.int32)
            self.pool = self._copy_block(self.pool, trash, trash)
            self._repin()
            compiled += 1
        dc_before = self._decode_compiles
        toks = jnp.zeros((self.slots, 1), jnp.int32)
        key = self.key  # traced only; warmup consumes no entropy
        if self.paged:
            if self._has_paged:
                pt, write_pos = self._pt_dev, jnp.zeros(self.slots, jnp.int32)
            else:
                pt, write_pos = self._const_view_args
            out = self._decode_step(self.params, toks, self.pool, pt,
                                    write_pos, key)
        else:
            out = self._decode_step(self.params, toks, self.pool, key)
        jax.block_until_ready(out[0])
        compiled += self._decode_compiles - dc_before
        self.stats["warmup_compiles"] += compiled
        dur = time.time() - t0
        self.stats["warmup_s"] += dur
        self.tracer.complete("warmup", t0, dur,
                             args={"compiles": compiled})
        self._refresh_stats()
        return compiled

    def _refresh_stats(self) -> None:
        self.stats["prefill_compiles"] = len(self._buckets_used)
        self.stats["decode_compiles"] = self._decode_compiles
        # registry mirrors of the compile counters — set HERE, never inside
        # the traced fused body (the OB001 boundary: _decode_compiles is a
        # trace-time python increment; the gauges are host bookkeeping)
        self._m_g_prefill_compiles.set(len(self._buckets_used))
        self._m_g_decode_compiles.set(self._decode_compiles)
        self.stats["host_syncs_per_step"] = (
            self.stats["sample_host_syncs"]
            / max(1, self.stats["decode_steps"]))
        self.stats.update(self.sched.stats())
        if self.paged:
            pool_stats = self.alloc.stats()  # incl. pages_appended
            if self._shards > 1:
                pool_stats = {k: sum(a.stats()[k] for a in self._allocs)
                              for k in pool_stats}
            self.stats["pool"] = pool_stats
            self.stats["prefix_hit_rate"] = (
                self._prefix_hit_tokens / self._prefix_prompt_tokens
                if self._prefix_prompt_tokens else 0.0)
            self.stats["shared_pages"] = sum(a.shared_blocks()
                                             for a in self._allocs)
            self.stats["cow_copies"] = self._cow_copies
            self.stats["pinned_pages"] = len(self._pins)

    # ------------------------------------------------------------------
    # convenience drivers
    # ------------------------------------------------------------------
    def run_all(self, max_batch: Optional[int] = None) -> list[np.ndarray]:
        """Serve the queue to completion; returns generated ids for the
        requests resolved by this call, in submission order (dropped
        requests yield empty arrays). ``max_batch`` is accepted for backward
        compatibility — concurrency is the engine's ``slots``."""
        seen = {r.rid for r in self.sched.finished + self.sched.dropped}
        while self.step():
            pass
        new = [r for r in self.sched.finished + self.sched.dropped
               if r.rid not in seen]
        return [np.asarray(r.tokens, np.int32)
                for r in sorted(new, key=lambda r: r.rid)]
