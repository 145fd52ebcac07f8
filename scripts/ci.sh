#!/usr/bin/env bash
# CI entry point: fast test tier + interpret-mode kernel-parity smoke.
#
# Runs on CPU — every Pallas kernel executes in interpret mode, so kernel
# regressions (layout, masking, VJP) are caught without a TPU. The slow
# tier (subprocess device farms, end-to-end trains, the broad smoke matrix)
# is excluded; run `python -m pytest -x -q` before shipping (ROADMAP.md).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== policy-resolution smoke (backend x policy eligibility) =="
# every registered backend against the four canonical policies; fails if any
# canonical policy (bidi/causal x infer/train) has no eligible backend.
# (-W: runpy warns that repro.core already imported dispatch — benign; the
# __main__ stub delegates to the canonical module instance)
dispatch_list="$(python -W "ignore::RuntimeWarning" -m repro.core.dispatch --list)"
echo "$dispatch_list"
# the paged serve pool's kernel must stay policy-addressable (DESIGN.md §4)
echo "$dispatch_list" | grep -q "^paged " \
    || { echo "ERROR: 'paged' backend missing from the registry"; exit 1; }
# ...and auto-resolvable for decode-shaped pools (latents=1 scores above the
# dense backends) while staying out of dense call sites — the fused decode
# step's routing contract (DESIGN.md §4 "Fused decode step")
python - <<'PY'
import jax.numpy as jnp
from repro.core.dispatch import MixerShape
from repro.core.policy import MixerPolicy, resolve_policy

decode = MixerShape(batch=4, heads=2, tokens=64, latents=1, head_dim=8)
plan = resolve_policy(MixerPolicy(), decode, jnp.dtype("bfloat16"), causal=False)
assert plan.backend == "paged", f"decode-shaped auto pick: {plan.backend}"
dense = MixerShape(batch=4, heads=2, tokens=64, latents=8, head_dim=8)
plan = resolve_policy(MixerPolicy(), dense, jnp.dtype("bfloat16"), causal=False)
assert plan.backend != "paged", f"dense M>1 site leaked to paged: {plan.backend}"
print(f"paged routing OK (decode->paged, dense->{plan.backend})")
PY

echo "== mesh-parallel eligibility smoke (DESIGN.md §15) =="
# both sharded backends must be registered with the mesh-eligibility columns,
# and the strict symmetry must hold: sharded backends never auto-resolve
# without a mesh, and naming one without a mesh is a hard error
echo "$dispatch_list" | grep -q "^packed_shard " \
    || { echo "ERROR: 'packed_shard' backend missing from the registry"; exit 1; }
echo "$dispatch_list" | grep -q "^paged_shard " \
    || { echo "ERROR: 'paged_shard' backend missing from the registry"; exit 1; }
echo "$dispatch_list" | grep -q "with-mesh" \
    || { echo "ERROR: dispatch --list lost the mesh-eligibility columns"; exit 1; }
python - <<'PY'
import jax.numpy as jnp
from repro.core.dispatch import MixerShape, get_backend, resolve

shape = MixerShape(batch=4, heads=4, tokens=64, latents=8, head_dim=8)
for causal in (False, True):
    for grad in (False, True):
        _, plan = resolve("auto", shape=shape, dtype=jnp.float32,
                          causal=causal, grad=grad)
        assert not get_backend(plan.backend).caps.sharded, \
            f"auto without a mesh picked sharded backend {plan.backend}"
for name in ("packed_shard", "paged_shard"):
    try:
        resolve(name, shape=shape, dtype=jnp.float32, causal=False)
    except ValueError:
        pass
    else:
        raise SystemExit(f"{name} resolved without a mesh")
print("mesh eligibility OK (sharded backends strictly mesh-gated)")
PY

echo "== flarecheck (static analysis, DESIGN.md §14) =="
# rule catalog must be non-empty (a registration regression would silently
# turn the gate into a no-op), then the gate itself: any finding not in the
# committed baseline fails the build before a single test runs
rules="$(python -m repro.analysis.lint --list-rules)"
[ -n "$rules" ] || { echo "ERROR: flarecheck rule catalog is empty"; exit 1; }
echo "$rules"
python -m repro.analysis.lint src tests --baseline .flarecheck.json

echo "== fast tier (pytest -m 'not slow', allocator sanitizer on) =="
REPRO_SANITIZE=1 python -m pytest -x -q -m "not slow"

echo "== interpret-mode kernel-parity smoke =="
# quick standalone guard: the fused kernels (packed + flash) against the
# jnp oracles, exactly what a kernel regression would break first
python -m pytest -x -q tests/test_kernels.py tests/test_packed.py \
    -k "sweep or oracles or matches"

echo "== continuous-batching serve smoke =="
# slot-pool engine end-to-end on the FLARE-LM smoke config (DESIGN.md §4)
python -m repro.launch.serve --arch flare_lm --smoke --requests 4 --max-new 8
# one-row serving benchmark through the harness contract (includes a paged
# row: admitted-slot + HBM-bytes columns at a fixed byte budget)
REPRO_BENCH_TAG=none REPRO_BENCH_SERVE_SMOKE=1 python -m benchmarks.run serve

echo "== paged-pool smoke (DESIGN.md §4 'Paged pool') =="
# a pool small enough (48 tokens = 6 blocks, vs ~4 pages/request worst
# case) to force page-granular admission backpressure, while max-new
# pushes every request across at least one block boundary (page appends)
python -m repro.launch.serve --arch qwen2_1_5b --smoke --requests 6 \
    --max-new 12 --capacity 32 --slots 4 --pool-tokens 48 --block-size 8 \
    --kv-quant int8 --coalesce

echo "== fused decode-step smoke (DESIGN.md §4 'Fused decode step') =="
# kernel-backed paged decode (forced, not auto) with warmup: the steady-state
# loop must add ZERO decode-step compiles after warmup, and the fused
# sampler must keep per-step host syncs at 0 (both enforced by the launcher)
out="$(python -m repro.launch.serve --arch qwen2_1_5b --smoke --requests 6 \
    --max-new 12 --capacity 32 --slots 4 --pool-tokens 96 --block-size 8 \
    --decode-backend paged --warmup --max-decode-compiles 0)"
echo "$out"
echo "$out" | grep -q "decode backend: paged(" \
    || { echo "ERROR: serve smoke did not route through the paged kernel"; exit 1; }
echo "$out" | grep -q "host syncs/step: 0.0" \
    || { echo "ERROR: fused decode step is syncing logits to the host"; exit 1; }

echo "== observability smoke (DESIGN.md §16) =="
# the fused-decode config again, now with span tracing + a metrics dump:
# tracing is host-side relabeling of stamps the engine already takes, so
# the zero-host-sync invariant must survive it — and the exported trace
# must be schema-valid Chrome trace-event JSON covering every lifecycle
# phase with monotonic timestamps
obs_dir="$(mktemp -d)"
trap 'rm -rf "$obs_dir"' EXIT
obs_out="$(python -m repro.launch.serve --arch qwen2_1_5b --smoke --requests 6 \
    --max-new 12 --capacity 32 --slots 4 --pool-tokens 96 --block-size 8 \
    --decode-backend paged --warmup --max-decode-compiles 0 \
    --trace-out "$obs_dir/trace.json" --metrics-out "$obs_dir/metrics.json")"
echo "$obs_out" | tail -n 6
echo "$obs_out" | grep -q "host syncs/step: 0.0" \
    || { echo "ERROR: tracing broke the fused decode step's zero-sync invariant"; exit 1; }
OBS_DIR="$obs_dir" python - <<'PY'
import json, os

d = os.environ["OBS_DIR"]
doc = json.load(open(os.path.join(d, "trace.json")))
evs = doc["traceEvents"]
assert isinstance(evs, list) and evs, "empty traceEvents"
data = [e for e in evs if e.get("ph") != "M"]
for e in data:
    assert {"name", "ph", "ts", "pid", "tid"} <= set(e), f"malformed event {e}"
    assert e["ph"] in ("X", "i"), f"unexpected phase {e['ph']!r}"
ts = [e["ts"] for e in data]
assert ts == sorted(ts) and ts[0] == 0.0, "trace ts not monotonic/rebased"
names = {e["name"] for e in data}
from repro.obs.trace import PHASES
missing = [p for p in PHASES if p not in names]
assert not missing, f"lifecycle phases missing from trace: {missing}"
metrics = json.load(open(os.path.join(d, "metrics.json")))["metrics"]
for series in ("sched.admitted", "engine.tokens_out", "engine.decode_step_s"):
    assert series in metrics, f"metrics dump missing {series}"
assert metrics["sched.admitted"] == 6.0, metrics["sched.admitted"]
print(f"obs smoke OK ({len(data)} spans, {len(metrics)} metric series, "
      "all lifecycle phases present)")
PY

echo "== prefix-cache smoke (DESIGN.md §4 'Prefix cache') =="
# two waves sharing a 40-token template on a tiny pool: the cached+pinned
# run must (a) report a nonzero hit rate, (b) exercise at least one
# copy-on-write (request 0 is the exact template and the pin probe has
# already registered it), and (c) emit BIT-identical greedy outputs to a
# cold-cache run of the same seeded workload
warm="$(python -m repro.launch.serve --arch qwen2_1_5b --smoke --requests 6 \
    --prompt-len 40 --max-new 6 --capacity 64 --slots 4 --pool-tokens 192 \
    --block-size 8 --share-prefix 1 --prefix-cache --pin-prompt)"
echo "$warm" | grep "prefix cache:"
echo "$warm" | grep -q "prefix cache: enabled=True hit_rate=0\.[1-9]" \
    || { echo "ERROR: prefix cache reported a zero hit rate"; exit 1; }
echo "$warm" | grep "prefix cache:" | grep -q "cow_copies=0" \
    && { echo "ERROR: expected at least one copy-on-write"; exit 1; }
cold="$(python -m repro.launch.serve --arch qwen2_1_5b --smoke --requests 6 \
    --prompt-len 40 --max-new 6 --capacity 64 --slots 4 --pool-tokens 192 \
    --block-size 8 --share-prefix 1)"
diff <(echo "$warm" | grep '^req ') <(echo "$cold" | grep '^req ') \
    || { echo "ERROR: prefix-cache outputs diverge from the cold run"; exit 1; }
echo "prefix-cache smoke OK (bit-identical to cold run)"

echo "CI OK"
