"""Benchmark harness entry point — one module per paper table/figure.

Usage:
    PYTHONPATH=src python -m benchmarks.run             # everything
    PYTHONPATH=src python -m benchmarks.run table1 fig2 # subset

Each row is ``name,us_per_call,derived`` CSV (harness contract); the same
rows — annotated with which mixer backend/plan produced them — are written
to ``benchmark_results.json`` (override with REPRO_BENCH_JSON) and, for the
tracked perf trajectory, to ``BENCH_<tag>.json`` at the repo root (tag =
REPRO_BENCH_TAG or the short git commit hash; disable with
REPRO_BENCH_TAG=none). Committing the BENCH file pins each commit's numbers
so future PRs can diff perf.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time
import traceback

MODULES = {
    "table1": "benchmarks.bench_table1_pde",       # Table 1: PDE accuracy
    "fig2": "benchmarks.bench_fig2_scaling",       # Fig 2: time scaling
    "fig8": "benchmarks.bench_fig8_layer_time",    # Fig 8: layer exec time
    "fig5": "benchmarks.bench_fig5_million",       # Fig 5: M-scaling, large N
    "fig9": "benchmarks.bench_fig9_blocks_latents",  # Figs 5/9: B & M sweeps
    "fig11": "benchmarks.bench_fig11_latent_blocks",  # Fig 11: latent blocks
    "fig12": "benchmarks.bench_fig12_shared_latents",  # Fig 12: shared latents
    "fig13": "benchmarks.bench_fig13_heads",       # Fig 13: head dimension
    "table2": "benchmarks.bench_table2_lra",       # Table 2: LRA proxy
    "roofline": "benchmarks.bench_roofline",       # dry-run roofline table
    "serve": "benchmarks.bench_serve",             # continuous-batching engine
    "mesh": "benchmarks.bench_mesh",               # mesh-parallel (DESIGN.md §15)
}


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    names = sys.argv[1:] or list(MODULES)
    print("name,us_per_call,derived")
    failures = []
    for name in names:
        mod_name = MODULES[name]
        t0 = time.time()
        try:
            mod = __import__(mod_name, fromlist=["run"])
            mod.run()
            print(f"{name}/_wall,{(time.time() - t0) * 1e6:.0f},ok")
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            print(f"{name}/_wall,{(time.time() - t0) * 1e6:.0f},FAILED:{type(e).__name__}")
            failures.append(name)
    from benchmarks.common import write_bench_json, write_results_json

    json_path = os.environ.get("REPRO_BENCH_JSON", "benchmark_results.json")
    try:
        write_results_json(json_path)
    except OSError as e:  # pragma: no cover — JSON sidecar is best-effort
        print(f"_json,0,FAILED:{e}")
    tag = os.environ.get("REPRO_BENCH_TAG") or _git_commit(short=True) or "local"
    if tag != "none":
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        try:
            write_bench_json(os.path.join(root, f"BENCH_{tag}.json"),
                             tag=tag, commit=_git_commit() or "unknown",
                             modules=names)
        except OSError as e:  # pragma: no cover
            print(f"_bench_json,0,FAILED:{e}")
    if failures:
        raise SystemExit(f"benchmark failures: {failures}")


def _git_commit(short: bool = False) -> str:
    try:
        args = ["git", "rev-parse"] + (["--short"] if short else []) + ["HEAD"]
        return subprocess.run(
            args, capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        ).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        return ""


if __name__ == "__main__":
    main()
