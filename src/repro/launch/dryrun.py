import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell we:
  1. build the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. build the step (train/prefill/decode) with full shardings,
  3. jit(...).lower(ShapeDtypeStructs).compile()  — no real allocation,
  4. record memory_analysis(), cost_analysis(), and the trip-count-aware
     HLO analysis (FLOPs / bytes / collective bytes per device) plus the
     three-term roofline,
  5. write a JSON artifact under experiments/artifacts/.

Skips (structured, with reasons): long_500k for pure full-attention archs.

Usage:
  python -m repro.launch.dryrun --arch phi3_mini_3_8b --shape train_4k
  python -m repro.launch.dryrun --all            # every assigned cell
  python -m repro.launch.dryrun --all --mesh multi
"""
import argparse
import json
import time
import traceback

import jax

from repro.analysis.flops import model_flops, param_counts
from repro.analysis.hlo import analyze_hlo
from repro.analysis.roofline import V5E, roofline_terms
from repro.config import SHAPES
from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell, input_specs  # noqa: F401 (input_specs is the public API)

# long_500k needs sub-quadratic attention (DESIGN.md §5):
LONG_OK = {"rwkv6_3b", "zamba2_7b", "mixtral_8x7b", "flare_lm"}
LM_SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]
PDE_SHAPES = ["pde_40k", "pde_1m"]


def cells_for(arch: str):
    shapes = PDE_SHAPES if arch == "flare_pde" else LM_SHAPES
    for s in shapes:
        yield s


def skip_reason(arch: str, shape: str):
    if shape == "long_500k" and arch not in LONG_OK:
        return ("full-attention arch: 500k decode cache/prefill is quadratic-"
                "prohibitive; run only for SSM/hybrid/SWA/FLARE families")
    return None


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str) -> dict:
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "hw": V5E.name, "status": "ok",
    }
    reason = skip_reason(arch, shape_name)
    if reason:
        record.update(status="skipped", reason=reason)
        return _write(record, out_dir)
    try:
        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        t0 = time.time()
        cell = build_cell(cfg, shape, mesh)
        with mesh, jax.sharding.set_mesh(mesh):
            lowered = jax.jit(
                cell.fn, in_shardings=cell.in_shardings,
                out_shardings=cell.out_shardings,
                donate_argnums=cell.donate,
            ).lower(*cell.args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0

        ma = compiled.memory_analysis()
        mem = {}
        if ma is not None:
            for f in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes",
                      "generated_code_size_in_bytes"):
                mem[f] = int(getattr(ma, f, 0))
            mem["peak_bytes_per_device_est"] = (
                mem["argument_size_in_bytes"] + mem["temp_size_in_bytes"]
                + mem["output_size_in_bytes"] - mem["alias_size_in_bytes"])
        ca = compiled.cost_analysis()
        cost = ca if isinstance(ca, dict) else (ca[0] if ca else {})
        hlo_text = compiled.as_text()
        analysis = analyze_hlo(hlo_text)
        counts = param_counts(cfg)
        mflops = model_flops(cfg, shape, counts)
        n_dev = mesh.devices.size
        roof = roofline_terms(analysis, model_flops_per_device=mflops / n_dev)

        record.update(
            devices=int(n_dev),
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            num_microbatches=cell.meta.get("num_microbatches"),
            memory_analysis=mem,
            cost_analysis={k: float(v) for k, v in cost.items()
                           if isinstance(v, (int, float)) and k in
                           ("flops", "bytes accessed", "transcendentals")},
            hlo_analysis={k: (v if isinstance(v, dict) else float(v))
                          for k, v in analysis.items()},
            params=counts,
            model_flops=mflops,
            roofline=roof,
            sharding_notes=cell.meta.get("sharding_report", [])[:40],
        )
    except Exception as e:  # noqa: BLE001 — record the failure, keep sweeping
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-2000:])
    return _write(record, out_dir)


def _write(record: dict, out_dir: str) -> dict:
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{record['arch']}__{record['shape']}__{record['mesh']}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1)
    dom = record.get("roofline", {}).get("dominant", "-")
    status = record["status"]
    extra = record.get("reason") or record.get("error") or ""
    print(f"[{status:7s}] {record['arch']:24s} {record['shape']:12s} {record['mesh']:6s} "
          f"dom={dom:10s} compile={record.get('compile_s', '-')}s {extra[:80]}")
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/artifacts")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = ARCH_IDS if args.all or args.arch is None else [args.arch]
    n_ok = n_skip = n_err = 0
    for mesh_kind in meshes:
        for arch in archs:
            shapes = [args.shape] if args.shape else list(cells_for(arch))
            for shape in shapes:
                rec = run_cell(arch, shape, mesh_kind, args.out)
                n_ok += rec["status"] == "ok"
                n_skip += rec["status"] == "skipped"
                n_err += rec["status"] == "error"
    print(f"\ndone: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
