"""Run one benchmark cell once on the chip and print its result line.

    python bench/run.py --workload <config>.<traffic> --seed N --seconds S --trace 0|1

``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1`` runs the
profiler over part of the window and prints its per-layer metrics. Both
decide ``correct`` against the configuration's plain reference. The last
line of standard output is the result (JSON); the numbers compared, each
with its limit, are the last lines of standard error and the result's last
key. Without a TPU, or with fewer chips than the cell asks for, it exits
with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import harness  # noqa: E402


def _clean(x):
    if isinstance(x, float) and not math.isfinite(x):
        return None
    if isinstance(x, dict):
        return {k: _clean(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_clean(v) for v in x]
    return x


def per_layer(bench: dict, name: str, ctx: dict) -> dict:
    """The cell's per-layer metrics that their readers find something for."""
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and name not in m["workloads"]:
            continue
        value = harness.metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="also copy the profiler trace to this directory")
    args = ap.parse_args(argv)

    bench = harness.benchmark()
    c = harness.cell(args.workload, bench)
    harness.setup_env()
    try:
        dev = harness.device(c["cell"]["chips"])
    except harness.NoDevice as e:
        print(e, file=sys.stderr)
        return 2
    from bench.metrics import _trace
    from bench.peaks import peaks_for

    peaks = peaks_for(dev["kind"])
    counter = harness.CompileCounter()
    drive = harness.driver(c["config"]["entry"])
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        res = drive.run(c, args.seed, args.seconds, bool(args.trace),
                        t_start=T_START, counter=counter, trace_dir=trace_dir)
        device = dict(dev, memory_peak_bytes=res["memory_peak_bytes"])
        out = {"correct": res["correct"], "attempted": res["attempted"],
               "failed": res["failed"]}
        if args.trace:
            path = _trace.find_xplane(trace_dir)
            if args.keep_trace:
                os.makedirs(args.keep_trace, exist_ok=True)
                shutil.copy(path, args.keep_trace)
            tr = _trace.load(path)
            ctx = dict(res["ctx"], trace=tr, peaks=peaks, device=dev)
            out["metrics"] = per_layer(bench, c["cell"]["name"], ctx)
            device.update(busy_s=_trace.busy_s(tr), window_s=tr.window_s)
            out["breakdown"] = {"device_ops": _trace.top_ops(tr),
                                "idle_gaps": _trace.idle_gaps(tr)}
        else:
            units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
            out["metrics"] = {k: {"value": v, "unit": units[k]}
                              for k, v in res["e2e"].items() if k in units}
        out["device"] = device
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
        gc.collect()
    out["checks"] = res["checks"]
    print(json.dumps(_clean({"info": res["info"]})), flush=True)
    for k, v in res["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(_clean(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
