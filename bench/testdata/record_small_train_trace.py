"""Record ``small_train_trace.xplane.pb`` and ``small_train_trace.hlo.txt``:
one traced train step of ``flare_pde`` at a reduced size, driven the way
the benchmark's traced run drives it, and the compiled step's instruction
names with their ``op_name``s.

    python3 bench/testdata/record_small_train_trace.py record OUT_DIR   # on a TPU
    python3 bench/testdata/record_small_train_trace.py shrink OUT_DIR   # anywhere

``record`` builds the cell ``flare_pde.train_40k`` at 2 x 32 x 32 points and
M = 256 latents per head (the configuration's other widths and its 8
blocks), takes ``drive_train``'s set-up steps, then profiles one more ``fit``
step inside the harness's window annotation, without the Python tracer. It
writes ``raw.xplane.pb`` and, for every instruction that ran in the window,
one line ``%<name> = metadata={op_name="..."}`` of the compiled step's
text (``hlo.txt``). ``shrink`` (TensorFlow's XSpace proto) keeps the device
planes' ``XLA Modules`` and ``XLA Ops`` lines, the host's ``train/`` and
``bench.`` annotations and the profile's start time, cuts each op's name
to its instruction (custom calls keep their whole name), leaves out of
the text the instructions without an ``op_name`` (the parameters'
``copy-start``/``copy-done``: no pattern matches them, so the readings
stand and only the coverage drops), and writes the two files under their
names here, under 300 KB together.
"""
from __future__ import annotations

import copy
import os
import re
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

NAME = "small_train_trace"
SEED = 2147483659


def small_cell():
    from bench import harness

    c = harness.cell("flare_pde.train_40k")
    cfg = copy.deepcopy(c["config"])
    cfg["num_latents"] = 256
    cfg["program"]["flare_latents"] = 256
    mix = dict(c["mix"], batch=2, grid=32, cg_iters=50, distinct_batches=4,
               check_steps=2, trace_steps=1)
    return dict(c, config=cfg, mix=mix)


def record(out: str) -> None:
    import jax

    from bench import drive_train, harness
    from bench.metrics import _hlo, _trace

    harness.setup_env()
    c = small_cell()
    hook = drive_train._StepHook()
    st = drive_train.first_steps(c, SEED, hook)
    trainer, feed = st["trainer"], st["feed"]
    trace_dir = tempfile.mkdtemp(prefix="small_train_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation(_trace.WINDOW)

        def stop():
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()

        ann.__enter__()
        hook.arm(time.time(), steps=1, on_stop=stop)
        trainer.fit(feed, steps=1 << 30)
        os.makedirs(out, exist_ok=True)
        shutil.copy(_trace.find_xplane(trace_dir), os.path.join(out, "raw.xplane.pb"))
        names = _hlo.op_names(trainer.step_program(feed(0)).as_text())
        tr = _trace.load(os.path.join(out, "raw.xplane.pb"))
        ran = {re.match(r"%?([^\s=]+)", n).group(1)
               for evs in tr.ops.values() for n, _, _ in evs}
        with open(os.path.join(out, "hlo.txt"), "w") as f:
            for n in sorted(ran & set(names)):
                f.write(f'%{n} = metadata={{op_name="{names[n]}"}}\n')
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
        shutil.rmtree(st["ckpt"], ignore_errors=True)


def _head(name: str) -> str:
    """An op event's name cut to its instruction, ``%<instr>``; custom
    calls keep theirs whole (outputs and target)."""
    if "custom-call(" in name:
        return name
    return re.match(r"%?[^\s=]+", name).group(0)


def shrink(out: str) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    with open(os.path.join(out, "raw.xplane.pb"), "rb") as f:
        space.ParseFromString(f.read())
    keep = xplane_pb2.XSpace()
    for plane in space.planes:
        device = re.match(r"^/device:TPU:\d+$", plane.name)
        if not (device or plane.name.startswith("/host:CPU")
                or plane.name == "Task Environment"):
            continue
        p = keep.planes.add()
        p.CopyFrom(plane)
        del p.lines[:]
        used = set()
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            events = [e for e in line.events
                      if device or re.match(r"^(train/|bench\.)",
                                            plane.event_metadata[e.metadata_id].name)]
            if not events:
                continue
            ln = p.lines.add()
            ln.CopyFrom(line)
            del ln.events[:]
            for e in events:
                ev = ln.events.add()
                ev.CopyFrom(e)
                del ev.stats[:]
                used.add(e.metadata_id)
        for k in list(p.stat_metadata):
            if k not in {st.metadata_id for st in p.stats}:
                del p.stat_metadata[k]
        for k in list(p.event_metadata):
            if k not in used:
                del p.event_metadata[k]
            else:
                md = p.event_metadata[k]
                md.name = _head(md.name)
                md.display_name = ""
                del md.stats[:]
    with open(os.path.join(ROOT, "bench", "testdata", NAME + ".xplane.pb"), "wb") as f:
        f.write(keep.SerializeToString())
    with open(os.path.join(out, "hlo.txt")) as f:
        named = [line for line in f if 'op_name=""' not in line]
    with open(os.path.join(ROOT, "bench", "testdata", NAME + ".hlo.txt"), "w") as f:
        f.writelines(named)


if __name__ == "__main__":
    {"record": record, "shrink": shrink}[sys.argv[1]](sys.argv[2])
