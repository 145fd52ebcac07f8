"""The benchmark's reduction from profiler traces to metrics, and its FLOP
and byte functions, checked on a small trace recorded on a TPU v5e
(``bench/testdata/small_trace.xplane.pb``: one jit of the packed FLARE
kernel's forward and gradient at B=2, H=8, N=1024, M=256, D=8, one paged
attention launch, one 512x512 matmul) and on hand counts."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.metrics import _trace  # noqa: E402

SMALL = os.path.join(ROOT, "bench", "testdata", "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def small():
    return _trace.load(SMALL)


def test_small_trace_planes(small):
    assert list(small.ops) == [0]
    assert len(small.ops[0]) == 29
    assert len(small.modules[0]) == 3
    assert small.window_s > 0


def test_busy_is_union_inside_window(small):
    busy = _trace.busy_s(small)
    total = _trace.time_s(_trace.clip(small.ops[0], small.t0, small.t1))
    assert 0 < busy <= total
    assert busy <= small.window_s
    assert _trace.idle_share(small) == pytest.approx(1 - busy / small.window_s)


def test_kernel_events_by_name(small):
    calls = _trace.matching(small, r"tpu_custom_call")
    assert [_trace.op_label(n) for n, _, _ in calls] == ["jvp__", "transpose_jvp___", "_lambda_"]
    assert _trace.time_s(calls) == pytest.approx(
        9.3452e-05 + 8.1965e-05 + 2.5946e-05, rel=1e-3)


def test_top_ops_self_time(small):
    top = _trace.top_ops(small, 3)
    assert [n for n, _ in top] == ["jvp__", "transpose_jvp___", "_lambda_"]
    assert sum(t for _, t in _trace.top_ops(small, 100)) == pytest.approx(
        _trace.busy_s(small), rel=1e-6)


def test_packed_launch_classification(small):
    roof = harness.metric_reader("flare_packed_roofline")
    kinds = [k for k, _ in roof.launches(small)]
    assert kinds == ["fwd", "bwd"]


@pytest.mark.parametrize("events,want", [
    ([("a", 0.0, 1.0), ("b", 0.5, 2.0)], 2.0),
    ([("a", 0.0, 1.0), ("b", 3.0, 4.0)], 2.0),
    ([("a", 0.0, 4.0), ("b", 1.0, 2.0)], 4.0),
    ([("a", -1.0, 0.5), ("b", 9.0, 11.0)], 1.5),
])
def test_union_by_hand(events, want):
    assert _trace.union_s(events, 0.0, 10.0) == pytest.approx(want)


def test_self_times_nested():
    ev = [("loop", 0.0, 10.0), ("body1", 1.0, 3.0), ("body2", 4.0, 8.0),
          ("inner", 5.0, 6.0), ("after", 11.0, 12.0)]
    got = dict(_trace.self_times(ev))
    assert got == pytest.approx({"loop": 4.0, "body1": 2.0, "body2": 3.0,
                                 "inner": 1.0, "after": 1.0})


def test_idle_gaps_named_by_host_annotation():
    tr = _trace.Trace(0.0, 10.0, ops={0: [("x", 1.0, 2.0), ("y", 6.0, 7.0)]},
                      host=[("bench.step", 2.0, 5.0), ("serve/prefill_b64x1", 7.0, 10.0),
                            ("$python", 0.0, 10.0)])
    assert _trace.idle_gaps(tr, 3) == [["bench.step", 4.0], ["serve/prefill_b64x1", 3.0],
                                       ["host:unannotated", 1.0]]


def test_inside_modules():
    ops = [("k", 1.0, 1.5), ("k", 3.0, 3.2), ("k", 5.5, 5.6)]
    mods = [("m", 0.5, 2.0), ("m", 5.0, 6.0)]
    assert _trace.inside(ops, mods) == [ops[0], ops[2]]


def test_train_flops_by_hand():
    mfu = harness.metric_reader("mfu.train")
    cfg = {"hidden_size": 4, "num_heads": 2, "num_latents": 3, "num_blocks": 1,
           "kv_proj_layers": 1, "mlp_layers": 1, "io_layers": 1, "in_dim": 3, "out_dim": 1}
    mix = {"batch": 2, "grid": 2}                      # N = 4 points
    # mixer: 6*M*N*D*H = 6*3*4*2*2; per point: K,V ResMLPs 2*(16+16+16) each,
    # out proj 2*16, MLP 2*(16+16+16); io: in 2*(12+16+16), out 2*(16+16+4)
    block = 6 * 3 * 4 * 2 * 2 + 4 * (2 * 96 + 32 + 96)
    io = 4 * (88 + 72)
    assert mfu.step_flops(cfg, mix) == 3 * 2 * (block + io)


def test_packed_call_cost_by_hand():
    roof = harness.metric_reader("flare_packed_roofline")
    cfg = {"hidden_size": 16, "num_heads": 2, "num_latents": 4}
    mix = {"batch": 3, "grid": 5}                      # N = 25, D = 8, G = 6
    f, b = roof.call_cost(cfg, mix, "fwd")
    assert f == 6 * 4 * 25 * 8 * 6
    assert b == 4 * (3 * 6 * 25 * 8 + 2 * 4 * 8 + 6 * 4 * 8 + 2 * 6 * 4)
    f, b = roof.call_cost(cfg, mix, "bwd")
    assert f == 12 * 4 * 25 * 8 * 6
    assert b == 4 * (6 * 6 * 25 * 8 + 2 * 4 * 8 + 2 * 6 * 4 * 8 + 2 * 6 * 4)


def test_leaf_norm_gap():
    gap, leaf, med = harness.leaf_norm_gaps({"a": 1.1, "b": 2.0, "c": 0.0},
                                      {"a": 1.0, "b": 2.0, "c": 1e-9})
    # leaf c: |0 - 1e-9| over the median norm 1.0; leaf a: 0.1 over 1.0
    assert leaf == "a" and gap == pytest.approx(0.1) and med == pytest.approx(1e-9)
