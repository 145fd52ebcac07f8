"""The ``train_lm`` driver's pieces on the CPU: the token generator, the
FLOP and byte counts of ``mfu.train_lm`` and ``moe.experts_roofline`` at
hand-computed shapes, both trace readers on a hand-built trace and HLO
text, and one small run of `drive_train_lm` against the reference."""
import copy
import os
import statistics
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

from bench import drive_train_lm, harness  # noqa: E402
from bench.metrics import _trace  # noqa: E402

MFU = harness.metric_reader("mfu.train_lm")
ROOF = harness.metric_reader("moe.experts_roofline")
ROUTING = harness.metric_reader("moe.routing_share")
CELL = {"name": "deepseek_v2_lite.train_4k", "config": "deepseek_v2_lite",
        "traffic": "train_4k", "chips": 1}

MIX = {"batch": 2, "seq_len": 64, "distinct_batches": 3, "zipf_exponent": 1.1,
       "doc_len_median": 20, "doc_len_sigma": 1.0, "eos_id": 0}


def test_token_batches_deterministic_and_next_token_labels():
    a = drive_train_lm.token_batches(MIX, 256, jax.random.PRNGKey(1))
    b = drive_train_lm.token_batches(MIX, 256, jax.random.PRNGKey(1))
    c = drive_train_lm.token_batches(MIX, 256, jax.random.PRNGKey(2))
    assert len(a) == 3
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
        np.testing.assert_array_equal(x["labels"], y["labels"])
    assert not np.array_equal(a[0]["tokens"], c[0]["tokens"])
    for x in a:
        t, lab = np.asarray(x["tokens"]), np.asarray(x["labels"])
        assert t.shape == lab.shape == (2, 64) and t.dtype == np.int32
        np.testing.assert_array_equal(t[:, 1:], lab[:, :-1])
        assert t.min() >= 0 and t.max() < 256


def test_token_stream_packs_zipf_documents():
    mix = dict(MIX, batch=4, seq_len=256, distinct_batches=8)
    bs = drive_train_lm.token_batches(mix, 256, jax.random.PRNGKey(3))
    # the stream the batches were cut from: rows of seq_len + 1
    rows = [np.append(np.asarray(b["tokens"][i]), np.asarray(b["labels"][i, -1]))
            for b in bs for i in range(4)]
    stream = np.concatenate(rows)
    eos = np.flatnonzero(stream == 0)
    lengths = np.diff(eos) - 1
    # 8,224 tokens in documents of mean ~33 tokens, each with its EOS
    assert 180 < len(eos) < 320 and eos[-1] > len(stream) - 200
    assert 14 <= statistics.median(lengths) <= 28
    ids, counts = np.unique(stream[stream != 0], return_counts=True)
    assert ids[np.argmax(counts)] == 1  # rank 1 is the id after EOS
    # Zipf(1.1): rank 1 is about twice as frequent as rank 2
    assert 1.5 < counts[ids == 1][0] / counts[ids == 2][0] < 2.8


TINY = {"hidden_size": 4, "num_attention_heads": 2, "kv_lora_rank": 2,
        "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2,
        "moe_intermediate_size": 3, "first_k_dense_replace": 1, "num_hidden_layers": 2,
        "intermediate_size": 5, "router_experts": 4, "n_shared_experts": 1,
        "n_routed_experts": 2, "vocab_size": 10}


def test_mfu_step_flops_by_hand():
    # per token: 2 layers x (projections 160 + causal core 48), dense FFN 120,
    # router 32 + shared expert 72, head 80 = 720; routed rows 6 x 72;
    # forward and backward three times that
    assert MFU.step_flops(TINY, {"batch": 1, "seq_len": 4}, 6) == 3 * (4 * 720 + 6 * 72)
    ctx = {"config": TINY, "mix": {"batch": 1, "seq_len": 4}, "steps": 2, "window_s": 2.0,
           "peaks": {"bf16_flops": 9936.0}, "moe.rows_held": [6, 6]}
    assert MFU.read(ctx) == pytest.approx(100.0)
    assert MFU.read(dict(ctx, **{"moe.rows_held": []})) is None


def test_expert_cost_by_hand():
    # 6 rows x (3 matmuls of 4 x 3): 432 FLOPs a pass; rows read and written
    # (6 x (8 + 6) + 6 x 7) plus one layer's 2 experts' weights (72), in bf16
    f, by = ROOF.expert_cost(TINY, 6)
    assert f == 4 * 432
    assert by == 4 * 2 * (84 + 42 + 72)


HLO = """HloModule jit_train_step, is_scheduled=true

ENTRY %main.1 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %ragged-dot.1 = bf16[8]{0} ragged-dot(f32[8]{0} %p0), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/moe.experts/ragged_dot_general"}
  %ragged-dot.2 = bf16[8]{0} ragged-dot(f32[8]{0} %p0), metadata={op_name="jit(train_step)/transpose(jvp())/while/body/checkpoint/rematted_computation/moe.experts/ragged_dot_general"}
  %fusion.3 = bf16[8]{0} fusion(f32[8]{0} %p0), metadata={op_name="jit(train_step)/transpose(jvp(moe.experts))/mul"}
  %sort.4 = s32[8]{0} sort(f32[8]{0} %p0), metadata={op_name="jit(train_step)/jvp()/while/body/moe.dispatch/jit(argsort)/sort"}
  %fusion.5 = f32[8]{0} fusion(f32[8]{0} %p0), metadata={op_name="jit(train_step)/jvp()/while/body/moe.route/top_k"}
  %gather.6 = bf16[8]{0} gather(f32[8]{0} %p0), metadata={op_name="jit(train_step)/transpose(jvp())/while/body/moe.combine/jit(_take)/gather"}
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %p0), metadata={op_name="jit(train_step)/jvp()/while/body/mla.attention/while/body/exp"}
  ROOT %fusion.8 = f32[8]{0} fusion(f32[8]{0} %p0), metadata={op_name="jit(train_step)/mul"}
}
"""


def _synthetic():
    ev = lambda instr, s, e: (f"%{instr} = f32[8]{{0}} op(...)", s, e)
    ops = [ev("ragged-dot.1", 0.0, 1.0), ev("ragged-dot.2", 1.0, 3.0),
           ev("fusion.3", 3.0, 6.0), ev("sort.4", 6.0, 7.0), ev("fusion.5", 7.0, 7.5),
           ev("gather.6", 7.5, 8.0), ev("fusion.7", 8.0, 9.5), ev("fusion.8", 9.5, 10.0)]
    tr = _trace.Trace(0.0, 10.0, ops={0: ops}, modules={0: [("jit_train_step(1)", 0.0, 10.0)]})
    return {"trace": tr, "hlo": HLO, "config": TINY, "moe.rows_held": [6],
            "peaks": {"bf16_flops": 1e3, "hbm_bytes_per_s": 1e3}}


def test_routing_share_on_a_synthetic_step():
    # route 0.5 + dispatch 1 + combine 0.5 of 10 busy seconds
    assert ROUTING.read(_synthetic()) == pytest.approx(20.0)


def test_experts_roofline_on_a_synthetic_step():
    # FLOPs bound: 1,728 / 1e3 = 1.728 s against 6 s under moe.experts
    assert ROOF.read(_synthetic()) == pytest.approx(100.0 * 1.728 / 6.0)
    assert ROOF.read(dict(_synthetic(), **{"moe.rows_held": None})) is None


def small_cell():
    c = harness.load_cell(CELL)
    cfg = copy.deepcopy(c["config"])
    cfg.update(hidden_size=64, intermediate_size=96, kv_lora_rank=32, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, num_attention_heads=4,
               moe_intermediate_size=32, num_hidden_layers=3, n_routed_experts=2,
               router_experts=8, num_experts_per_tok=3, expert_offset=2, vocab_size=256)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], original_max_position_embeddings=64)
    p = cfg["program"]
    p.update(num_layers=3, d_model=64, d_ff=96, vocab=256)
    p["attn"] = dict(p["attn"], num_heads=4, num_kv_heads=4, head_dim=16,
                     mla=dict(kv_lora_rank=32, q_lora_rank=None, qk_nope_head_dim=16,
                              qk_rope_head_dim=8, v_head_dim=16),
                     rope_scaling=dict(p["attn"]["rope_scaling"],
                                       original_max_position_embeddings=64))
    p["moe"] = dict(p["moe"], num_experts=8, top_k=3, num_shared=2, expert_ffn=32,
                    shared_ffn=64, experts_held=2, expert_offset=2)
    mix = dict(c["mix"], batch=2, seq_len=96, distinct_batches=4, doc_len_median=40)
    return dict(c, config=cfg, mix=mix)


def test_driver_runs_a_small_cell_against_the_reference():
    c = small_cell()
    res = drive_train_lm.run(c, 2 ** 40 + 12345, 1.0, False, t_start=time.perf_counter(),
                             counter=harness.CompileCounter())
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["info"]["compiles_in_window"]["misses"] == 0
    rows = res["ctx"]["moe.rows_held"]
    # 2 of 8 experts held, top-3 of 2 x 96 tokens, 2 MoE layers
    assert len(rows) == res["attempted"] and 0 < min(rows) and max(rows) <= 2 * 96 * 2 * 2
