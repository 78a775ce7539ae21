"""The yardstick's arithmetic, each count checked by hand at a small size."""
import json

import pytest

from benchmark import counts, harness, trace


def test_layer_matmul_params_by_hand():
    # h=4, ffn=16: qkv 3*16 + out 16 + mlp 2*64 = 192
    assert counts.layer_matmul_params(4, 16) == 192


def test_train_flops_per_token_by_hand():
    # 1 layer h=4 ffn=16 seq=8 vocab=32, every position labelled:
    # body 6*192 = 1152; attention 3*4*8*4 = 384; head 6*(16+128) = 864
    got = counts.train_flops_per_token(hidden=4, ffn=16, layers=1, seq=8,
                                       vocab=32, head_share=1.0)
    assert got == 1152 + 384 + 864


def test_train_flops_bert_large_matches_the_issue_floor():
    # ISSUE 24: 41.8 ms at 197 TFLOP/s for 4,096 tokens counts 6*N with
    # the embeddings; the least-work count must be below it and near it
    per_token = counts.train_flops_per_token(
        hidden=1024, ffn=4096, layers=24, seq=128, vocab=30592,
        head_share=19 / 128)
    ms = per_token * 4096 / 197e12 * 1e3
    assert 36.0 < ms < 41.8


def test_prefill_and_decode_flops_by_hand():
    m = dict(hidden=4, ffn=16, layers=2, vocab=32)
    # 3 tokens: matmuls 2*3*2*192 = 2304; keys seen 1+2+3 = 6 ->
    # 4*4*2*6 = 192; head 2*4*32 = 256
    assert counts.prefill_flops(3, **m) == 2304 + 192 + 256
    # one token at context 10: 2*2*192 + 4*4*2*10 + 256
    assert counts.decode_flops(10, **m) == 768 + 320 + 256
    # prefilling n tokens one by one costs the same attention as at once
    one_by_one = sum(counts.decode_flops(c, **m) for c in (1, 2, 3))
    assert one_by_one - 2 * 256 == counts.prefill_flops(3, **m)


def test_bytes_by_hand():
    m = dict(hidden=4, ffn=16, layers=2, vocab=32)
    assert counts.weight_stream_bytes(**m) == 2 * (2 * 192 + 128)
    assert counts.kv_bytes_per_token(hidden=4, layers=2) == 32
    assert counts.prefill_bytes(3, **m) == 1024 + 3 * 32
    assert counts.lamb_update_bytes(10) == 280


def test_gpt3_decode_floor_matches_the_issue():
    # ISSUE 24: "~3.2 ms weight-stream floor" for GPT-3 1.3B decode
    b = counts.weight_stream_bytes(hidden=2048, ffn=8192, layers=24,
                                   vocab=51200)
    assert 3.0e-3 < b / 819e9 < 3.4e-3


def test_roofline_takes_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.roofline_seconds(200.0, 10.0, peaks) == 2.0
    assert counts.roofline_seconds(50.0, 30.0, peaks) == 3.0


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_peaks_of_the_v5e(kind):
    p = harness.peaks_for(kind)
    assert (p["bf16_flops_per_s"], p["hbm_bytes_per_s"],
            p["hbm_bytes"]) == (197e12, 819e9, 16 * 2 ** 30)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(harness.Refused, match="no default"):
        harness.peaks_for(kind)


def test_peaks_table_names_its_source():
    with open(harness.HERE / "peaks.json") as f:
        assert "cloud.google.com" in json.load(f)["source"]


# -- interval arithmetic ------------------------------------------------------

def test_union_merges_and_drops_empty():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 3), (7, 8)]) \
        == [(0, 3), (5, 8)]
    assert trace.total([(0, 3), (5, 8)]) == 6


def test_subtract_and_gaps():
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) \
        == [(0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert trace.gaps([(2, 3), (5, 7)], 0, 10) == [(0, 2), (3, 5), (7, 10)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


def _toy_trace():
    E = trace.Event
    ops = [E("fusion.1", 0, 40), E("lamb", 40, 60), E("all-reduce", 50, 90),
           E("fusion.1", 100, 140)]
    return trace.Trace(
        ops={0: ops}, modules={0: [E("jit_step(1)", 0, 90),
                                   E("jit_step(1)", 100, 140)]},
        host=[E("bench.step", 0, 10), E("bench.sync", 10, 95),
              E("bench.make_batch", 95, 100)])


def test_busy_window_and_top_ops():
    t = _toy_trace()
    assert trace.window(t) == (0, 140)
    assert trace.busy_seconds(t) == {0: pytest.approx(130e-9)}
    top = trace.top_ops(t, n=2)
    assert top[0] == ["fusion.1", pytest.approx(80e-9)]
    assert top[1] == ["all-reduce", pytest.approx(40e-9)]


def test_idle_gap_goes_to_the_host_span_that_covers_it():
    # the one idle interval, 90..100, is half under sync, half make_batch
    gaps = dict(trace.idle_gaps_by_host_span(_toy_trace()))
    assert gaps == {"bench.sync": pytest.approx(5e-9),
                    "bench.make_batch": pytest.approx(5e-9)}


# -- a small recorded trace ---------------------------------------------------

def _recorded():
    from pathlib import Path
    doc = json.loads((Path(__file__).parent / "fixtures"
                      / "trace_train_first400.json").read_text())
    def unpack(rows):
        return [trace.Event(n, s, e) for n, s, e in rows]
    return trace.Trace(
        ops={int(k): unpack(v) for k, v in doc["ops"].items()},
        modules={int(k): unpack(v) for k, v in doc["modules"].items()},
        host=unpack(doc["host"]))


def test_recorded_trace_reduces():
    t = _recorded()
    lo, hi = trace.window(t)
    busy = trace.busy_seconds(t)[0]
    assert 0.9 * (hi - lo) * 1e-9 < busy <= (hi - lo) * 1e-9
    assert len(t.ops[0]) == 400
    # the idle the device shows is given to the host span that covers it
    idle = dict(trace.idle_gaps_by_host_span(t))
    assert set(idle) <= {"bench.sync", "bench.step", "(unannotated)"}
    assert sum(idle.values()) == pytest.approx((hi - lo) * 1e-9 - busy)


def test_recorded_trace_names():
    t = _recorded()
    # operations over the flat optimizer buffers are told by the flat
    # length in their type; convert.325 is the master -> bf16 pass
    secs, n = trace.matching_seconds(t.ops[0], r"(f32|bf16)\[334820352\]")
    assert n == 10 and secs > 0
    names = [trace.short_name(e.name) for e in t.ops[0]]
    assert "convert.325 convert" in names
    assert all(len(n) <= 80 and "[" not in n for n in names)
    top = trace.top_ops(t, n=3)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1]
    assert trace.matching_seconds(t.modules[0], r"^jit_step")[1] == 1


def test_recorded_trace_layernorm_kernels():
    # the step's custom-calls whose type lacks the flat length: 11
    # LayerNorm forwards among the first 400 operations, 8 us each
    import types
    from benchmark import reduce
    run = types.SimpleNamespace(trace=_recorded(), facts={
        "n_params": 334820352, "traced_steps": 1})
    assert reduce.layernorm_ms(run) == pytest.approx(0.087858)
    assert reduce.lamb_update_ms(run) > reduce.layernorm_ms(run)
