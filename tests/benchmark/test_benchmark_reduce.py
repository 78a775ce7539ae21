"""From stamps and counters to metric values, checked by hand."""
import types

import pytest

from benchmark import counts, reduce, trace

PEAKS = {"bf16_flops_per_s": 1e6, "hbm_bytes_per_s": 1e4}


def _train_run(traced_steps=0):
    facts = {"step_starts": [0.0, 1.0, 2.0, 3.0],
             "step_ends": [0.9, 1.8, 2.9, 4.0], "tokens_per_step": 10,
             "labels_per_row": 2, "traced_steps": traced_steps,
             "memory_peak_bytes": 3 * 2 ** 30}
    cell = types.SimpleNamespace(
        chips=1, mix={"seq": 8},
        config={"hidden_size": 4, "intermediate_size": 16,
                "num_hidden_layers": 1, "vocab_size": 32})
    return types.SimpleNamespace(facts=facts, cell=cell, peaks=PEAKS,
                                 trace=None)


def test_train_rate_is_all_tokens_over_first_start_to_last_end():
    assert reduce.train_tokens_per_s(_train_run().facts) == 40 / 4.0
    # the pace at which steps are seen to end: 0.9, 1.1, 1.1 s apart
    assert reduce.train_step_ms_p50(_train_run().facts) \
        == pytest.approx(1100.0)
    assert reduce.train_step_ms_p50(_train_run(3).facts) is None


def test_traced_steps_are_left_out_of_the_rate():
    # the profiler came on before the last step: three steps, 0.0 .. 2.9
    assert reduce.train_tokens_per_s(_train_run(1).facts) \
        == pytest.approx(30 / 2.9)


def test_train_step_mfu_by_hand():
    run = _train_run()
    per_token = counts.train_flops_per_token(
        hidden=4, ffn=16, layers=1, seq=8, vocab=32, head_share=2 / 8)
    assert reduce.train_step_mfu(run) == pytest.approx(
        100 * per_token * 10.0 / 1e6)


def _serve_facts():
    return {
        "window": (0.0, 10.0), "drained": 12.0, "slots": 4,
        "counters": {"decode_steps": 10.0, "idle_slot_tokens": 10.0},
        "passes": [(0.0, 0.3, 1, 1, 1), (0.3, 0.4, 0, 2, 2),
                   (0.4, 0.6, 0, 2, 2), (0.6, 10.7, 0, 0, 2)],
        "pool": [(6, 1), (9, 3), (12, 2), (40, 30)],
        "trace_started": None, "trace_stopped": None,
        "requests": [
            {"due": 0.0, "sent": 0.1, "admitted": 0.2, "prompt_len": 5,
             "new_tokens": 3, "token_times": [1.0, 1.5, 2.5]},
            {"due": 8.0, "sent": 8.0, "admitted": 8.5, "prompt_len": 7,
             "new_tokens": 2, "token_times": [9.0, 11.0]},
            {"due": 9.0, "sent": 9.5, "admitted": None, "prompt_len": 4,
             "new_tokens": 2, "token_times": []}]}


def test_serve_rate_counts_only_what_the_window_completed():
    # prompts 5 + 7 prefilled, tokens at 1.0 1.5 2.5 9.0 (11.0 is late)
    assert reduce.serve_tokens_per_s(_serve_facts()) == (12 + 4) / 10.0


def test_ttft_is_from_due_and_a_request_never_served_counts_as_worst():
    assert reduce.serve_ttft_ms(_serve_facts()) == [1000.0, 1000.0, 3000.0]
    assert reduce.queue_wait_ms(_serve_facts()) == [200.0, 500.0, 3000.0]
    assert reduce.generator_lag_ms(_serve_facts()) == [100.0, 0.0, 500.0]


def test_gaps_pool_every_request():
    assert sorted(reduce.serve_gaps_ms(_serve_facts())) \
        == [500.0, 1000.0, 2000.0]


def test_decode_step_is_the_median_of_passes_that_only_decoded():
    assert reduce.decode_step_ms_p50(_serve_facts()) \
        == pytest.approx(150.0)


def test_slot_occupancy_from_the_programs_counters():
    assert reduce.slot_occupancy(_serve_facts()) == 75.0


def _serve_run(facts, tr=None):
    cell = types.SimpleNamespace(
        chips=1, mix={},
        config={"hidden_size": 4, "intermediate_size": 16,
                "num_hidden_layers": 2, "vocab_size": 32})
    return types.SimpleNamespace(facts=facts, cell=cell, peaks=PEAKS,
                                 trace=tr)


def test_serve_step_mfu_by_hand():
    m = dict(hidden=4, ffn=16, layers=2, vocab=32)
    flops = (counts.prefill_flops(5, **m) + counts.prefill_flops(7, **m)
             + counts.decode_flops(6, **m) + counts.decode_flops(7, **m))
    assert reduce.serve_step_mfu(_serve_run(_serve_facts())) \
        == pytest.approx(100 * flops / 10.0 / 1e6)


def test_rooflines_read_nothing_without_a_trace():
    run = _serve_run(_serve_facts())
    assert reduce.decode_roofline(run, "decode") is None
    assert reduce.prefill_roofline(run, "prefill") is None
    assert reduce.device_idle_share(run) is None


def test_decode_roofline_by_hand():
    facts = dict(_serve_facts(), trace_started=1.2, trace_stopped=3.0)
    E = trace.Event
    tr = trace.Trace(ops={0: [E("x", 0, 4e9)]}, host=[], modules={0: [
        E("jit_decode_fn(1)", 0, 1e9), E("jit_decode_fn(1)", 2e9, 3e9),
        E("jit_prefill_fn(2)", 3e9, 4e9)]})
    # tokens stamped in 1.2..3.0: contexts 6 and 7; two decode programs
    m = dict(hidden=4, ffn=16, layers=2, vocab=32)
    least = (2 * counts.weight_stream_bytes(**m)
             + 13 * counts.kv_bytes_per_token(hidden=4, layers=2)) / 1e4
    got = reduce.decode_roofline(_serve_run(facts, tr), r"decode")
    assert got == pytest.approx(100 * least / 2.0)
    assert reduce.device_idle_share(_serve_run(facts, tr)) == 0.0


def test_pool_pages_over_the_passes_that_ended_in_the_window():
    # the fourth pass ended after the window closed at 10.0
    facts = _serve_facts()
    assert reduce.pool_pages(facts, 0, max) == 12.0
    assert reduce.pool_pages(facts, 1, max) == 3.0
    assert reduce.pool_pages(facts, 0, lambda v: sum(v) / len(v)) == 9.0
    assert reduce.pool_pages(dict(facts, window=(0.0, 0.1)), 0, max) is None


@pytest.mark.parametrize("calls,want", [
    (2, [5, 7]),        # as many device calls as first tokens stamped
    (3, [5, 7, 4]),     # one ran inside, its stamp fell after the stop
    (1, [7]),           # the first one stamped ran before the start
    (4, None), (0, None),   # any other difference: nothing is read
], ids=["equal", "one-more", "one-fewer", "two-more", "two-fewer"])
def test_traced_prefills_follow_the_devices_count(calls, want):
    facts = dict(_serve_facts(), trace_started=0.5, trace_stopped=9.5)
    facts["requests"][2]["token_times"] = [9.8, 10.2]
    assert reduce.traced_prefills(facts, calls) == want


def test_traced_prefills_need_a_stamp_after_the_stop_to_take_one_more():
    facts = dict(_serve_facts(), trace_started=0.5, trace_stopped=9.5)
    assert reduce.traced_prefills(facts, 3) is None
    assert reduce.traced_prefills(_serve_facts(), 2) is None   # no trace


def test_prefill_roofline_by_hand():
    facts = dict(_serve_facts(), trace_started=0.5, trace_stopped=9.5)
    E = trace.Event
    tr = trace.Trace(ops={0: [E("x", 0, 4e9)]}, host=[], modules={0: [
        E("jit_prefill_fn(1)", 0, 1e9), E("jit_decode_fn(1)", 1e9, 2e9),
        E("jit_prefill_fn(2)", 2e9, 4e9)]})
    m = dict(hidden=4, ffn=16, layers=2, vocab=32)
    least = sum(counts.roofline_seconds(
        counts.prefill_flops(n, **m), counts.prefill_bytes(n, **m), PEAKS)
        for n in (5, 7))
    got = reduce.prefill_roofline(_serve_run(facts, tr), r"^jit_prefill")
    assert got == pytest.approx(100 * least / 3.0)
    # three device calls against two stamps and none after the stop
    tr.modules[0].append(E("jit_prefill_fn(1)", 4e9, 5e9))
    assert reduce.prefill_roofline(
        _serve_run(facts, tr), r"^jit_prefill") is None
