"""The serving driver end to end on the CPU at a toy size, both loop kinds;
a token altered where it is produced comes out NOT correct, and so does the
control."""
import time

import numpy as np
import pytest

from benchmark import harness, reduce, traffic
from benchmark.drivers import serve as D


def test_open_loop_rehearsal(run_toy):
    r = run_toy("toy.chat", seconds=2.0, trace=1)
    assert r["correct"] and r["failed"] == 0
    assert r["attempted"] == 12          # 6 requests/s for 2 s, all due
    # counts only: no time, rate, share or memory reading off the chip
    pool = {f"pool_pages_{what}_{stat}" for what in ("live", "attended")
            for stat in ("peak", "mean")}
    assert set(r["metrics"]) == pool | {
        "compiles_in_window.serve", "slot_occupancy", "toy_passes"}
    value = {k: v["value"] for k, v in r["metrics"].items()}
    assert 0 < value["pool_pages_attended_mean"] \
        <= value["pool_pages_attended_peak"] \
        <= value["pool_pages_live_peak"] <= 64     # toy-chat.json's pool
    assert r["metrics"]["compiles_in_window.serve"]["value"] == 0.0
    assert 0.0 < r["metrics"]["slot_occupancy"]["value"] <= 100.0
    assert [c["name"] for c in r["checks"]] == [
        "served_token_gap", "requests_unfinished", "token_count_wrong"]


def test_backlog_rehearsal_keeps_the_queue_deep(run_toy):
    r = run_toy("toy.backlog", seconds=1.5)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 8
    assert "serve_ttft_p95_ms" not in r["metrics"]


def test_a_lapped_backlog_never_runs_dry_inside_the_window(toy_root,
                                                           monkeypatch):
    """A lap of 5 requests under a server that finishes many more: the
    queue stays ``depth`` deep until the window closes."""
    cell = harness.load_cell("toy.backlog", toy_root)
    cell.mix["arrivals"] = dict(cell.mix["arrivals"],
                                requests_per_s_bound=2.5)
    seconds, depth = 2.0, cell.mix["arrivals"]["depth"]
    requests = traffic.serve_requests(cell.mix, 5, seconds,
                                      cell.config["token_ids"])
    assert requests.lap == len(requests) == 5
    engine, sched, _ = D.build(cell, 5)
    D.warm_up(sched, cell, traffic.rng_for(5, stream=2))
    queued, one_pass = [], D.Loop.one_pass

    def watched(self):
        queued.append((time.perf_counter(), len(self.sched.queue)))
        one_pass(self)
    monkeypatch.setattr(D.Loop, "one_pass", watched)
    out = D.measure(cell, sched, requests, seconds)
    lo, hi = out["facts"]["window"]
    inside = [n for t, n in queued if t < hi]
    assert len(inside) > 5 and set(inside) == {depth}
    by_uid = out["by_uid"]
    indices = sorted(r["index"] for r in by_uid.values())
    assert len(indices) > 3 * requests.lap        # every lap's are attempted
    assert indices == list(range(len(indices)))   # unique, none skipped
    assert all(r["reason"] == "length" for r in by_uid.values())
    # the sample is found by index across laps: the prompt that was sent
    seqs = D.sample_sequences(cell, 5, requests, by_uid, out["served"])
    sent = {(r["prompt_len"], r["new_tokens"]) for r in by_uid.values()}
    assert len(seqs) == cell.config["correct"]["sample_requests"]
    assert all((len(p), len(t)) in sent for p, t in seqs)
    late = max(by_uid, key=lambda u: by_uid[u]["index"])
    assert len(requests[by_uid[late]["index"]].prompt) \
        == by_uid[late]["prompt_len"]
    limit = cell.config["correct"]["limits"]["served_token_gap"]
    _, shapes = harness.load_binding(cell).model_of(cell.config)
    assert D.served_token_gap(cell, shapes, 5, seqs)["widest"] <= limit


def test_measure_stamps_every_token_of_every_request(toy_root):
    cell = harness.load_cell("toy.chat", toy_root)
    requests = traffic.serve_requests(cell.mix, 4, 1.5,
                                      cell.config["token_ids"])
    engine, sched, _ = D.build(cell, 4)
    D.warm_up(sched, cell, traffic.rng_for(4, stream=2))
    out = D.measure(cell, sched, requests, 1.5)
    facts = out["facts"]
    assert facts["compiles_in_window"] == 0
    assert len(facts["requests"]) == len(requests)
    for r in facts["requests"]:
        assert r["reason"] == "length"
        assert len(r["token_times"]) == r["new_tokens"]
        assert r["due"] <= r["sent"] <= r["admitted"] \
            <= r["token_times"][0]
        assert r["token_times"] == sorted(r["token_times"])
    assert all(t >= 0 for t in reduce.serve_ttft_ms(facts))
    assert reduce.serve_tokens_per_s(facts) > 0
    assert sum(p[3] for p in facts["passes"]) == sum(
        r["new_tokens"] for r in facts["requests"])
    # what the window served is what the reference is shown
    seqs = D.sample_sequences(cell, 4, requests, out["by_uid"],
                              out["served"])
    longest = max(len(r.prompt) + r.new_tokens for r in requests)
    assert len(seqs) == cell.config["correct"]["sample_requests"]
    assert len(seqs[0][0]) + len(seqs[0][1]) == longest


def test_fault_a_token_altered_where_it_is_produced(run_toy, monkeypatch):
    from apex_tpu.inference import InferenceEngine
    real = InferenceEngine.decode

    def altered(self, cache, last_tokens, active=None):
        cache, toks, logits, truncated = real(self, cache, last_tokens,
                                              active)
        return cache, (np.asarray(toks) + 1) % 128, logits, truncated
    monkeypatch.setattr(InferenceEngine, "decode", altered)
    r = run_toy("toy.chat", seconds=1.5)
    assert not r["correct"]
    gap = next(c for c in r["checks"] if c["name"] == "served_token_gap")
    assert gap["value"] > gap["limit"]


def test_control_in_the_precision_below_is_not_correct(toy_root):
    """The token the fp8 reference puts first lies further below the fp32
    reference's best than the limit allows."""
    cell = harness.load_cell("toy.chat", toy_root)
    binding = harness.load_binding(cell)
    _, shapes = binding.model_of(cell.config)
    rng = np.random.RandomState(0)
    seqs = [(rng.randint(0, 128, size=40).astype(np.int32),
             rng.randint(0, 128, size=60).astype(np.int32))
            for _ in range(3)]
    limit = cell.config["correct"]["limits"]["served_token_gap"]
    low = D.served_token_gap(cell, shapes, 7, seqs, quant="fp8")
    assert low["tokens"] == 180 and low["widest"] > limit
    # the reference's own first tokens have no gap at all
    import jax.numpy as jnp
    from benchmark import weights
    from benchmark.references import gpt_lm
    w = binding.reference_weights(cell.config, weights.make(shapes, 7))
    prompt = seqs[0][0]
    first = int(jnp.argmax(gpt_lm.logits(
        w, jnp.asarray(prompt), heads=4)[len(prompt) - 1]))
    own = D.served_token_gap(cell, shapes, 7,
                             [(prompt, np.asarray([first]))])
    assert own["widest"] == 0.0
