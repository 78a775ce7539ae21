"""The bench orchestrator has NO fallback: when no TPU answers, or a
leg fails, ``main()`` returns non-zero and prints no capture (the
2026-07/08 driver records were CPU numbers under device metric names,
published by the fallback this replaces).  Leg execution is mocked; this
tests the orchestrator plumbing and the capture-hygiene scrubber only.
"""
import json
import os
import sys
from unittest import mock

import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))

import bench


def _run_main(probe_ok, legs):
    """(exit code, stdout lines) of ``bench.main()`` with the probe and
    the per-leg subprocesses mocked; ``legs`` maps leg -> (obj, err)."""
    def fake_leg(mode, leg, timeout, key=None):
        assert mode == "tpu"
        return legs.get(leg, ({"_leg": leg, f"{leg}_us": 1.0}, None))

    with mock.patch.object(bench, "_probe_tpu",
                           return_value=(probe_ok, None if probe_ok
                                         else "probe err")), \
         mock.patch.object(bench, "_run_leg", side_effect=fake_leg), \
         mock.patch("builtins.print") as p:
        rc = bench.main()
    out = [c.args[0] for c in p.call_args_list
           if c.kwargs.get("file") in (None, sys.stdout)]
    return rc, out


_MAIN_OK = ({"metric": "m", "value": 2.0, "unit": "u", "vs_baseline": 1.4,
             "extras": {"chip": "TPU v5 lite"}}, None)


def test_no_tpu_exits_nonzero_and_prints_no_capture():
    rc, out = _run_main(False, {"main": _MAIN_OK})
    assert rc != 0
    assert out == []


def test_failed_leg_exits_nonzero_and_prints_no_capture():
    for bad in ("main", "ln"):
        rc, out = _run_main(True, {"main": _MAIN_OK,
                                   bad: (None, f"tpu:{bad} rc=1: boom")})
        assert rc != 0
        assert out == []


def test_healthy_capture_merges_every_leg():
    rc, out = _run_main(True, {"main": _MAIN_OK})
    assert rc == 0 and len(out) == 1
    cap = json.loads(out[0])
    assert cap["value"] == 2.0
    assert cap["extras"]["backend"] == "tpu"
    assert cap["extras"]["ln_us"] == 1.0 and "_leg" not in cap["extras"]
    # nothing from an earlier capture rides along
    for k in ("value_tpu_best", "vs_baseline_tpu_best_recorded",
              "value_provenance", "error"):
        assert k not in cap
    assert "recorded_tpu_captures" not in cap["extras"]


def test_inner_tpu_raises_without_a_tpu():
    """``--inner tpu`` on a host where JAX came up on the CPU must not
    run the toy-size branch of a leg under the TPU's name."""
    with pytest.raises(RuntimeError, match="not 'tpu'"):
        bench._bench_setup(force_cpu=False)


def test_probe_accepts_only_the_tpu_platform():
    class _P:
        returncode = 0
        stderr = ""

        def __init__(self, out):
            self.stdout = out

    for out, ok in (("BACKEND=tpu\n", True), ("BACKEND=cpu\n", False),
                    ("BACKEND=tpu_like\n", False)):
        with mock.patch.object(bench.subprocess, "run",
                               return_value=_P(out)):
            assert bench._probe_tpu()[0] is ok


def test_capture_scrubber_rejects_impossible_values():
    """The capture-hygiene validator, against the actually-corrupt
    committed capture (r5 verdict weak #1/#6): flash_attn_us 0.0 (timing
    collapsed inside RTT jitter), flash_attn_speedup 89198634x (ratio to
    a collapsed ~0), moe sweep us_gather 0.0 — all physically impossible
    and must not be republished; plausible siblings survive."""
    import pathlib
    cap = (pathlib.Path(bench.__file__).resolve().parent /
           "bench_captures" / "r5_watch_capture_001.json")
    payload = json.loads(cap.read_text())
    extras = bench._scrub_capture_values(payload["extras"])
    assert "flash_attn_us" not in extras           # == 0.0
    assert "flash_attn_speedup" not in extras      # > 100x
    # plausible values pass through untouched, including nested rows
    assert extras["flash_attn_us_median"] == \
        payload["extras"]["flash_attn_us_median"]
    assert extras["adam_speedup"] == payload["extras"]["adam_speedup"]
    assert extras["adam_gbps"] == payload["extras"]["adam_gbps"]
    assert len(extras["moe_dispatch_sweep"]) == \
        len(payload["extras"]["moe_dispatch_sweep"])
    for row in extras["moe_dispatch_sweep"]:
        assert "us_gather" not in row              # == 0.0 in every row
        assert row["us"] > 0 and row["tokens_per_s"] > 0


def test_capture_scrubber_covers_inference_fields():
    """ISSUE 4 satellite: the tokens/sec and decode-latency fields the
    infer leg emits get the same hygiene — 0.0 µs latencies and
    non-physical throughputs (<= 0 or beyond the 1e8 ceiling) vanish;
    plausible values survive untouched."""
    payload = {
        "infer_decode_token_us": 0.0,              # RTT collapse
        "infer_decode_token_us_median": 812.5,     # plausible
        "infer_decode_tokens_per_s": 9.8e9,        # tokens / ~0 s
        "infer_prefill_tokens_per_s": -3.0,        # tokens / negative
        "infer_prefill_us": 4402.1,
        "nested": [{"tokens_per_s": 0.0, "us": 11.0},
                   {"tokens_per_s": 123456.0}],
        "bert_tokens_per_s": 36353.9,              # existing field OK
        "infer_shape": [8, 512, 8, 1024],          # not a measurement
    }
    out = bench._scrub_capture_values(payload)
    assert "infer_decode_token_us" not in out
    assert "infer_decode_tokens_per_s" not in out
    assert "infer_prefill_tokens_per_s" not in out
    assert out["infer_decode_token_us_median"] == 812.5
    assert out["infer_prefill_us"] == 4402.1
    assert "tokens_per_s" not in out["nested"][0]
    assert out["nested"][0]["us"] == 11.0
    assert out["nested"][1]["tokens_per_s"] == 123456.0
    assert out["bert_tokens_per_s"] == 36353.9
    assert out["infer_shape"] == [8, 512, 8, 1024]


def test_capture_scrubber_rejects_nonphysical_ttft_and_latency():
    """ISSUE 8 satellite: the serve-telemetry latencies the infer leg
    now stamps (TTFT, per-token decode with host read) get the full
    physicality check — negatives (clock skew) and > 1 h single-request
    latencies (a hung dispatch / seconds-vs-us unit bug) vanish alongside
    the existing 0.0 artifact; plausible values and the non-latency
    telemetry counters survive."""
    payload = {
        "infer_serve_ttft_us": -125.0,             # clock-skew garbage
        "infer_serve_decode_token_us": 7.2e9,      # > 1 h per token
        "infer_prefill_us": 0.0,                   # RTT collapse (old rule)
        "infer_decode_token_us": 812.5,            # plausible
        "infer_serve_requests": 9,                 # counter: not latency
        "infer_serve_recompiles": 0,               # pinned-zero counter
    }
    out = bench._scrub_capture_values(payload)
    assert "infer_serve_ttft_us" not in out
    assert "infer_serve_decode_token_us" not in out
    assert "infer_prefill_us" not in out
    assert out["infer_decode_token_us"] == 812.5
    assert out["infer_serve_requests"] == 9
    assert out["infer_serve_recompiles"] == 0      # 0 is a VALUE here


def test_capture_scrubber_rejects_nonphysical_speculation_stats():
    """ISSUE 15 satellite: speculation stats get the physicality
    check — an acceptance rate outside (0, 1] (accepted is a subset
    of drafted) and an effective tokens/s BELOW its same-capture
    floor stamp (every verify step emits at least the bonus token, so
    effective >= floor on the same clock) are measurement artifacts;
    plausible values and the non-measurement stamps survive."""
    payload = {
        "infer_spec_acceptance_rate": 1.7,            # > 1: impossible
        "infer_spec_oracle_acceptance_rate": -0.2,    # negative
        "infer_spec_effective_tokens_per_s": 400.0,   # below its floor
        "infer_spec_floor_tokens_per_s": 650.0,
        "infer_spec_base_tokens_per_s": 768.6,        # plausible
        "infer_spec_k": 4,                            # knob stamp
        "infer_spec_verify_steps": 9,                 # counter
        "nested": [{"spec_acceptance_rate": 0.31}],   # plausible
    }
    out = bench._scrub_capture_values(payload)
    assert "infer_spec_acceptance_rate" not in out
    assert "infer_spec_oracle_acceptance_rate" not in out
    assert "infer_spec_effective_tokens_per_s" not in out
    assert out["infer_spec_floor_tokens_per_s"] == 650.0
    assert out["infer_spec_base_tokens_per_s"] == 768.6
    assert out["infer_spec_k"] == 4
    assert out["infer_spec_verify_steps"] == 9
    assert out["nested"][0]["spec_acceptance_rate"] == 0.31
    # a consistent pair passes through untouched
    ok = bench._scrub_capture_values(
        {"infer_spec_effective_tokens_per_s": 1154.1,
         "infer_spec_floor_tokens_per_s": 632.9,
         "infer_spec_acceptance_rate": 0.21})
    assert ok["infer_spec_effective_tokens_per_s"] == 1154.1
    assert ok["infer_spec_acceptance_rate"] == 0.21


def test_overrides_forwarded_to_inner_leg_subprocess():
    """--override knobs must reach the per-leg subprocesses — the
    orchestrator invocation is what the experiment runners use."""
    captured = {}

    class _P:
        returncode = 0
        stdout = '{"_leg": "attn", "ok": 1}\n'
        stderr = ""

    def fake_run(cmd, **kw):
        captured["cmd"] = cmd
        return _P()

    with mock.patch.object(bench, "_OVERRIDES",
                           {"batch": 16, "block_q": 512}), \
         mock.patch.object(bench.subprocess, "run", fake_run):
        obj, err = bench._run_leg("tpu", "attn", 60)
    assert err is None and obj["ok"] == 1
    cmd = captured["cmd"]
    assert cmd[cmd.index("--override") + 1] == "batch=16"
    assert "block_q=512" in cmd


def test_timed_median_fallback_on_rtt_collapse():
    """A min sample inside the RTT jitter must not publish a ~0 best
    (r5: flash_attn_us 0.0 / moe us_gather 0.0): best falls back to the
    median when it reads < 0.25x of it."""
    # perf_counter pairs per rep -> samples .061, .30, .31, .32, .33:
    # the first rep finishes inside RTT jitter
    times = [0.0, 0.061, 0.1, 0.40, 0.5, 0.81, 0.9, 1.22, 1.3, 1.63]
    with mock.patch.object(bench.time, "perf_counter",
                           side_effect=times):
        t = bench._timed(lambda: None, iters=10, rtt=0.060)
    # min per-iter would be (0.061-0.060)/10 = 1e-4 — under 0.25x the
    # median (0.31-0.060)/10 = 0.025, so the median wins
    assert t.best == t.median
    assert t.best > 1e-4


def test_timed_normal_min_kept():
    times = [0.0, 0.50, 0.6, 1.12, 1.2, 1.74, 1.8, 2.36, 2.4, 3.02]
    with mock.patch.object(bench.time, "perf_counter",
                           side_effect=times):
        t = bench._timed(lambda: None, iters=10, rtt=0.060)
    assert t.best != t.median          # fallback must NOT have fired
    assert abs(t.best - (0.50 - 0.060) / 10) < 1e-9


def test_orchestrator_parent_never_initialises_a_backend():
    """One process per chip: the orchestrator holds no backend, so its
    leg children can take the TPU.  Under a platform name that cannot
    initialise, importing ``bench`` and running ``main()`` (probe and
    legs mocked) must still work — any device touch would raise."""
    import subprocess
    code = (
        "from unittest import mock; import bench, jax\n"
        "leg = lambda mode, leg, t, key=None: ("
        "{'metric': 'm', 'value': 1.0, 'extras': {}} if leg == 'main' "
        "else {'_leg': leg}, None)\n"
        "with mock.patch.object(bench, '_probe_tpu', "
        "return_value=(True, None)), mock.patch.object(bench, '_run_leg', "
        "side_effect=leg):\n"
        "    assert bench.main() == 0\n"
        "try:\n"
        "    jax.devices()\n"
        "except RuntimeError:\n"
        "    print('NO_BACKEND')\n")
    repo = os.path.join(os.path.dirname(__file__), "..", "..")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=repo, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="no_such_platform"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip().endswith("NO_BACKEND")
