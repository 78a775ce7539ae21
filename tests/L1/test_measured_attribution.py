"""ISSUE 14 acceptance: a CPU-driven leg with ``APEX_TPU_PROFILE_DIR``
armed stamps the MEASURED attribution into its capture — category
times summing to the window within the documented tolerance, the
measured-vs-``comm_model`` exposed-comm comparison under
``measured:trace`` provenance — and a run with no trace present stamps
the explicit ``unavailable:`` marker, never zeros."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))

import bench
from apex_tpu.observability.attribution import COVERAGE_TOLERANCE
from apex_tpu.observability.tracing import profile_capture


@pytest.fixture
def captured_leg(tmp_path, monkeypatch):
    """A real (tiny) CPU-profiled leg: a few dispatches of a jitted
    matmul chain under profile_capture, exactly the bench bracket."""
    prof = tmp_path / "prof"
    monkeypatch.setenv("APEX_TPU_PROFILE_DIR", str(prof))

    @jax.jit
    def step(x, w):
        return jnp.tanh(x @ w) @ w.T

    x = jnp.ones((128, 128))
    w = jnp.ones((128, 128))
    with profile_capture(tag="bench_main_fused") as started:
        if not started:
            pytest.skip("profiler unavailable in this process")
        for _ in range(3):
            x = step(x, w)
        jax.block_until_ready(x)
    return str(prof)


def test_cpu_leg_stamps_measured_attribution(captured_leg):
    # the trace is a CPU one; the chip is NAMED so the MFU arithmetic
    # has a peak to divide by (a "cpu" kind yields no MFU — below)
    extras = {"chip": "TPU v5 lite", "compiled_flops": 2 * 128 ** 3 * 2,
              "exposed_comm_model_us": 0.0}
    bench._stamp_measured_attribution(extras, captured_leg, steps=3)
    assert extras["measured_attribution_provenance"] == "measured:trace"
    assert extras["measured_window_us"] > 0
    assert extras["measured_step_us"] == pytest.approx(
        extras["measured_window_us"] / 3)
    assert extras["measured_compute_us"] > 0
    # single-chip CPU leg: no collectives observed -> no fabricated
    # zero-valued _us stamp (the hygiene scrub would drop it anyway)
    assert "measured_exposed_comm_us" not in extras
    # model prediction is 0 (no collectives in the jaxpr): the ratio is
    # undefined, so no drift stamp either — absence, not a made-up 1.0
    assert "exposed_comm_drift_ratio" not in extras
    # measured MFU landed from compiled FLOPs / measured compute time
    assert 0 < extras.get("measured_mfu", 0) <= 1.0
    # the live kind of this host is not in the chip table: no MFU stamp
    cpu_extras = {"chip": "cpu", "compiled_flops": 2 * 128 ** 3 * 2}
    bench._stamp_measured_attribution(cpu_extras, captured_leg, steps=3)
    assert cpu_extras["measured_attribution_provenance"] == "measured:trace"
    assert "measured_mfu" not in cpu_extras

    # acceptance arithmetic: the attributed category times + host gap
    # sum to the measured window within the documented tolerance
    from apex_tpu.observability.attribution import attribute
    from apex_tpu.observability.trace_ingest import load_profile_dirs
    rec = attribute(load_profile_dirs([captured_leg]), steps=3)
    total = sum(rec["categories"].values()) + rec["host_gap_us"]
    assert total == pytest.approx(rec["window_us"],
                                  rel=COVERAGE_TOLERANCE)


def test_model_comparison_rides_measured_provenance(captured_leg):
    """When the comm model DID predict exposed comm (the ZeRO/TP
    legs), the measured-vs-model comparison lands in the attribution
    RECORD — but a 0.0 ratio is withheld from the capture stamp: it
    would become the watch's unbeatable best-prior (ratio vs 0 is
    None, so the series could never regress again)."""
    from apex_tpu.observability.attribution import attribute
    from apex_tpu.observability.trace_ingest import load_profile_dirs
    rec = attribute(load_profile_dirs([captured_leg]), steps=3,
                    model_exposed_comm_us=12.5)
    assert rec["provenance"] == "measured:trace"
    # measured exposure is 0 on one chip -> the honest 0.0 ratio is in
    # the record (and the attribution JSONL event)...
    assert rec["exposed_comm_drift_ratio"] == 0.0
    # ...but NOT in the capture stamp
    extras = {"chip": "cpu", "exposed_comm_model_us": 12.5}
    bench._stamp_measured_attribution(extras, captured_leg, steps=3)
    assert extras["measured_attribution_provenance"] == "measured:trace"
    assert "exposed_comm_drift_ratio" not in extras


def test_no_trace_stamps_unavailable_marker(tmp_path):
    """The degradation face of the acceptance criterion: an armed dir
    with no trace yields the explicit unavailable: marker in the
    capture stamp — and no numeric measured fields at all."""
    empty = tmp_path / "never_captured"
    empty.mkdir()
    extras = {"chip": "cpu", "compiled_flops": 1000}
    bench._stamp_measured_attribution(extras, str(empty), steps=3)
    assert extras["measured_attribution_provenance"] == \
        "unavailable:no-trace-files"
    for key in list(extras):
        assert not key.startswith("measured_w"), key
    assert "measured_step_us" not in extras
    assert "measured_mfu" not in extras
    assert "exposed_comm_drift_ratio" not in extras
