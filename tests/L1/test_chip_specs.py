"""Chip-spec single source of truth (ISSUE 10 satellite): every peak
number resolves through ``apex_tpu.chip_specs`` — no second copy of the
table anywhere, the comm-model default comes from it, bench resolves
through it, and the capture scrubber's HBM bound derives from it."""
import re
from pathlib import Path

import pytest

from apex_tpu import chip_specs

REPO = Path(__file__).resolve().parents[2]


def test_table_shape_and_physics():
    assert chip_specs.DEFAULT_CHIP in chip_specs.CHIP_SPECS
    for key, spec in chip_specs.CHIP_SPECS.items():
        assert spec.key == key
        assert spec.bf16_tflops > 0
        assert spec.hbm_gbps > 0
        assert spec.hbm_bytes >= 8 * 1024 ** 3   # no chip under 8 GiB
        # VMEM (ISSUE 16: the pallas_audit envelope bound): on-chip
        # vector memory is MiB-scale, orders of magnitude under HBM
        assert 16 * 1024 ** 2 <= spec.vmem_bytes < spec.hbm_bytes // 8


def test_find_spec_matches_device_kind_spellings():
    assert chip_specs.find_spec("TPU v5e").key == "v5e"
    assert chip_specs.find_spec("TPU v5 lite").key == "v5lite"
    assert chip_specs.find_spec("TPU v4").key == "v4"
    # a kind outside the table is an error, never the default chip
    for kind in ("Colossus MK1", "cpu", None):
        with pytest.raises(KeyError, match="not in apex_tpu.chip_specs"):
            chip_specs.find_spec(kind)


def test_local_spec_raises_on_unknown_live_device(monkeypatch):
    """The live device of this host is the CPU platform — not a chip in
    the table — so ``local_spec()`` raises; and it resolves a live
    device that IS in the table."""
    import jax

    with pytest.raises(KeyError, match="'cpu'"):
        chip_specs.local_spec()

    class _Dev:
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    assert chip_specs.local_spec() is chip_specs.CHIP_SPECS["v5lite"]
    _Dev.device_kind = "TPU v9 mega"
    with pytest.raises(KeyError, match="TPU v9 mega"):
        chip_specs.local_spec()


def test_no_second_copy_of_the_numbers():
    """The literal peak figures may appear ONLY in chip_specs.py —
    bench.py lost its _CHIP_SPECS dict and comm_model its bare 197.0
    default; a reintroduced copy fails here."""
    import bench
    assert not hasattr(bench, "_CHIP_SPECS"), \
        "bench.py regrew its own chip table — use apex_tpu.chip_specs"
    # the distinctive peak-TFLOPs literals of the table
    literals = {f"{s.bf16_tflops:g}" for s in
                chip_specs.CHIP_SPECS.values()}
    assert literals >= {"197", "275", "459", "918"}
    for rel in ("bench.py", "apex_tpu/analysis/comm_model.py",
                "apex_tpu/observability/train.py",
                "apex_tpu/observability/serve.py"):
        text = (REPO / rel).read_text(encoding="utf-8")
        for lit in literals:
            hits = [m for m in
                    re.finditer(rf"\b{re.escape(lit)}(?:\.0)?\b", text)]
            assert not hits, (
                f"{rel} carries the chip peak literal {lit} — resolve "
                f"through apex_tpu.chip_specs instead")


def test_bench_chip_spec_resolves_through_the_table():
    """On this CPU host the --inner cpu legs price against the nominal
    chip, asked for by name."""
    import bench
    tflops, hbm = bench._chip_spec()
    spec = chip_specs.default_spec()
    assert (tflops, hbm) == (spec.bf16_tflops, spec.hbm_gbps)


def test_comm_model_default_tflops_is_the_table_default():
    import jax
    import jax.numpy as jnp
    from apex_tpu.analysis.comm_model import step_time_estimate

    closed = jax.make_jaxpr(lambda x: x @ x)(jnp.ones((64, 64)))
    default = step_time_estimate(closed, {})
    explicit = step_time_estimate(
        closed, {}, tflops=chip_specs.default_spec().bf16_tflops)
    assert default == explicit
    # a different peak must actually change the estimate (the default
    # is not hardcoded inside)
    other = step_time_estimate(closed, {}, tflops=1.0)
    assert other["compute_us"] > default["compute_us"]


def test_scrub_rejects_nonphysical_compiled_fields():
    """ISSUE 10 satellite: the capture scrubber drops compiled stamps
    that are not physics — FLOPs <= 0, peak HBM <= 0 or beyond the
    chip's capacity — and keeps valid ones."""
    import bench

    v5e = chip_specs.CHIP_SPECS["v5e"]
    good = {"chip": "TPU v5e", "compiled_flops": 123456,
            "compiled_peak_hbm_bytes": v5e.hbm_bytes // 2,
            "compiled_stats_provenance": "xla:cost+memory"}
    assert bench._scrub_capture_values(good) == good

    bad = {"chip": "TPU v5e", "compiled_flops": 0,
           "compiled_peak_hbm_bytes": v5e.hbm_bytes + 1}
    scrubbed = bench._scrub_capture_values(bad)
    assert "compiled_flops" not in scrubbed
    assert "compiled_peak_hbm_bytes" not in scrubbed
    assert scrubbed["chip"] == "TPU v5e"

    neg = {"compiled_flops": -5, "compiled_peak_hbm_bytes": -1}
    assert bench._scrub_capture_values(neg) == {}

    # unknown chip: the bound is the LARGEST capacity in the table —
    # permissive, so a big-HBM chip's valid stamp survives
    big = max(s.hbm_bytes for s in chip_specs.CHIP_SPECS.values())
    unknown = {"chip": "FutureTPU", "compiled_peak_hbm_bytes": big}
    assert bench._scrub_capture_values(unknown) == unknown
    over = {"chip": "FutureTPU", "compiled_peak_hbm_bytes": big + 1}
    assert "compiled_peak_hbm_bytes" not in \
        bench._scrub_capture_values(over)


def test_scrub_rejects_nonphysical_vmem_model_fields():
    """ISSUE 16 satellite: a ``*vmem_model_bytes`` stamp (the
    pallas_audit envelope riding the fused-decode capture) must be
    positive and fit the capture's chip's VMEM — a poisoned value
    vanishes, a valid one survives."""
    import bench

    v5e = chip_specs.CHIP_SPECS["v5e"]
    good = {"chip": "TPU v5e",
            "fused_vmem_model_bytes": v5e.vmem_bytes // 2}
    assert bench._scrub_capture_values(good) == good

    poisoned = {"chip": "TPU v5e",
                "fused_vmem_model_bytes": v5e.vmem_bytes + 1,
                "other_vmem_model_bytes": 0,
                "spec_vmem_model_bytes": -4096}
    scrubbed = bench._scrub_capture_values(poisoned)
    assert scrubbed == {"chip": "TPU v5e"}

    # unknown chip: permissive largest-capacity bound, same policy as
    # the HBM rule
    big = max(s.vmem_bytes for s in chip_specs.CHIP_SPECS.values())
    unknown = {"chip": "FutureTPU", "fused_vmem_model_bytes": big}
    assert bench._scrub_capture_values(unknown) == unknown
    over = {"chip": "FutureTPU", "fused_vmem_model_bytes": big + 1}
    assert "fused_vmem_model_bytes" not in \
        bench._scrub_capture_values(over)


def test_scrub_rejects_nonphysical_host_tier_bytes_fields():
    """ISSUE 18 satellite: a ``*host_tier_bytes`` stamp is a HOST-RAM
    budget, not an HBM quantity — 0 (tier off) is valid and must
    survive, negatives and beyond-any-host values vanish, and a
    legitimate budget far above the chip's HBM must NOT trip the
    chip-selected HBM bound (that rule is exact-key)."""
    import bench
    from apex_tpu.observability.capture_hygiene import (
        MAX_PLAUSIBLE_HOST_TIER_BYTES)

    v5e = chip_specs.CHIP_SPECS["v5e"]
    # a 256 GiB host budget dwarfs v5e HBM and is still physical
    good = {"chip": "TPU v5e",
            "infer_host_tier_bytes": 256 * 1024 ** 3,
            "infer_swap_batch_pages": 8}
    assert good["infer_host_tier_bytes"] > v5e.hbm_bytes
    assert bench._scrub_capture_values(good) == good

    off = {"chip": "TPU v5e", "infer_host_tier_bytes": 0}
    assert bench._scrub_capture_values(off) == off

    at_bound = {"infer_host_tier_bytes":
                MAX_PLAUSIBLE_HOST_TIER_BYTES}
    assert bench._scrub_capture_values(at_bound) == at_bound

    poisoned = {"chip": "TPU v5e",
                "infer_host_tier_bytes":
                MAX_PLAUSIBLE_HOST_TIER_BYTES + 1,
                "other_host_tier_bytes": -1}
    assert bench._scrub_capture_values(poisoned) == {"chip": "TPU v5e"}


def test_scrub_existing_rules_still_hold():
    import bench
    payload = {"flash_attn_us": 0.0, "adam_speedup": 1e9,
               "tokens_per_s": -3.0, "mfu": 0.48}
    assert bench._scrub_capture_values(payload) == {"mfu": 0.48}


def test_scrub_rejects_nan_and_inf_in_any_numeric_field():
    """ISSUE 11 satellite: NaN evaluates False against EVERY range
    comparison, so before the finite gate a poisoned capture sailed
    through checks written as rejections (``speedup > MAX`` is False
    for NaN; ``flops <= 0`` is False for NaN) — now nonfinite values
    vanish from any numeric field, range-checked or not."""
    import math

    import bench

    nan, inf = float("nan"), float("inf")
    poisoned = {
        "mfu": nan,                       # no range rule at all
        "adam_speedup": nan,              # rule is `> MAX` — False for NaN
        "compiled_flops": nan,            # rule is `<= 0` — False for NaN
        "tokens_per_s": inf,
        "flash_attn_us": inf,
        "loss": -inf,
        "value": 42.0,
        "label": "kept",
        "nested": {"bert_mfu": nan, "bert_tokens_per_s": 10.0},
    }
    out = bench._scrub_capture_values(poisoned)
    assert out == {"value": 42.0, "label": "kept",
                   "nested": {"bert_tokens_per_s": 10.0}}
    for v in [v for d in (out, out["nested"]) for v in d.values()
              if isinstance(v, float)]:
        assert math.isfinite(v)
