"""The single source of truth for TPU chip peak specs.

Every capacity number the repo prices against hardware — bench MFU and
HBM rooflines, ``comm_model.step_time_estimate``'s compute roofline,
the ``train_mfu`` telemetry gauge, and the capture-hygiene scrub bound
on compiled peak-HBM stamps — resolves through this table.  Before
ISSUE 10 the numbers lived twice (``bench.py::_CHIP_SPECS`` and a bare
``tflops=197.0`` default inside ``comm_model``) and could drift apart
silently; ``tests/L1/test_chip_specs.py`` now pins that no second copy
exists.

Conservative public figures: bf16 matmul peak (TFLOP/s), HBM bandwidth
(GB/s), and HBM capacity (bytes) per chip generation.  Pure data — this
module must import without jax so the trace-only analysis engines can
use it.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional

__all__ = ["ChipSpec", "CHIP_SPECS", "DEFAULT_CHIP", "match_spec",
           "find_spec", "default_spec", "local_spec"]

_GiB = 1024 ** 3


_MiB = 1024 ** 2


@dataclass(frozen=True)
class ChipSpec:
    key: str                 # substring matched against device_kind
    bf16_tflops: float       # peak bf16 matmul TFLOP/s per chip
    hbm_gbps: float          # peak HBM bandwidth GB/s per chip
    hbm_bytes: int           # HBM capacity per chip
    vmem_bytes: int          # VMEM capacity per core (the pallas_audit
    #                          envelope bound; the ceiling production
    #                          kernels compile against via
    #                          vmem_limit_bytes, NOT the compiler's
    #                          conservative per-buffer scoping default)


CHIP_SPECS: Dict[str, ChipSpec] = {s.key: s for s in [
    ChipSpec("v4", 275.0, 1228.0, 32 * _GiB, 128 * _MiB),
    ChipSpec("v5e", 197.0, 819.0, 16 * _GiB, 128 * _MiB),
    # "v5lite"/"v6lite" are alternate device_kind SPELLINGS of v5e/v6e
    # ("TPU v5 lite" is what real v5e hosts report — PERF.md round-3),
    # not smaller parts: every figure must match the e-series twin or
    # capacity-bound scrubs resolve differently by spelling.
    ChipSpec("v5lite", 197.0, 819.0, 16 * _GiB, 128 * _MiB),
    ChipSpec("v5p", 459.0, 2765.0, 95 * _GiB, 128 * _MiB),
    ChipSpec("v6e", 918.0, 1640.0, 32 * _GiB, 128 * _MiB),
    ChipSpec("v6lite", 918.0, 1640.0, 32 * _GiB, 128 * _MiB),
]}

#: the NOMINAL chip for trace-only analysis code that prices a program
#: without a device (``pallas_audit``'s VMEM envelope, ``comm_model``'s
#: roofline): callers ask for it by name through :func:`default_spec`.
#: Never a fallback for a live device — see :func:`local_spec`.
DEFAULT_CHIP = "v5e"


def default_spec() -> ChipSpec:
    return CHIP_SPECS[DEFAULT_CHIP]


def match_spec(device_kind: Optional[str]) -> Optional[ChipSpec]:
    """The spec whose key substring-matches ``device_kind`` (the
    ``jax.Device.device_kind`` string, any case/spacing), or ``None``
    on a miss — the one matching loop; callers choose their own miss
    policy (:func:`find_spec` raises, bench's scrub bound takes the
    largest capacity)."""
    kind = (device_kind or "").lower().replace(" ", "")
    for key, spec in CHIP_SPECS.items():
        if key in kind:
            return spec
    return None


def find_spec(device_kind: Optional[str]) -> ChipSpec:
    """Like :func:`match_spec`, but a miss is an error: a utilization
    priced against the peak of a chip the program is not running on is
    wrong by an unknown factor, so there is no default."""
    spec = match_spec(device_kind)
    if spec is None:
        raise KeyError(
            f"device_kind {device_kind!r} is not in apex_tpu.chip_specs."
            f"CHIP_SPECS ({sorted(CHIP_SPECS)}); add its peaks with "
            f"their source, or ask for the nominal chip by name "
            f"(default_spec())")
    return spec


def local_spec() -> ChipSpec:
    """The spec of the first live jax device (initializes the backend;
    host loops only).  A device whose ``device_kind`` is not in the
    table — the CPU platform included — raises."""
    import jax

    return find_spec(jax.devices()[0].device_kind)
