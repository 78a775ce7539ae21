"""Compiled-truth extractor (ISSUE 10): XLA's cost/memory numbers per
executable, with the degradation contract — a backend that cannot
report a number yields an explicit provenance marker and ``None``,
never a fabricated zero."""
import jax
import jax.numpy as jnp
import pytest

from apex_tpu.observability.xla_stats import (
    PROVENANCE_COST_ONLY, PROVENANCE_FULL,
    PROVENANCE_UNAVAILABLE_PREFIX, compile_and_stats,
    stats_from_compiled)


def _matmul(x):
    return jnp.tanh(x @ x)


def test_compile_and_stats_full_provenance():
    x = jnp.ones((32, 32), jnp.float32)
    stats = compile_and_stats(_matmul, (x,))
    assert stats.provenance == PROVENANCE_FULL
    assert not stats.degraded
    # a 32x32x32 matmul is at least 2*32^3 FLOPs
    assert stats.flops >= 2 * 32 ** 3
    assert stats.bytes_accessed > 0
    assert stats.argument_bytes == 32 * 32 * 4
    assert stats.output_bytes == 32 * 32 * 4
    # peak identity: arg + out - alias + temp
    assert stats.peak_hbm_bytes == (
        stats.argument_bytes + stats.output_bytes
        - stats.alias_bytes + stats.temp_bytes)


def test_donation_shows_up_as_alias_bytes():
    x = jnp.ones((64, 64), jnp.float32)
    stats = compile_and_stats(lambda s, g: (s - g, jnp.sum(g)), (x, x),
                              donate_argnums=(0,))
    assert stats.provenance == PROVENANCE_FULL
    assert stats.alias_bytes >= 64 * 64 * 4, \
        "the donated buffer must appear in alias_size_in_bytes"


def test_asdict_drops_none_never_fabricates():
    x = jnp.ones((8, 8), jnp.float32)
    full = compile_and_stats(_matmul, (x,)).asdict()
    assert full["provenance"] == PROVENANCE_FULL
    assert full["flops"] > 0 and full["peak_hbm_bytes"] > 0


class _NoMemCompiled:
    """A compiled artifact whose backend lacks memory_analysis."""

    def __init__(self, real):
        self._real = real

    def cost_analysis(self):
        return self._real.cost_analysis()


class _NothingCompiled:
    """A compiled artifact exposing no analysis at all."""


def test_missing_memory_analysis_degrades_with_marker():
    x = jnp.ones((16, 16), jnp.float32)
    real = jax.jit(_matmul).lower(x).compile()
    stats = stats_from_compiled(_NoMemCompiled(real))
    assert stats.provenance == PROVENANCE_COST_ONLY
    assert stats.degraded
    assert stats.flops > 0                      # cost side still truth
    assert stats.peak_hbm_bytes is None         # NEVER a fabricated 0
    assert stats.temp_bytes is None
    d = stats.asdict()
    assert "peak_hbm_bytes" not in d and "temp_bytes" not in d
    assert d["provenance"] == PROVENANCE_COST_ONLY


def test_partial_cost_model_reports_none_not_zero_bytes():
    """A cost model with flops but no 'bytes accessed' key must yield
    bytes_accessed=None (dropped from the dict), never a fabricated 0."""
    class _FlopsOnly:
        def cost_analysis(self):
            return {"flops": 42.0}

    stats = stats_from_compiled(_FlopsOnly())
    assert stats.flops == 42
    assert stats.bytes_accessed is None
    assert "bytes_accessed" not in stats.asdict()


def test_provenance_rank_ladder():
    from apex_tpu.observability.xla_stats import provenance_rank
    assert provenance_rank(PROVENANCE_FULL) == 2
    assert provenance_rank(PROVENANCE_COST_ONLY) == 1
    assert provenance_rank(PROVENANCE_UNAVAILABLE_PREFIX + "x") == 0


def test_no_cost_analysis_is_unavailable():
    stats = stats_from_compiled(_NothingCompiled())
    assert stats.provenance.startswith(PROVENANCE_UNAVAILABLE_PREFIX)
    assert stats.flops is None and stats.peak_hbm_bytes is None
    assert list(stats.asdict()) == ["provenance"]


def test_raising_memory_analysis_degrades_not_raises():
    x = jnp.ones((16, 16), jnp.float32)
    real = jax.jit(_matmul).lower(x).compile()

    class _Raises:
        def cost_analysis(self):
            return real.cost_analysis()

        def memory_analysis(self):
            raise NotImplementedError("no memory stats on this backend")

    stats = stats_from_compiled(_Raises())
    assert stats.provenance == PROVENANCE_COST_ONLY
    assert stats.peak_hbm_bytes is None


def test_compile_failure_yields_marker_not_exception():
    def broken(x):
        return jax.lax.psum(x, "nonexistent_axis")

    stats = compile_and_stats(broken, (jnp.ones((4,)),))
    assert stats.provenance.startswith(PROVENANCE_UNAVAILABLE_PREFIX)
    assert "compile-failed" in stats.provenance
    assert stats.flops is None


@pytest.mark.parametrize("exec_name", ["train_step_dense"])
def test_ledger_stats_covers_registered_executable(exec_name):
    from apex_tpu.observability.xla_stats import ledger_stats

    out = ledger_stats([exec_name])
    assert exec_name in out
    entry = out[exec_name]
    assert "provenance" in entry
    # this image's CPU backend reports both analyses
    if entry["provenance"] == PROVENANCE_FULL:
        assert entry["flops"] > 0 and entry["peak_hbm_bytes"] > 0
