"""Comm/compute overlap (ISSUE 7): overlap on/off is a SCHEDULING
change only, machine-checked from every side —

1. ZeRO layered prefetch == monolithic gather numerically (bitwise for
   Adam at any dp — per-span psum_scatter sums the same two/four
   operands elementwise; <= 2e-6 for LAMB at dp=4, whose per-leaf norm
   partials regroup across ranks), dp in {2, 4};
2. chunked TP row/column == fused psum (<= 2e-6; bitwise at tp=2 where
   two-term addition commutes) at 2 and 4 chunks;
3. comm BYTES are identical overlap on/off for all three hot paths
   (the APX215 zero-growth acceptance, asserted directly on
   ``comm_report`` so it holds at this test's shapes, not just the
   audit fixture's);
4. the overlapped zero step still compiles to ONE donated executable
   (compile-event counting — the overlap must not split the program);
5. DDP leaf-bucket overlap: bucketed == delayed bitwise, and no
   whole-tree ravel concatenate gates the bucket psums;
6. the registered overlapped executables audit clean (APX217 + the
   re-pinned ledger) — the acceptance criteria in one place.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))

from apex_tpu import train_step
from apex_tpu.analysis.comm_model import comm_report
from apex_tpu.optimizers import functional
from apex_tpu.utils import tree_ravel

shard_map = functools.partial(jax.shard_map, check_vma=False)


@pytest.fixture(autouse=True)
def _restore_parallel_state():
    """The TP helpers initialize a tp=2 topology; leaving it behind
    poisons later suites' audits (they trace ops under the wrong
    world)."""
    yield
    from apex_tpu.transformer import parallel_state
    parallel_state.destroy_model_parallel()


def _params(n_layers=8, d=8, seed=0):
    rng = np.random.RandomState(seed)
    out = {}
    for i in range(n_layers):
        out[f"w{i}"] = jnp.asarray(rng.randn(d, d) * 0.3, jnp.float32)
        out[f"b{i}"] = jnp.asarray(rng.randn(d) * 0.01, jnp.float32)
    return out


def _loss(p, batch):
    h = batch["x"]
    for i in range(sum(1 for k in p if k.startswith("w"))):
        h = jnp.tanh(h @ p[f"w{i}"] + p[f"b{i}"])
    return jnp.mean((h - batch["y"]) ** 2)


def _batch(n=16, d=8, seed=1):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, d), jnp.float32)
    return {"x": x, "y": jnp.tanh(x @ jnp.ones((d, d)) * 0.1)}


def _zero_run(tx, params, batch, dp, prefetch, steps=3):
    """steps of the zero step; returns (losses, final params pytree)."""
    mesh = Mesh(np.array(jax.devices()[:dp]), ("data",))
    state, specs = train_step.init_zero_train_state(
        tx, params, "data", dp, loss_scale="dynamic", prefetch=prefetch)
    step = train_step.make_train_step(_loss, tx, zero=True)

    def body(st, b):
        losses = []
        for _ in range(steps):
            st, l = step(st, b)
            losses.append(l)
        return st, jnp.stack(losses)

    st, losses = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(specs, P()),
        out_specs=(specs, P())))(state, batch)
    return np.asarray(losses), st.params()


@pytest.mark.parametrize("dp", [2, 4])
def test_zero_prefetch_matches_monolithic_adam_bitwise(dp):
    params, batch = _params(), _batch()
    tx = functional.fused_adam(lr=1e-2, weight_decay=0.01)
    ref_losses, ref_params = _zero_run(tx, params, batch, dp, prefetch=0)
    for prefetch in (8, 5):          # per-layer spans + uneven grouping
        losses, out = _zero_run(tx, params, batch, dp, prefetch=prefetch)
        np.testing.assert_array_equal(losses, ref_losses)
        jax.tree.map(lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), out, ref_params)


@pytest.mark.parametrize("dp", [2, 4])
def test_zero_prefetch_matches_monolithic_lamb(dp):
    """LAMB's per-leaf trust-ratio partial sums regroup across ranks
    under the span layout: <= 2e-6.  (dp=2 was pinned bitwise — "two-term
    adds commute" — which also pinned the order in which jax 0.4's
    XLA:CPU summed WITHIN a rank; under jax 0.9 the span layout's local
    sums regroup too and dp=2 differs by 7.5e-9.)"""
    params, batch = _params(), _batch()
    tx = functional.fused_lamb(lr=1e-2, weight_decay=0.01)
    ref_losses, ref_params = _zero_run(tx, params, batch, dp, prefetch=0)
    losses, out = _zero_run(tx, params, batch, dp, prefetch=8)
    tol = 2e-6
    np.testing.assert_allclose(losses, ref_losses, rtol=0, atol=tol)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=0, atol=tol),
        out, ref_params)


def test_zero_prefetch_comm_bytes_identical():
    """APX215 zero-growth, asserted structurally: the per-span gathers
    move exactly the monolithic gather's bytes (and the per-span
    scatters the monolithic scatter's), here at a shape where every
    span pads."""
    params, batch = _params(n_layers=5), _batch()
    tx = functional.fused_adam(lr=1e-2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def traced(prefetch):
        state, specs = train_step.init_zero_train_state(
            tx, params, "data", 2, loss_scale="dynamic",
            prefetch=prefetch)
        step = train_step.make_train_step(_loss, tx, zero=True)
        return comm_report(jax.make_jaxpr(shard_map(
            step, mesh=mesh, in_specs=(specs, P()),
            out_specs=(specs, P())))(state, batch), {"data": 2}), \
            len(state.opt.spans)

    (mono, _), (spans, n_spans) = traced(0), traced(5)
    assert spans["by_collective"]["all_gather@data"] == \
        mono["by_collective"]["all_gather@data"]
    assert spans["by_collective"]["reduce_scatter@data"] == \
        mono["by_collective"]["reduce_scatter@data"]
    assert spans["total_bytes"] == mono["total_bytes"]
    # and the pipeline is real: one gather per span, not one total
    assert n_spans > 1
    assert spans["counts"]["all_gather@data"] == n_spans
    assert mono["counts"]["all_gather@data"] == 1


def test_zero_prefetch_step_compiles_one_donated_executable():
    """Overlap must not split the ONE-donated-executable invariant:
    compile-event counting (auditor-independent, same probe as
    test_zero_train_step)."""
    params, batch = _params(), _batch()
    tx = functional.fused_adam(lr=1e-2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    state, specs = train_step.init_zero_train_state(
        tx, params, "data", 2, loss_scale="dynamic", prefetch=8)
    zstep = train_step.make_train_step(_loss, tx, zero=True)
    sharded = shard_map(zstep, mesh=mesh, in_specs=(specs, P()),
                        out_specs=(specs, P()))
    from jax.sharding import NamedSharding
    state = jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        state, specs)
    step = jax.jit(sharded, donate_argnums=(0,))
    batch = jax.device_put(batch)

    events = []
    from jax._src import monitoring as _mon
    saved = {attr: list(getattr(_mon, attr))
             for attr in dir(_mon)
             if attr.endswith("_listeners")
             and isinstance(getattr(_mon, attr), list)}
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.append(name))
    try:
        jax.jit(lambda x: x * 2)(jnp.ones(3)).block_until_ready()
        jax.clear_caches()
        events.clear()
        jax.block_until_ready(step(state, batch))
        n = sum(1 for e in events if "compile_requests" in e)
        assert n == 1, n
    finally:
        for attr, listeners in saved.items():
            getattr(_mon, attr)[:] = listeners


# --- TP chunked ring pipelines ----------------------------------------------

def _tp_run(chunks, fused=False, tokens=8):
    from apex_tpu.transformer import parallel_state as ps
    from apex_tpu.transformer import tensor_parallel

    ps.destroy_model_parallel()
    ps.initialize_model_parallel(tensor_model_parallel_size_=2)
    mesh = ps.get_mesh()
    col = tensor_parallel.ColumnParallelLinear(
        8, 16, gather_output=False, bias=False, overlap_chunks=chunks,
        gradient_accumulation_fusion=fused)
    row = tensor_parallel.RowParallelLinear(
        16, 8, input_is_parallel=True, bias=False,
        overlap_chunks=chunks, gradient_accumulation_fusion=fused)

    def body(x):
        pc = col.init(jax.random.key(0), x)
        h, _ = col.apply(pc, x)
        pr = row.init(jax.random.key(1), h)

        def loss(x, pc, pr):
            h, _ = col.apply(pc, x)
            y, _ = row.apply(pr, h)
            return jnp.mean(y ** 2)

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(x, pc, pr)

    fn = shard_map(body, mesh=mesh, in_specs=(P(),),
                   out_specs=(P(), (P(), P(), P())))
    x = jnp.asarray(np.linspace(-1, 1, tokens * 8,
                                dtype=np.float32).reshape(tokens, 8))
    return jax.jit(fn)(x), fn, x


@pytest.mark.parametrize("chunks", [2, 4])
@pytest.mark.parametrize("fused", [False, True])
def test_tp_chunked_matches_fused_psum(chunks, fused):
    (ref_l, ref_g), _, _ = _tp_run(1, fused=fused)
    (l, g), _, _ = _tp_run(chunks, fused=fused)
    # tp=2: every ring sum is two-term -> bitwise; keep the 2e-6
    # ceiling the reordering bound promises anyway
    np.testing.assert_allclose(np.asarray(l), np.asarray(ref_l),
                               rtol=0, atol=2e-6)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=2e-6)


def test_tp_chunked_comm_bytes_equal_fused():
    """The ring decomposition moves exactly the fused psums' ring
    bytes: (chunks serialized hops of B/chunks) + the all-gather half
    == 2(n-1)/n * B per psum replaced."""
    _, fn1, x = _tp_run(1)
    rep1 = comm_report(jax.make_jaxpr(fn1)(x), {"tensor": 2})
    for chunks in (2, 4):
        _, fnc, x = _tp_run(chunks)
        repc = comm_report(jax.make_jaxpr(fnc)(x), {"tensor": 2})
        assert repc["total_bytes"] == rep1["total_bytes"], chunks
        assert "psum@tensor" not in repc["by_collective"]
        assert repc["by_collective"]["ppermute@tensor"] > 0
        assert repc["by_collective"]["all_gather@tensor"] > 0


# --- DDP leaf-bucket overlap ------------------------------------------------

def test_ddp_bucketed_matches_delayed_and_overlaps():
    from apex_tpu.parallel.distributed import DistributedDataParallel

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    rng = np.random.RandomState(0)
    grads = {f"w{i}": jnp.asarray(rng.randn(16, 16), jnp.float32)
             for i in range(6)}
    grads.update({f"b{i}": jnp.asarray(rng.randn(16), jnp.float32)
                  for i in range(6)})

    def run(ddp):
        return jax.jit(shard_map(
            lambda g: ddp.reduce_gradients(g), mesh=mesh,
            in_specs=(P(),), out_specs=P()))(grads)

    ref = run(DistributedDataParallel(axis_name="data",
                                      delay_allreduce=True))
    out = run(DistributedDataParallel(axis_name="data",
                                      message_size=4096))
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), ref, out)

    # structural overlap property: the bucketed path has NO whole-tree
    # concatenate (each bucket's psum depends only on its own leaves)
    # and >= 2 psums, at the delayed path's exact byte total
    ddp = DistributedDataParallel(axis_name="data", message_size=4096)
    jaxpr = jax.make_jaxpr(shard_map(
        lambda g: ddp.reduce_gradients(g), mesh=mesh,
        in_specs=(P(),), out_specs=P()))(grads)
    n_total = sum(int(np.prod(v.shape)) for v in grads.values())

    def eqns(j):
        j = getattr(j, "jaxpr", j)
        for e in j.eqns:
            yield e
            for v in e.params.values():
                for s in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(s, "eqns") or hasattr(s, "jaxpr"):
                        yield from eqns(s)

    full_concat = [e for e in eqns(jaxpr)
                   if e.primitive.name == "concatenate"
                   and e.outvars[0].aval.size >= n_total]
    assert not full_concat, \
        "bucketed DDP still ravels the whole tree before any psum"
    rep = comm_report(jaxpr, {"data": 2})
    assert rep["counts"]["psum@data"] >= 2
    ddp_delay = DistributedDataParallel(axis_name="data",
                                        delay_allreduce=True)
    rep_delay = comm_report(jax.make_jaxpr(shard_map(
        lambda g: ddp_delay.reduce_gradients(g), mesh=mesh,
        in_specs=(P(),), out_specs=P()))(grads), {"data": 2})
    assert rep["total_bytes"] == rep_delay["total_bytes"]


# --- the registered overlapped executables (acceptance criteria) ------------

def test_registered_overlap_executables_audit_clean():
    """APX217 confirms overlap on the registered zero + TP executables
    (it runs as part of their audit and emits nothing), the ledger
    matches the committed budget bit-for-bit, and the ZeRO comm
    identity survives the span decomposition."""
    import json

    from apex_tpu.analysis.cli import repo_root
    from apex_tpu.analysis.spmd_audit import (BUDGET_NAME, exec_specs,
                                              run_spmd_audit)

    flagged = {s.name for s in exec_specs() if s.check_overlap}
    # PR 17 adds the tp-sharded fused decode step to the overlap set
    assert flagged == {"train_step_zero", "tp_column_row",
                       "inference_decode_fused_paged_tp2"}
    findings, report = run_spmd_audit(execs=sorted(flagged))
    assert findings == [], [(f.rule, f.message) for f in findings]
    committed = json.loads(
        (repo_root() / BUDGET_NAME).read_text())["executables"]
    for name in flagged:
        assert report["executables"][name] == committed[name], name
    zero = report["executables"]["train_step_zero"]
    assert zero["rs_ag_equals_ar"] is True
    assert zero["collective_counts"]["all_gather@data"] > 1
