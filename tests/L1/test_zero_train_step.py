"""ZeRO-sharded train step: structural + equivalence regressions
(ISSUE 3 acceptance; structural checks delegated to the SPMD auditor
in ISSUE 5).

1. the SPMD auditor audits the registered ``train_step_zero``
   executable clean and its ledger shows the fused
   computation-collective shape — ``all_gather`` (params into the
   forward), ``reduce_scatter`` (autodiff's transpose of that gather
   IS the grad reduce-scatter), the replica-uniform ``pmax``'d
   overflow flag, verified donation, and the RS+AG==AR byte identity —
   plus the one property the auditor does not own: NO param-leaf
   re-ravel concatenate;
2. independent cross-check: the whole zero step compiles to ONE
   donated executable, measured by compile-event counting (not derived
   from the jaxpr the auditor already walked);
3. a dp=2 zero run matches the dense single-device replay on loss and
   post-update master, including an overflow-skip step where the
   poison hits only ONE rank's shard (the pmax'd found_inf must stop
   every rank);
4. ``init_zero_train_state`` round-trips: the global view's
   ``params()`` reproduces the construction pytree, and the spec tree
   marks exactly the dp-shardable buffers.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))

from apex_tpu import train_step
from apex_tpu.optimizers import functional
from apex_tpu.utils import tree_ravel

DP = 2


def _make_params(seed=0, n_layers=8):
    rng = np.random.RandomState(seed)
    params = {}
    d = 8
    for i in range(n_layers):
        params[f"w{i}"] = jnp.asarray(rng.randn(d, d) * 0.3, jnp.float32)
        params[f"b{i}"] = jnp.asarray(rng.randn(d) * 0.01, jnp.float32)
    return params


def _loss_fn(params, batch):
    x, y = batch["x"], batch["y"]
    h = x
    for i in range(len([k for k in params if k.startswith("w")])):
        h = jnp.tanh(h @ params[f"w{i}"] + params[f"b{i}"])
    return jnp.mean((h - y) ** 2)


def _batch(seed=1, n=16):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, 8), jnp.float32)
    return {"x": x, "y": jnp.tanh(x @ jnp.ones((8, 8)) * 0.1)}


def _iter_eqns(jaxpr):
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from _iter_eqns(sub)


def _zero_setup(loss_scale=None, placed=False, make_tx=functional.fused_adam,
                loss_fn=_loss_fn):
    params = _make_params()
    tx = make_tx(lr=1e-2)
    mesh = Mesh(np.array(jax.devices()[:DP]), ("data",))
    state, specs = train_step.init_zero_train_state(
        tx, params, "data", DP, loss_scale=loss_scale)
    step = train_step.make_train_step(loss_fn, tx, zero=True)
    sharded = functools.partial(jax.shard_map, check_vma=False)(
        step, mesh=mesh, in_specs=(specs, P()), out_specs=(specs, P()))
    if placed:
        # commit the state onto the mesh layout up front, as a real
        # training loop's init does — otherwise the first call ALSO
        # compiles the host->mesh placement transfer, which would be
        # counted as a second "executable" below
        from jax.sharding import NamedSharding
        state = jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            state, specs)
    return params, tx, state, sharded


def test_zero_spmd_audit_clean_and_ledger():
    """The SPMD auditor owns the collective/donation/uniformity
    assertions: the registered zero executable audits clean, and its
    comm ledger carries exactly the fused computation-collective shape
    PR 3 built (AG + RS + pmax, RS+AG==AR).  The one structural
    property outside the auditor's scope — no param-leaf re-ravel
    concatenate — stays a direct jaxpr scan."""
    from apex_tpu.analysis.spmd_audit import run_spmd_audit

    findings, report = run_spmd_audit(execs=["train_step_zero"])
    assert findings == [], [(f.rule, f.message) for f in findings]
    entry = report["executables"]["train_step_zero"]
    by = entry["by_collective"]
    assert any(k.startswith("all_gather@data") for k in by), by
    assert any(k.startswith(("reduce_scatter@data", "psum_scatter@data"))
               for k in by), by
    assert any(k.startswith("pmax@data") for k in by), by
    # the PERF.md round-6 accounting, machine-checked on the jaxpr
    assert entry["rs_ag_equals_ar"] is True

    # auditor-independent: no grad re-ravel concatenate (PR 2's
    # flat-native property; the auditor does not model it)
    params, tx, state, sharded = _zero_setup(loss_scale="dynamic")
    jaxpr = jax.make_jaxpr(sharded)(state, _batch())
    n_leaves = len(jax.tree.leaves(params))
    n_params = int(tree_ravel(params)[0].size)
    reravel = [
        e for e in _iter_eqns(jaxpr)
        if e.primitive.name == "concatenate"
        and e.outvars[0].aval.size >= n_params
        and len(e.invars) >= n_leaves // 2]
    assert not reravel, "zero step rebuilt flat grads by concatenation"


def test_zero_lamb_selects_only_the_master_and_skips_bitwise():
    """ZeRO twin of ``test_lamb_step_selects_only_the_master``: each
    rank's LAMB update holds ONE shard-length select (the master's; the
    moments are predicated inside ``apex_lamb_stage1``), and an
    overflowed step leaves master and both moments bit-identical on
    every rank with the scale halved."""
    def loss_fn(p, batch):
        return _loss_fn(p, batch) + jnp.sum(p["w0"]) * batch["poison"]

    _, _, state, sharded = _zero_setup(
        loss_scale="dynamic", make_tx=functional.fused_lamb,
        loss_fn=loss_fn)
    clean = dict(_batch(), poison=jnp.float32(0.0))
    poisoned = dict(_batch(), poison=jnp.float32(1e38))
    shard_len = state.opt.master.shape[0] // DP
    selects = [e for e in _iter_eqns(jax.make_jaxpr(sharded)(state, clean))
               if e.primitive.name == "select_n"
               and e.outvars[0].aval.shape == (shard_len,)]
    assert len(selects) == 1, selects

    step = jax.jit(sharded)
    state, _ = step(state, clean)
    before = {"master": np.asarray(state.opt.master),
              **{k: np.asarray(v) for k, v in state.opt.slots.items()}}
    assert sorted(before) == ["exp_avg", "exp_avg_sq", "master"]
    assert all(np.any(v) for v in before.values())
    scale_before = float(state.scaler.loss_scale)
    state, _ = step(state, poisoned)
    after = {"master": state.opt.master, **state.opt.slots}
    for k, v in before.items():
        np.testing.assert_array_equal(
            np.asarray(after[k]).view(np.uint32), v.view(np.uint32),
            err_msg=k)
    assert float(state.scaler.loss_scale) == scale_before * 0.5
    state, _ = step(state, clean)       # and the loop recovers
    assert not np.array_equal(np.asarray(state.opt.master),
                              before["master"])


def test_zero_step_compiles_one_donated_executable():
    # the auditor-INDEPENDENT cross-check: compile-event counting sees
    # the actual executable count, not the jaxpr the auditor walks
    _, _, state, sharded = _zero_setup(loss_scale="dynamic", placed=True)
    step = jax.jit(sharded, donate_argnums=(0,))
    batch = jax.device_put(_batch())

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


def test_zero_matches_dense_including_rank_local_overflow():
    """dp=2 vs dense: loss trace, final master, AND an overflow step
    whose poison reaches only rank 1's grad shard — rank 0 must skip on
    the pmax'd flag alone or the masters diverge."""
    params = _make_params()
    tx = functional.fused_adam(lr=1e-2)
    B = 8

    def loss_fn(p, batch):
        return _loss_fn(p, batch) + jnp.sum(p["b0"]) * jnp.mean(
            batch["poison"])

    base = _batch(n=B)
    poison = np.zeros((3, B), np.float32)
    poison[1, B // 2:] = 1e38
    b3 = {"x": jnp.broadcast_to(base["x"], (3, B, 8)),
          "y": jnp.broadcast_to(base["y"], (3, B, 8)),
          "poison": jnp.asarray(poison)}

    dstate = train_step.init_train_state(tx, params, loss_scale="dynamic")
    dstep = jax.jit(train_step.make_train_step(loss_fn, tx))
    dlosses = []
    for i in range(3):
        dstate, l = dstep(dstate, jax.tree.map(lambda a: a[i], b3))
        dlosses.append(float(l))

    mesh = Mesh(np.array(jax.devices()[:DP]), ("data",))
    zstep = train_step.make_train_step(loss_fn, tx, zero=True)

    def zbody(b3):
        st = train_step.init_train_state(
            tx, params, loss_scale="dynamic", shard=("data", DP))
        losses, masters = [], []
        for i in range(3):
            st, l = zstep(st, jax.tree.map(lambda a: a[i], b3))
            losses.append(l)
            masters.append(st.opt.master)
        return jnp.stack(losses), jnp.stack(masters, axis=1), \
            st.scaler.loss_scale

    zlosses, zmasters, zscale = jax.jit(
        functools.partial(jax.shard_map, check_vma=False)(
            zbody, mesh=mesh,
            in_specs=({"x": P(None, "data"), "y": P(None, "data"),
                       "poison": P(None, "data")},),
            out_specs=(P(), P("data"), P())))(b3)
    zmasters = np.asarray(zmasters).T

    n = int(tree_ravel(params)[0].size)
    # overflow step skipped bitwise on EVERY rank
    np.testing.assert_array_equal(zmasters[1], zmasters[0])
    # clean-step losses and the final master match the dense replay
    assert abs(float(zlosses[0]) - dlosses[0]) < 1e-5
    assert abs(float(zlosses[2]) - dlosses[2]) < 1e-5
    np.testing.assert_allclose(zmasters[2][:n],
                               np.asarray(dstate.opt.master),
                               rtol=1e-5, atol=2e-4)
    # dynamic scale backed off identically
    assert float(zscale) == float(dstate.scaler.loss_scale)


def test_init_zero_train_state_global_view_roundtrip():
    params = _make_params(n_layers=3)
    tx = functional.fused_adam(lr=1e-3)
    state, specs = train_step.init_zero_train_state(tx, params, "data", DP)
    opt = state.opt
    n = int(tree_ravel(params)[0].size)
    assert opt.shard == ("data", DP)
    assert opt.master.shape[0] == opt.padded_numel >= n
    # global view materializes the construction pytree without a mesh
    out = state.params()
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(a, b),
                 out, params)
    # the spec tree marks exactly the padded (dp-shardable) buffers
    leaves_specs = jax.tree.leaves(
        jax.tree.map(lambda s: s == P("data"), specs,
                     is_leaf=lambda x: isinstance(x, P)))
    leaves = jax.tree.leaves(state)
    sharded_flags = [bool(f) for f in leaves_specs]
    for leaf, flag in zip(leaves, sharded_flags):
        assert flag == (leaf.ndim == 1
                        and leaf.shape[0] == opt.padded_numel)


def test_zero_requires_sharded_state():
    params = _make_params(n_layers=2)
    tx = functional.fused_adam(lr=1e-3)
    state = train_step.init_train_state(tx, params)
    step = train_step.make_train_step(_loss_fn, tx, zero=True)
    try:
        step(state, _batch(n=4))
    except ValueError as e:
        assert "dp-sharded" in str(e)
    else:
        raise AssertionError("zero=True accepted a dense state")


def test_zero_aux_floats_pmeaned_ints_rank_local():
    """Under zero=True, float aux leaves get the same global-batch
    pmean as the loss beside them; integer diagnostics stay
    rank-local (averaging would corrupt their meaning)."""
    params = _make_params(n_layers=2)
    tx = functional.fused_adam(lr=1e-3)
    mesh = Mesh(np.array(jax.devices()[:DP]), ("data",))

    def loss_fn(p, batch):
        loss = _loss_fn(p, batch)
        rank_f = jnp.mean(batch["x"])          # differs per shard
        rank_i = batch["x"].shape[0] * jnp.ones((), jnp.int32)
        return loss, {"x_mean": rank_f, "n_local": rank_i}

    step = train_step.make_train_step(loss_fn, tx, has_aux=True,
                                      zero=True)

    def body(batch):
        st = train_step.init_train_state(tx, params,
                                         shard=("data", DP))
        _, (loss, aux) = step(st, batch)
        return loss, aux["x_mean"], aux["n_local"]

    B = 8
    batch = _batch(n=B)
    loss, xm, nl = jax.jit(
        functools.partial(jax.shard_map, check_vma=False)(
            body, mesh=mesh,
            in_specs=({"x": P("data"), "y": P("data")},),
            out_specs=(P(), P(), P())))(batch)
    # the float aux is the GLOBAL batch mean, matching a dense compute
    assert abs(float(xm) - float(jnp.mean(batch["x"]))) < 1e-6
    # the int aux stayed the rank-local shard size
    assert int(nl) == B // DP
