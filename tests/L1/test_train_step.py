"""Flat-native train step: structural regression tests.

Pins the three properties the flat-native path buys (ISSUE 2 acceptance):

1. the step's jaxpr contains NO grad re-ravel ``concatenate`` over the
   parameter leaves (autodiff produces flat grads directly);
2. no host-transfer/callback primitive appears anywhere between backward
   and update (the whole step is one pure program);
3. one optimizer step via the functional path compiles/dispatches
   exactly ONE executable, vs >= 3 for the old class-API loop
   (grad jit + eager unscale + optimizer-step jit).

Plus end-to-end behavior: the scanned loop learns, an overflow step is
skipped in-program (noop_flag) with the scale backed off, and with the
loss scale's 1/scale riding the update's ``grad_scale`` every functional
tx gives the update an unscaled copy of the grads gave.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")))

from apex_tpu import train_step
from apex_tpu.amp.scaler import LossScaler, update_scale
from apex_tpu.analysis.jaxpr_audit import FORBIDDEN_PRIMS
from apex_tpu.ops.fused_update import _BLOCK, fused_scale
from apex_tpu.optimizers import FusedAdam, functional
from apex_tpu.utils import tree_ravel

N_LAYERS = 8   # 16 leaves — enough that a grad re-ravel is unmistakable


def _make_params(seed=0, n_layers=N_LAYERS):
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
    for i in range(len(params) // 2):
        h = jnp.tanh(h @ params[f"w{i}"] + params[f"b{i}"])
    return jnp.mean((h - y) ** 2)


def _batch(seed=1, n=16):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(n, 8), jnp.float32)
    return {"x": x, "y": jnp.tanh(x @ jnp.ones((8, 8)) * 0.1)}


def _iter_eqns(jaxpr):
    """All equations of a (closed) jaxpr, recursing into sub-jaxprs
    (scan/cond/pjit bodies, custom_vjp calls, ...)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                    yield from _iter_eqns(sub)


def _grad_reravel_concats(jaxpr, n_params, n_leaves):
    """concatenate eqns that rebuild a param-buffer-sized array from
    (at least half) the parameter leaves — the re-ravel signature."""
    hits = []
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name != "concatenate":
            continue
        out = eqn.outvars[0].aval
        if out.size == n_params and len(eqn.invars) >= n_leaves // 2:
            hits.append(eqn)
    return hits


def test_flat_native_step_has_no_reravel_and_no_host_transfer():
    params = _make_params()
    n_leaves = len(jax.tree.leaves(params))
    n_params = int(tree_ravel(params)[0].size)
    tx = functional.fused_adam(lr=1e-2)
    state = train_step.init_train_state(tx, params, loss_scale="dynamic")
    step = train_step.make_train_step(_loss_fn, tx)
    jaxpr = jax.make_jaxpr(step)(state, _batch())

    # 1. no grad re-ravel concatenate over the parameter leaves
    assert not _grad_reravel_concats(jaxpr, n_params, n_leaves), (
        "flat-native step rebuilt the flat grad buffer by concatenating "
        "parameter leaves — the ravel tax is back")

    # 2. no host transfer anywhere between backward and update (the
    # analysis suite's forbidden-primitive list)
    seen = {e.primitive.name for e in _iter_eqns(jaxpr)}
    assert not (seen & FORBIDDEN_PRIMS), seen & FORBIDDEN_PRIMS

    # detector positive control: the OLD shape — differentiate the
    # params TREE, then ravel the grad tree — must trip the check
    def old_style(params, batch):
        grads = jax.grad(_loss_fn)(params, batch)
        return tree_ravel(grads)[0]

    old_jaxpr = jax.make_jaxpr(old_style)(params, _batch())
    assert _grad_reravel_concats(old_jaxpr, n_params, n_leaves)


def test_functional_step_compiles_one_executable_class_path_three():
    """The whole flat-native step lowers to ONE compiled executable; the
    old class-API loop (jitted grad fn + eager fused unscale + jitted
    optimizer step) needs >= 3.  Counted via the backend's compile
    events from cold caches in an otherwise-warm process.  A 2-layer
    model keeps the forced recompiles inside the fast-lane budget —
    the property under test is program COUNT, not program size."""
    params = _make_params(n_layers=2)
    batch = _batch(n=4)
    tx = functional.fused_adam(lr=1e-2)
    state = train_step.init_train_state(tx, params, loss_scale="dynamic")
    step = jax.jit(train_step.make_train_step(_loss_fn, tx))

    events = []
    # snapshot existing listeners so teardown can RESTORE them instead
    # of wiping every process-wide listener with clear_event_listeners
    from jax._src import monitoring as _mon
    saved = {attr: list(getattr(_mon, attr))
             for attr in dir(_mon)
             if attr.endswith("_listeners")
             and isinstance(getattr(_mon, attr), list)}
    jax.monitoring.register_event_listener(
        lambda name, **kw: events.append(name))

    def compiles(fn):
        jax.clear_caches()
        events.clear()
        fn()
        return sum(1 for e in events if "compile_requests" in e)

    try:
        # warm process-level machinery so the counts below are pure
        jax.jit(lambda x: x * 2)(jnp.ones(3)).block_until_ready()

        n_functional = compiles(
            lambda: jax.block_until_ready(step(state, batch)))
        assert n_functional == 1, n_functional

        def class_path_step():
            opt = FusedAdam(params, lr=1e-2)
            scaler = LossScaler("dynamic")
            grad_fn = jax.jit(jax.value_and_grad(_loss_fn))
            _, grads = grad_fn(params, batch)
            grads = scaler.unscale_(grads)
            out = opt.step(grads, noop_flag=scaler.found_inf)
            scaler.update_scale()
            return out

        n_class = compiles(
            lambda: jax.block_until_ready(class_path_step()))
        assert n_class >= 3, n_class
        assert n_class >= n_functional + 2
    finally:
        for attr, listeners in saved.items():
            getattr(_mon, attr)[:] = listeners


def test_train_loop_learns_and_matches_stepwise():
    params = _make_params()
    tx = functional.fused_adam(lr=3e-2)
    run = train_step.train_loop(_loss_fn, tx)
    batches = {"x": jnp.stack([_batch(s)["x"] for s in range(30)]),
               "y": jnp.stack([_batch(s)["y"] for s in range(30)])}

    state = train_step.init_train_state(tx, params, loss_scale="dynamic")
    state, losses = run(state, batches)
    losses = np.asarray(losses)
    assert np.all(np.isfinite(losses))
    assert losses[-1] < losses[0] * 0.5, (losses[0], losses[-1])
    # scan path == step-by-step path (same program, same carry)
    state2 = train_step.init_train_state(tx, params, loss_scale="dynamic")
    step = jax.jit(train_step.make_train_step(_loss_fn, tx))
    for i in range(30):
        state2, _ = step(state2, jax.tree.map(lambda a: a[i], batches))
    np.testing.assert_array_equal(np.asarray(state.opt.master),
                                  np.asarray(state2.opt.master))
    # checkpoint/eval boundary: params materialize in construction shape
    out = state.params()
    assert jax.tree.structure(out) == jax.tree.structure(params)


def _flat_selects(jaxpr, n):
    """``select_n`` equations whose output is a whole flat buffer of
    length ``n`` (``jnp.where`` sits inside a ``jit`` equation, so the
    sub-jaxprs are walked)."""
    return [e for e in _iter_eqns(jaxpr)
            if e.primitive.name == "select_n"
            and e.outvars[0].aval.shape == (n,)]


def test_lamb_step_selects_only_the_master():
    """LAMB's skip-on-overflow of the moments lives inside
    ``apex_lamb_stage1``: the step holds ONE flat-length select, the
    master's.  A select of a moment after the kernel reads the buffer
    the kernel has overwritten in place, which costs the select pass
    AND a whole-buffer copy of each moment (PR 28: 20 ms of a 142 ms
    BERT-large step)."""
    params = _make_params()
    n = int(tree_ravel(params)[0].size)
    assert n % _BLOCK      # the kernel's tail block is on the path
    tx = functional.fused_lamb(lr=1e-2)
    state = train_step.init_train_state(tx, params, loss_scale="dynamic")
    step = train_step.make_train_step(_loss_fn, tx)
    jaxpr = jax.make_jaxpr(step)(state, _batch())
    assert len(_flat_selects(jaxpr, n)) == 1

    # detector positive control: the old spelling — the moments selected
    # outside the kernel — reads three
    def old_style(state, batch):
        new, loss = step(state, batch)
        skip = loss > 0
        slots = {k: jnp.where(skip, state.opt.slots[k], v)
                 for k, v in new.opt.slots.items()}
        return new.replace(opt=new.opt.replace(slots=slots)), loss

    old_jaxpr = jax.make_jaxpr(old_style)(state, _batch())
    assert len(_flat_selects(old_jaxpr, n)) == 3


def _poisoned_loss(params, batch):
    # batch["poison"] = 0 -> clean loss; huge -> inf grads
    return _loss_fn(params, batch) + jnp.sum(params["w0"]) * batch["poison"]


@pytest.mark.parametrize("make_tx", [functional.fused_adam,
                                     functional.fused_lamb],
                         ids=["adam", "lamb"])
def test_overflow_step_skips_in_program_and_backs_off_scale(make_tx):
    """A non-finite grad must be caught by the overflow flag and
    skipped by the update kernel's noop predicate — all in-program —
    with the dynamic scale halved afterwards."""
    params = _make_params()
    tx = make_tx(lr=1e-2)
    step = jax.jit(train_step.make_train_step(_poisoned_loss, tx))
    state = train_step.init_train_state(tx, params, loss_scale="dynamic")
    clean = dict(_batch(), poison=jnp.float32(0.0))
    poisoned = dict(_batch(), poison=jnp.float32(1e38))

    state, _ = step(state, clean)
    master_before = np.asarray(state.opt.master)
    slots_before = {k: np.asarray(v) for k, v in state.opt.slots.items()}
    assert sorted(slots_before) == ["exp_avg", "exp_avg_sq"]
    assert all(np.any(v) for v in slots_before.values())
    scale_before = float(state.scaler.loss_scale)
    count_before = float(state.opt.count)
    state, _ = step(state, poisoned)
    np.testing.assert_array_equal(np.asarray(state.opt.master),
                                  master_before)       # update skipped
    for k, v in slots_before.items():                  # moments too, bitwise
        np.testing.assert_array_equal(
            np.asarray(state.opt.slots[k]).view(np.uint32),
            v.view(np.uint32), err_msg=k)
    assert float(state.scaler.loss_scale) == scale_before * 0.5
    assert float(state.opt.count) == count_before + 1  # as it always has
    # and the loop recovers on the next clean batch
    state, _ = step(state, clean)
    assert not np.array_equal(np.asarray(state.opt.master), master_before)


def _folded_step(tx):
    """The train step, also returning the ``noop_flag`` and
    ``grad_scale`` it handed the update."""
    def step(state, batch):
        seen = {}

        class Spy:
            def update(self, opt, flat_grads, **kw):
                seen.update(kw)
                return tx.update(opt, flat_grads, **kw)

        new, loss = train_step.make_train_step(_poisoned_loss, Spy())(
            state, batch)
        return new, (loss, seen["noop_flag"], seen["grad_scale"])
    return step


def _unscaled_copy_step(tx):
    """The same step spelled as it was before the unscale rode the
    update's ``grad_scale``: ``fused_scale`` writes an unscaled copy of
    the flat grads and flags it, then the update runs at
    ``grad_scale=1``.  Returns the flag too."""
    def step(state, batch):
        opt, scaler = state.opt, state.scaler

        def flat_loss(flat):
            loss = _poisoned_loss(opt.unravel(flat.astype(opt.flat_dtype)),
                                  batch)
            return loss * scaler.loss_scale, loss

        (_, loss), g = jax.value_and_grad(flat_loss, has_aux=True)(
            opt.master)
        g, flag = fused_scale(g, 1.0 / scaler.loss_scale)
        new_opt = tx.update(opt, g, noop_flag=flag, grad_scale=1.0)
        scaler = update_scale(scaler.replace(found_inf=flag))
        return state.replace(opt=new_opt, scaler=scaler), (loss, flag)
    return step


FUNCTIONAL_TXS = {
    "lamb": lambda: functional.fused_lamb(lr=1e-2),
    "adam": lambda: functional.fused_adam(lr=1e-2),
    "sgd": lambda: functional.fused_sgd(lr=1e-2, momentum=0.9),
    "adagrad": lambda: functional.fused_adagrad(lr=1e-2),
    "novograd": lambda: functional.fused_novograd(lr=1e-2),
}


@pytest.mark.parametrize("overflow", [False, True],
                         ids=["clean", "overflow"])
@pytest.mark.parametrize("name", sorted(FUNCTIONAL_TXS))
def test_folded_unscale_matches_the_unscaled_copy(name, overflow):
    """1/scale rides the update's ``grad_scale`` and the flag is a
    read-only reduction: every functional tx gives the update it gave
    when the step wrote an unscaled copy of the flat grads first — the
    dynamic scale is a power of two, so the fold rounds as the copy did
    — and an overflowed step leaves master and slots as they were, sets
    the flag and halves the scale.  Both spellings run one primitive at
    a time: jitted on the CPU, XLA fuses the interpreted kernel's
    arithmetic into what surrounds it and contracts its multiply-adds
    differently in the two programs (a few ulp apart), where on the chip
    the kernel is the same compiled program either way."""
    tx = FUNCTIONAL_TXS[name]()
    params = _make_params()
    clean = dict(_batch(), poison=jnp.float32(0.0))
    batch = dict(_batch(seed=2),
                 poison=jnp.float32(1e38 if overflow else 0.0))
    folded, copied = _folded_step(tx), _unscaled_copy_step(tx)

    # one clean step first, so the slots hold something to keep
    state = train_step.init_train_state(tx, params, loss_scale="dynamic")
    state, (_, flag, grad_scale) = folded(state, clean)
    assert float(flag) == 0.0
    assert float(grad_scale) == 2.0 ** -16
    before = jax.tree.map(np.asarray, state)

    new, (loss_new, flag_new, _) = folded(state, batch)
    old, (loss_old, flag_old) = copied(before, batch)
    assert float(flag_new) == float(flag_old) == float(overflow)
    assert float(loss_new) == float(loss_old)
    for got, want in zip(jax.tree.leaves(new.opt),
                         jax.tree.leaves(old.opt)):
        np.testing.assert_array_max_ulp(np.asarray(got), np.asarray(want),
                                        maxulp=1)
    for field in ("loss_scale", "growth_tracker", "found_inf"):
        assert getattr(new.scaler, field) == getattr(old.scaler, field)
    if overflow:
        assert float(new.scaler.loss_scale) == 2.0 ** 15
        np.testing.assert_array_equal(np.asarray(new.opt.master),
                                      before.opt.master)
        for k, v in before.opt.slots.items():
            np.testing.assert_array_equal(np.asarray(new.opt.slots[k]), v,
                                          err_msg=k)
    else:
        assert float(new.scaler.loss_scale) == 2.0 ** 16
        assert not np.array_equal(np.asarray(new.opt.master),
                                  before.opt.master)
