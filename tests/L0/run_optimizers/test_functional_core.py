"""Functional optimizer core vs the class API.

The class optimizers are thin stateful shells over
``apex_tpu.optimizers.functional`` — these tests pin the contract: N
steps through either entry point are BITWISE identical, the state
formats are interchangeable through ``state_dict``, and a FlatState is
donation-safe and scan-carryable.
"""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.optimizers import (
    FusedAdagrad, FusedAdam, FusedLAMB, FusedNovoGrad, FusedSGD, functional,
)
from apex_tpu.utils import tree_ravel

SIZES = ((37,), (16, 24), (5, 7, 3), (200,), (1,))


def _params(seed=0):
    rng = np.random.RandomState(seed)
    return {f"p{i}": jnp.asarray(rng.randn(*s), jnp.float32)
            for i, s in enumerate(SIZES)}


def _grads_seq(n, seed0=1):
    return [_params(seed0 + i) for i in range(n)]


def _flat(tree):
    return tree_ravel(tree)[0]


# (name, class ctor, transform, traced-hyper dict): the class wrapper
# feeds its hyperparameters as traced scalars (so LR schedules don't
# recompile) — bitwise parity therefore drives update the same way;
# baked-constant hyperparameters let XLA fold 1-ulp differently.
_PAIRS = [
    ("adam",
     lambda p: FusedAdam(p, lr=3e-3, weight_decay=0.05, betas=(0.8, 0.95)),
     functional.fused_adam(lr=3e-3, weight_decay=0.05, betas=(0.8, 0.95)),
     dict(lr=3e-3, beta1=0.8, beta2=0.95, eps=1e-8, weight_decay=0.05)),
    ("lamb",
     lambda p: FusedLAMB(p, lr=1e-2, weight_decay=0.01, max_grad_norm=1.0),
     functional.fused_lamb(lr=1e-2, weight_decay=0.01, max_grad_norm=1.0),
     dict(lr=1e-2, beta1=0.9, beta2=0.999, eps=1e-6, weight_decay=0.01,
          max_grad_norm=1.0)),
    ("sgd",
     lambda p: FusedSGD(p, lr=0.05, momentum=0.9, weight_decay=0.01),
     functional.fused_sgd(lr=0.05, momentum=0.9, weight_decay=0.01),
     dict(lr=0.05, momentum=0.9, dampening=0.0, weight_decay=0.01)),
    ("novograd",
     lambda p: FusedNovoGrad(p, lr=1e-2, betas=(0.95, 0.98),
                             weight_decay=0.01),
     functional.fused_novograd(lr=1e-2, betas=(0.95, 0.98),
                               weight_decay=0.01),
     dict(lr=1e-2, beta1=0.95, beta2=0.98, eps=1e-8, weight_decay=0.01)),
    ("adagrad",
     lambda p: FusedAdagrad(p, lr=0.1, weight_decay=0.01),
     functional.fused_adagrad(lr=0.1, weight_decay=0.01),
     dict(lr=0.1, eps=1e-10, weight_decay=0.01)),
]


def _traced(hyper):
    return {k: jnp.asarray(v, jnp.float32) for k, v in hyper.items()}


@pytest.mark.parametrize("name,make_cls,tx,hyper", _PAIRS,
                         ids=[p[0] for p in _PAIRS])
def test_functional_matches_class_bitwise(name, make_cls, tx, hyper):
    """N steps through tx.init/tx.update == N steps through the class
    API, bit for bit (same kernels, same program)."""
    params = _params()
    opt = make_cls(params)
    st = tx.init(params)
    # noop_flag/grad_scale traced too: baked 0.0/1.0 constants fold the
    # skip-select away and let XLA fuse the final subtract into an FMA,
    # a 1-ulp divergence from the class program on a few elements
    upd = jax.jit(lambda s, g, nf, gs, hp: tx.update(
        s, g, noop_flag=nf, grad_scale=gs, **hp))
    out = params
    for g in _grads_seq(4):
        out = opt.step(g)
        st = upd(st, _flat(g), jnp.float32(0.0), jnp.float32(1.0),
                 _traced(hyper))
    np.testing.assert_array_equal(
        np.asarray(st.master), np.asarray(opt.param_groups[0].master))
    for k, v in opt.param_groups[0].state.items():
        np.testing.assert_array_equal(np.asarray(st.slots[k]),
                                      np.asarray(v))
    # and the materialized params round-trip identically
    for a, b in zip(jax.tree.leaves(st.params()), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name,make_cls,tx,hyper", _PAIRS[:2],
                         ids=[p[0] for p in _PAIRS[:2]])
def test_noop_flag_and_grad_scale_parity(name, make_cls, tx, hyper):
    params = _params()
    g = _params(9)
    opt = make_cls(params)
    st = tx.init(params)
    upd = jax.jit(lambda s, gf, nf, gs, hp: tx.update(
        s, gf, noop_flag=nf, grad_scale=gs, **hp))
    # a noop-skipped step then a scaled step
    opt.step(g, noop_flag=1.0)
    st = upd(st, _flat(g), 1.0, 1.0, _traced(hyper))
    np.testing.assert_array_equal(np.asarray(st.master),
                                  np.asarray(opt.param_groups[0].master))
    opt.step(g, grad_scale=0.125)
    st = upd(st, _flat(g), 0.0, 0.125, _traced(hyper))
    np.testing.assert_array_equal(np.asarray(st.master),
                                  np.asarray(opt.param_groups[0].master))


def test_state_dict_roundtrip_through_init_update():
    """Functional slots ARE the class checkpoint format: pack a
    FlatState into a ``state_dict``, load it into a fresh class
    optimizer, and both continuations agree to one fp32 ulp — and the
    reverse direction (class state_dict -> FlatState) too.  (Pinned
    bitwise under jax 0.4; the two continuations are two different
    XLA programs — traced vs baked-in hyperparameters — and jax 0.9's
    XLA:CPU contracts their multiply-adds differently, 3e-8 apart.)"""
    params = _params()
    tx = functional.fused_adam(lr=3e-3, weight_decay=0.05)
    hyper = dict(lr=3e-3, beta1=0.9, beta2=0.999, eps=1e-8,
                 weight_decay=0.05)
    upd = jax.jit(lambda s, g, hp: tx.update(s, g, **hp))
    st = tx.init(params)
    for g in _grads_seq(2):
        st = upd(st, _flat(g), _traced(hyper))

    # functional -> class
    opt = FusedAdam(params, lr=3e-3, weight_decay=0.05)
    opt.load_state_dict({
        "step": int(st.count),
        "groups": [{"master": st.master, "state": dict(st.slots),
                    "options": dict(opt.param_groups[0].options)}],
    })
    g3 = _params(7)
    opt.step(g3)
    st = upd(st, _flat(g3), _traced(hyper))
    np.testing.assert_allclose(np.asarray(st.master),
                               np.asarray(opt.param_groups[0].master),
                               rtol=2e-7, atol=1e-7)

    # class -> functional
    sd = opt.state_dict()
    st2 = tx.init(params)
    st2 = st2.replace(
        master=jnp.asarray(sd["groups"][0]["master"]),
        count=jnp.asarray(sd["step"], jnp.float32),
        slots={k: jnp.asarray(v)
               for k, v in sd["groups"][0]["state"].items()})
    g4 = _params(8)
    opt.step(g4)
    st2 = upd(st2, _flat(g4), _traced(hyper))
    np.testing.assert_allclose(np.asarray(st2.master),
                               np.asarray(opt.param_groups[0].master),
                               rtol=2e-7, atol=1e-7)


def test_update_is_donation_safe():
    """jit(update, donate_argnums=(0,)) must run repeatedly without
    'donated buffer reused' errors — nothing in the state may be needed
    after the update consumes it."""
    params = _params()
    tx = functional.fused_lamb(lr=1e-2)
    st = tx.init(params)
    upd = jax.jit(tx.update, donate_argnums=(0,))
    with warnings.catch_warnings():
        # CPU ignores donation with a warning; the contract under test
        # is that repeated donated calls stay correct
        warnings.simplefilter("ignore")
        for g in _grads_seq(3):
            st = upd(st, _flat(g))
    assert np.all(np.isfinite(np.asarray(st.master)))
    assert float(st.count) == 3.0


def test_flat_state_is_scan_carryable():
    """update preserves the treedef (static layout fields included), so
    a FlatState scans — and the scanned run equals the step-by-step
    run exactly."""
    params = _params()
    tx = functional.fused_adam(lr=1e-3, weight_decay=0.01)
    gs = jnp.stack([_flat(g) for g in _grads_seq(5)])

    @jax.jit
    def scanned(st, gs):
        return jax.lax.scan(lambda s, g: (tx.update(s, g), s.count),
                            st, gs)

    st_scan, counts = scanned(tx.init(params), gs)
    st_seq = tx.init(params)
    upd = jax.jit(tx.update)
    for g in gs:
        st_seq = upd(st_seq, g)
    np.testing.assert_array_equal(np.asarray(st_scan.master),
                                  np.asarray(st_seq.master))
    assert float(st_scan.count) == 5.0


def test_init_from_flat_buffer():
    """init accepts an already-flat 1-D buffer (the bench legs' entry):
    one implicit leaf, no unravel."""
    flat = jnp.arange(64, dtype=jnp.float32)
    tx = functional.fused_adam(lr=1e-3)
    st = tx.init(flat)
    assert st.sizes == (64,) and st.unravel is None
    st = jax.jit(tx.update)(st, jnp.ones(64, jnp.float32))
    assert not np.allclose(np.asarray(st.master), np.asarray(flat))
    with pytest.raises(ValueError):
        st.params()


def test_mid_training_static_option_mutation_takes_effect():
    """torch idiom: mutating a group's options between steps — static
    knobs included — must affect the next step (the class wrapper
    rebuilds its transform from the live options every step)."""
    params = _params()
    g = _params(5)
    opt_mut = FusedAdam(params, lr=1e-3)
    opt_ref = FusedAdam(params, lr=1e-3)
    opt_mut.step(g)
    opt_ref.step(g)
    opt_mut.param_groups[0].options["bias_correction"] = False
    out_mut = opt_mut.step(g)
    out_ref = opt_ref.step(g)
    assert not np.array_equal(np.asarray(out_mut["p3"]),
                              np.asarray(out_ref["p3"]))


def test_sgd_noop_step_does_not_seed_momentum():
    """The first EFFECTIVE step seeds the momentum buffer: an
    overflow-skipped step 1 must leave 'seeded' at 0 so step 2 still
    clones the grad (torch semantics), in class and functional alike."""
    params = _params()
    g = _params(3)
    tx = functional.fused_sgd(lr=0.1, momentum=0.9)
    st = tx.init(params)
    upd = jax.jit(lambda s, gf, nf: tx.update(s, gf, noop_flag=nf))
    st = upd(st, _flat(g), 1.0)
    assert float(st.slots["seeded"]) == 0.0
    st = upd(st, _flat(g), 0.0)
    assert float(st.slots["seeded"]) == 1.0
    # parity with the class path under the same skip pattern
    opt = FusedSGD(params, lr=0.1, momentum=0.9)
    opt.step(g, noop_flag=1.0)
    opt.step(g)
    np.testing.assert_array_equal(np.asarray(st.master),
                                  np.asarray(opt.param_groups[0].master))
