"""Fused optimizer vs oracle tests.

Mirrors ``tests/L0/run_optimizers/test_fused_optimizer.py`` in the reference:
every fused optimizer is stepped against a pure reference implementation
(torch.optim semantics) and must match within dtype tolerances.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch

from apex_tpu.ops.fused_update import (
    _BLOCK, adam_reference, fused_adam_flat, fused_axpby, fused_l2norm,
    fused_lamb_phase1_flat, fused_scale,
)
from apex_tpu.optimizers import (
    FusedAdagrad, FusedAdam, FusedLAMB, FusedNovoGrad, FusedSGD,
)


def _params(seed=0, sizes=((37,), (128, 129), (5, 7, 11), (1000,))):
    rng = np.random.RandomState(seed)
    return {f"p{i}": jnp.asarray(rng.randn(*s), jnp.float32)
            for i, s in enumerate(sizes)}


def _grads(seed=1, sizes=((37,), (128, 129), (5, 7, 11), (1000,))):
    return _params(seed, sizes)


class TestKernels:
    def test_scale(self):
        x = jnp.asarray(np.random.RandomState(0).randn(5000), jnp.float32)
        out, flag = jax.jit(fused_scale)(x, 0.25)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x) * 0.25,
                                   rtol=1e-6)
        assert float(flag) == 0.0

    def test_scale_detects_inf(self):
        x = jnp.asarray([1.0, jnp.inf, 3.0], jnp.float32)
        _, flag = jax.jit(fused_scale)(x, 1.0)
        assert float(flag) == 1.0

    def test_scale_detects_nan(self):
        x = jnp.asarray([1.0, jnp.nan, 3.0], jnp.float32)
        _, flag = jax.jit(fused_scale)(x, 1.0)
        assert float(flag) == 1.0

    def test_axpby(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(3000), jnp.float32)
        y = jnp.asarray(rng.randn(3000), jnp.float32)
        out, flag = jax.jit(fused_axpby)(2.0, x, -0.5, y)
        np.testing.assert_allclose(np.asarray(out),
                                   2.0 * np.asarray(x) - 0.5 * np.asarray(y),
                                   rtol=1e-6)
        assert float(flag) == 0.0

    def test_l2norm(self):
        x = jnp.asarray(np.random.RandomState(0).randn(70001), jnp.float32)
        got = jax.jit(fused_l2norm)(x)
        np.testing.assert_allclose(float(got),
                                   float(np.linalg.norm(np.asarray(x))),
                                   rtol=1e-5)

    @pytest.mark.parametrize("adam_w", [True, False])
    def test_adam_kernel_vs_oracle(self, adam_w):
        rng = np.random.RandomState(0)
        n = 10_000
        p = jnp.asarray(rng.randn(n), jnp.float32)
        g = jnp.asarray(rng.randn(n), jnp.float32)
        m = jnp.asarray(rng.rand(n), jnp.float32)
        v = jnp.asarray(rng.rand(n), jnp.float32)
        kw = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
                  weight_decay=0.01, step=3, adam_w_mode=adam_w)
        po, mo, vo = jax.jit(
            lambda *a: fused_adam_flat(*a, **kw))(p, g, m, v)
        pr, mr, vr = adam_reference(p, g, m, v, **kw)
        np.testing.assert_allclose(np.asarray(po), np.asarray(pr), atol=1e-6)
        np.testing.assert_allclose(np.asarray(mo), np.asarray(mr), atol=1e-6)
        np.testing.assert_allclose(np.asarray(vo), np.asarray(vr), atol=1e-6)

    def test_adam_noop_flag_skips(self):
        rng = np.random.RandomState(0)
        p = jnp.asarray(rng.randn(500), jnp.float32)
        g = jnp.asarray(rng.randn(500), jnp.float32)
        m = jnp.zeros(500, jnp.float32)
        v = jnp.zeros(500, jnp.float32)
        po, mo, vo = fused_adam_flat(
            p, g, m, v, lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8,
            weight_decay=0.0, step=1, noop_flag=1.0)
        np.testing.assert_array_equal(np.asarray(po), np.asarray(p))
        np.testing.assert_array_equal(np.asarray(mo), np.asarray(m))


_LAMB1_KW = dict(beta1=0.9, beta2=0.999, eps=1e-6, weight_decay=0.01)


def _lamb1_inputs(n, poison=None):
    rng = np.random.RandomState(0)
    g = rng.randn(n).astype(np.float32)
    if poison is not None:
        g[[0, n // 2, n - 1]] = poison
    return (jnp.asarray(rng.randn(n), jnp.float32), jnp.asarray(g),
            jnp.asarray(rng.randn(n), jnp.float32),
            jnp.asarray(rng.rand(n), jnp.float32))


def _lamb1_reference(p, g, m, v, *, beta1, beta2, eps, weight_decay, step,
                     grad_scale=1.0):
    """Pure-jnp spelling of LAMB stage 1 (grad averaging, bias
    correction): moments and the raw direction ``u``.  Every scalar is
    fp32 before it is combined, as in the kernel: fp32 ``1 - 0.999`` is
    4.7e-5 from 0.001 relatively, which ``m / sqrt(v)`` shows in ``u``
    wherever ``v`` is small."""
    b1, b2, t = jnp.float32(beta1), jnp.float32(beta2), jnp.float32(step)
    bc1 = 1.0 - jnp.power(b1, t)
    bc2 = 1.0 - jnp.power(b2, t)
    g = g * grad_scale
    m = b1 * m + (1.0 - b1) * g
    v = b2 * v + (1.0 - b2) * g * g
    u = (m / bc1) / (jnp.sqrt(v) / jnp.sqrt(bc2) + eps) + weight_decay * p
    return m, v, u


class TestLambStage1Kernel:
    """``noop_flag`` inside ``apex_lamb_stage1``: the moments are
    predicated where they are written, so no caller selects after it."""

    #: one partial block; whole blocks plus a tail block
    LENGTHS = (10_000, _BLOCK + 777)

    @pytest.mark.parametrize("n", LENGTHS)
    @pytest.mark.parametrize("poison", [None, np.inf, np.nan],
                             ids=["finite", "inf", "nan"])
    def test_noop_returns_moments_bit_identical(self, n, poison):
        assert n % _BLOCK
        p, g, m, v = _lamb1_inputs(n, poison)
        mo, vo, u = jax.jit(lambda *a: fused_lamb_phase1_flat(
            *a, noop_flag=1.0, step=3, **_LAMB1_KW))(p, g, m, v)
        assert u.shape == (n,) and u.dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(mo).view(np.uint32), np.asarray(m).view(np.uint32))
        np.testing.assert_array_equal(
            np.asarray(vo).view(np.uint32), np.asarray(v).view(np.uint32))

    @pytest.mark.parametrize("n", LENGTHS)
    def test_clean_step_vs_jnp(self, n):
        # the step count is traced, as in a train step: XLA's CPU
        # constant folder takes 0.999**3 another way (1e-5 off in u)
        p, g, m, v = _lamb1_inputs(n)
        mo, vo, u = jax.jit(lambda p, g, m, v, t: fused_lamb_phase1_flat(
            p, g, m, v, noop_flag=0.0, grad_scale=0.5, step=t,
            **_LAMB1_KW))(p, g, m, v, jnp.float32(3))
        mr, vr, ur = _lamb1_reference(p, g, m, v, grad_scale=0.5, step=3,
                                      **_LAMB1_KW)
        np.testing.assert_allclose(np.asarray(mo), np.asarray(mr), atol=1e-6)
        np.testing.assert_allclose(np.asarray(vo), np.asarray(vr), atol=1e-6)
        np.testing.assert_allclose(np.asarray(u), np.asarray(ur), atol=1e-6,
                                   rtol=1e-6)

    def test_default_noop_is_a_clean_step(self):
        p, g, m, v = _lamb1_inputs(10_000)
        got = fused_lamb_phase1_flat(p, g, m, v, step=3, **_LAMB1_KW)
        want = fused_lamb_phase1_flat(p, g, m, v, noop_flag=0.0, step=3,
                                      **_LAMB1_KW)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_noop_flag_is_traced_not_compiled_in(self):
        from apex_tpu import observability
        p, g, m, v = _lamb1_inputs(10_000)
        fn = jax.jit(lambda p, g, m, v, noop: fused_lamb_phase1_flat(
            p, g, m, v, noop_flag=noop, step=3, **_LAMB1_KW))
        clean = fn(p, g, m, v, jnp.float32(0.0))
        before = observability.compile_count()
        skipped = fn(p, g, m, v, jnp.float32(1.0))
        again = fn(p, g, m, v, jnp.float32(0.0))
        jax.block_until_ready((skipped, again))
        assert observability.compile_count() == before
        np.testing.assert_array_equal(np.asarray(skipped[0]), np.asarray(m))
        np.testing.assert_array_equal(np.asarray(again[0]),
                                      np.asarray(clean[0]))
        assert not np.array_equal(np.asarray(clean[0]), np.asarray(m))


def _torch_steps(torch_opt_cls, params, grads_seq, **kw):
    tparams = [torch.nn.Parameter(torch.tensor(np.asarray(v)))
               for v in params.values()]
    opt = torch_opt_cls(tparams, **kw)
    for grads in grads_seq:
        for tp, gv in zip(tparams, grads.values()):
            tp.grad = torch.tensor(np.asarray(gv))
        opt.step()
    return [tp.detach().numpy() for tp in tparams]


class TestFusedAdam:
    def test_vs_torch_adamw(self):
        params = _params()
        opt = FusedAdam(params, lr=3e-3, weight_decay=0.05, adam_w_mode=True)
        grads_seq = [_grads(seed=s) for s in range(1, 6)]
        out = params
        for g in grads_seq:
            out = opt.step(g)
        expected = _torch_steps(torch.optim.AdamW, params, grads_seq,
                                lr=3e-3, weight_decay=0.05)
        for got, exp in zip(out.values(), expected):
            np.testing.assert_allclose(np.asarray(got).ravel(), exp.ravel(),
                                       atol=2e-5)

    def test_vs_torch_adam_l2(self):
        params = _params()
        opt = FusedAdam(params, lr=1e-2, weight_decay=0.1, adam_w_mode=False)
        grads_seq = [_grads(seed=s) for s in range(1, 4)]
        out = params
        for g in grads_seq:
            out = opt.step(g)
        expected = _torch_steps(torch.optim.Adam, params, grads_seq,
                                lr=1e-2, weight_decay=0.1)
        for got, exp in zip(out.values(), expected):
            np.testing.assert_allclose(np.asarray(got).ravel(), exp.ravel(),
                                       atol=2e-5)

    def test_param_groups(self):
        pa, pb = _params(0, ((64,),)), _params(1, ((32, 8),))
        opt = FusedAdam([{"params": pa, "lr": 1e-2},
                         {"params": pb, "lr": 1e-4}], lr=1e-3)
        ga, gb = _grads(2, ((64,),)), _grads(3, ((32, 8),))
        outa, outb = opt.step([ga, gb])
        assert not np.allclose(np.asarray(outa["p0"]), np.asarray(pa["p0"]))
        # smaller lr -> smaller step
        da = np.abs(np.asarray(outa["p0"]) - np.asarray(pa["p0"])).mean()
        db = np.abs(np.asarray(outb["p0"]) - np.asarray(pb["p0"])).mean()
        assert da > db

    def test_state_dict_roundtrip(self):
        params = _params()
        opt = FusedAdam(params, lr=1e-3)
        g = _grads()
        opt.step(g)
        sd = opt.state_dict()
        opt2 = FusedAdam(params, lr=1e-3)
        opt2.load_state_dict(sd)
        out1 = opt.step(g)
        out2 = opt2.step(g)
        for a, b in zip(out1.values(), out2.values()):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_grad_scale_matches_prescaled(self):
        params = _params()
        g = _grads()
        opt1 = FusedAdam(params, lr=1e-3)
        out1 = opt1.step(jax.tree.map(lambda x: x * 8.0, g), grad_scale=0.125)
        opt2 = FusedAdam(params, lr=1e-3)
        out2 = opt2.step(g)
        for a, b in zip(out1.values(), out2.values()):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)


class TestFusedSGD:
    @pytest.mark.parametrize("momentum,nesterov,wd",
                             [(0.0, False, 0.0), (0.9, False, 0.0),
                              (0.9, True, 0.0), (0.9, False, 0.01)])
    def test_vs_torch_sgd(self, momentum, nesterov, wd):
        params = _params()
        opt = FusedSGD(params, lr=0.05, momentum=momentum, nesterov=nesterov,
                       weight_decay=wd)
        grads_seq = [_grads(seed=s) for s in range(1, 5)]
        out = params
        for g in grads_seq:
            out = opt.step(g)
        expected = _torch_steps(torch.optim.SGD, params, grads_seq, lr=0.05,
                                momentum=momentum, nesterov=nesterov,
                                weight_decay=wd)
        for got, exp in zip(out.values(), expected):
            np.testing.assert_allclose(np.asarray(got).ravel(), exp.ravel(),
                                       atol=1e-5)


class TestFusedAdagrad:
    def test_vs_torch_adagrad(self):
        params = _params()
        opt = FusedAdagrad(params, lr=0.1, eps=1e-10, weight_decay=0.01)
        grads_seq = [_grads(seed=s) for s in range(1, 4)]
        out = params
        for g in grads_seq:
            out = opt.step(g)
        expected = _torch_steps(torch.optim.Adagrad, params, grads_seq,
                                lr=0.1, eps=1e-10, weight_decay=0.01)
        for got, exp in zip(out.values(), expected):
            np.testing.assert_allclose(np.asarray(got).ravel(), exp.ravel(),
                                       atol=1e-5)


def _lamb_reference_numpy(params, grads_seq, lr, betas, eps, wd,
                          max_grad_norm=1.0):
    """Pure-numpy LAMB oracle (mirrors the reference test's in-test Lamb)."""
    ps = {k: np.asarray(v, np.float64) for k, v in params.items()}
    ms = {k: np.zeros_like(v) for k, v in ps.items()}
    vs = {k: np.zeros_like(v) for k, v in ps.items()}
    b1, b2 = betas
    t = 0
    for grads in grads_seq:
        t += 1
        gs = {k: np.asarray(v, np.float64) for k, v in grads.items()}
        gnorm = np.sqrt(sum(float((g * g).sum()) for g in gs.values()))
        clip = max_grad_norm / (gnorm + 1e-6) \
            if (max_grad_norm > 0 and gnorm > max_grad_norm) else 1.0
        for k in ps:
            g = gs[k] * clip
            ms[k] = b1 * ms[k] + (1 - b1) * g
            vs[k] = b2 * vs[k] + (1 - b2) * g * g
            mhat = ms[k] / (1 - b1 ** t)
            vhat = vs[k] / (1 - b2 ** t)
            u = mhat / (np.sqrt(vhat) + eps) + wd * ps[k]
            wn = np.linalg.norm(ps[k])
            un = np.linalg.norm(u)
            ratio = wn / un if (wn > 0 and un > 0) else 1.0
            ps[k] = ps[k] - lr * ratio * u
    return ps


class TestFusedLAMB:
    def test_vs_numpy_lamb(self):
        params = _params()
        lr, betas, eps, wd = 1e-2, (0.9, 0.999), 1e-6, 0.01
        opt = FusedLAMB(params, lr=lr, betas=betas, eps=eps, weight_decay=wd,
                        max_grad_norm=1.0)
        grads_seq = [_grads(seed=s) for s in range(1, 4)]
        out = params
        for g in grads_seq:
            out = opt.step(g)
        expected = _lamb_reference_numpy(params, grads_seq, lr, betas, eps,
                                         wd)
        for k in out:
            np.testing.assert_allclose(np.asarray(out[k]), expected[k],
                                       atol=2e-5)


def _novograd_reference_numpy(params, grads_seq, lr, betas, eps, wd,
                              grad_averaging=True, bias_correction=True):
    ps = {k: np.asarray(v, np.float64) for k, v in params.items()}
    ms = {k: np.zeros_like(v) for k, v in ps.items()}
    vs = {k: 0.0 for k in ps}
    b1, b2 = betas
    t = 0
    for grads in grads_seq:
        t += 1
        for k in ps:
            g = np.asarray(grads[k], np.float64)
            gsq = float((g * g).sum())
            vs[k] = gsq if t == 1 else b2 * vs[k] + (1 - b2) * gsq
            ghat = g / (np.sqrt(vs[k]) + eps) + wd * ps[k]
            coef = (1 - b1) if grad_averaging else 1.0
            ms[k] = b1 * ms[k] + coef * ghat
            step_size = lr / (1 - b1 ** t) if bias_correction else lr
            ps[k] = ps[k] - step_size * ms[k]
    return ps


class TestFusedNovoGrad:
    def test_vs_numpy_novograd(self):
        params = _params()
        lr, betas, eps, wd = 1e-2, (0.95, 0.98), 1e-8, 0.01
        opt = FusedNovoGrad(params, lr=lr, betas=betas, eps=eps,
                            weight_decay=wd)
        grads_seq = [_grads(seed=s) for s in range(1, 4)]
        out = params
        for g in grads_seq:
            out = opt.step(g)
        expected = _novograd_reference_numpy(params, grads_seq, lr, betas,
                                             eps, wd)
        for k in out:
            np.testing.assert_allclose(np.asarray(out[k]), expected[k],
                                       atol=2e-5)


class TestMultiTensorApply:
    def test_applier_scale(self):
        from apex_tpu.multi_tensor_apply import (
            multi_tensor_applier, multi_tensor_scale)
        xs = [jnp.ones((16,)), jnp.full((4, 4), 2.0)]
        outs, flag = multi_tensor_applier(multi_tensor_scale, 0.0, [xs], 0.5)
        np.testing.assert_allclose(np.asarray(outs[0]), 0.5)
        np.testing.assert_allclose(np.asarray(outs[1]), 1.0)
        assert float(flag) == 0.0


class TestEmptyBuffers:
    """Zero-length flat buffers must not read uninitialized SMEM (the grid
    would be empty, skipping the flag/accumulator init)."""

    def test_fused_scale_empty(self):
        from apex_tpu.ops.fused_update import fused_scale
        out, flag = fused_scale(jnp.zeros((0,), jnp.float32), 2.0)
        assert out.shape == (0,)
        assert float(flag) == 0.0

    def test_fused_axpby_empty(self):
        from apex_tpu.ops.fused_update import fused_axpby
        out, flag = fused_axpby(1.0, jnp.zeros((0,), jnp.float32),
                                2.0, jnp.zeros((0,), jnp.float32))
        assert out.shape == (0,)
        assert float(flag) == 0.0

    def test_fused_l2norm_empty(self):
        from apex_tpu.ops.fused_update import fused_l2norm
        assert float(fused_l2norm(jnp.zeros((0,), jnp.float32))) == 0.0

    def test_odd_sizes_match_reference(self):
        from apex_tpu.ops.fused_update import fused_l2norm, fused_scale
        for n in (1, 127, 129, 65537):
            x = jnp.arange(n, dtype=jnp.float32) % 13 - 6.0
            np.testing.assert_allclose(
                float(fused_l2norm(x)), float(jnp.linalg.norm(x)),
                rtol=1e-5)
            out, flag = fused_scale(x, 3.0)
            np.testing.assert_allclose(out, x * 3.0, rtol=1e-6)
            assert float(flag) == 0.0


class TestBroadcastLeafScalars:
    """The repeat-free per-leaf broadcast (r5: jnp.repeat's gather
    lowering measured seconds per call on TPU; this helper replaced it
    in LAMB/NovoGrad and must stay exactly equivalent)."""

    def test_matches_jnp_repeat(self):
        from apex_tpu.optimizers.base import broadcast_leaf_scalars
        sizes = (1, 7, 128, 1000, 3)
        scal = jnp.arange(len(sizes), dtype=jnp.float32) * 0.5 - 1.0
        got = jax.jit(lambda s: broadcast_leaf_scalars(s, sizes))(scal)
        ref = jnp.repeat(scal, jnp.asarray(sizes),
                         total_repeat_length=sum(sizes))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_empty(self):
        from apex_tpu.optimizers.base import broadcast_leaf_scalars
        out = broadcast_leaf_scalars(jnp.zeros((0,), jnp.float32), ())
        assert out.shape == (0,)
