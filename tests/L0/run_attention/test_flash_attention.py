"""Flash attention kernel vs jnp oracle.

Mirrors the reference's fused-attention tests
(``apex/contrib/test/fmha/test_fmha.py`` — fused vs python reference — and
``tests/L0/run_transformer/test_fused_softmax.py``'s kernel-vs-fallback
equality pattern).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.attention import flash_attention, mha_reference


def _rand(key, *shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=dtype)


def _qkv(seed, b, h, sq, sk, d, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (_rand(kq, b, h, sq, d, dtype=dtype),
            _rand(kk, b, h, sk, d, dtype=dtype),
            _rand(kv, b, h, sk, d, dtype=dtype))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(128, 128), (256, 256), (128, 256),
                                   (256, 128)])
def test_forward_matches_oracle(causal, sq, sk):
    # causal with sq != sk uses bottom-right diagonal alignment (decode with
    # a KV cache), matching the oracle's tril(k=sk-sq)
    q, k, v = _qkv(0, 2, 4, sq, sk, 64)
    out = flash_attention(q, k, v, causal=causal)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_padding_mask_matches_oracle():
    b, h, s, d = 2, 4, 128, 64
    q, k, v = _qkv(1, b, h, s, s, d)
    # reference convention: True = masked out (scaled_masked_softmax)
    lengths = jnp.array([96, 128])
    mask = (jnp.arange(s)[None, :] >= lengths[:, None])  # [b, sk]
    mask = mask[:, None, None, :]                        # [b, 1, 1, sk]
    mask = jnp.broadcast_to(mask, (b, 1, s, s))
    out = flash_attention(q, k, v, mask=mask)
    ref = mha_reference(q, k, v, mask=mask)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_oracle(causal):
    b, h, s, d = 1, 2, 128, 64
    q, k, v = _qkv(2, b, h, s, s, d)

    def loss_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=causal) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(a, b_, atol=1e-3, rtol=1e-3)


def test_mask_grads_match_oracle():
    b, h, s, d = 2, 2, 128, 64
    q, k, v = _qkv(3, b, h, s, s, d)
    lengths = jnp.array([64, 128])
    mask = jnp.broadcast_to(
        (jnp.arange(s)[None, :] >= lengths[:, None])[:, None, None, :],
        (b, 1, s, s))

    def loss_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, mask=mask) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, mask=mask) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(a, b_, atol=1e-3, rtol=1e-3)


def test_bf16_forward_close():
    q, k, v = _qkv(4, 1, 2, 128, 128, 64, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True)
    ref = mha_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(jnp.float32), ref,
                               atol=2e-2, rtol=2e-2)


def test_non_tiling_shape_falls_back():
    q, k, v = _qkv(5, 1, 1, 100, 100, 64)
    out = flash_attention(q, k, v)           # s < 128: single block
    ref = mha_reference(q, k, v)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(1000, 1000), (700, 1000)])
def test_non_tiling_long_shape_pads_to_kernel(causal, sq, sk, monkeypatch):
    """s=1000-style shapes must take the PADDED KERNEL path, not the
    O(s²) oracle (old silent fallback).  mha_reference is poisoned to
    prove the kernel ran."""
    import apex_tpu.ops.attention as attn_mod

    q, k, v = _qkv(7, 1, 2, sq, sk, 64)
    ref = mha_reference(q, k, v, causal=causal)

    def _boom(*a, **kw):
        raise AssertionError("oracle fallback taken for padded shape")

    monkeypatch.setattr(attn_mod, "mha_reference", _boom)
    out = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_padded_shape_grads_match_oracle():
    b, h, s, d = 1, 2, 384 + 128 + 64, 64   # 576: no 128-multiple divisor
    q, k, v = _qkv(8, b, h, s, s, d)

    def loss_kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(a, b_, atol=1e-3, rtol=1e-3)


def test_padded_shape_with_mask_matches_oracle():
    b, h, s, d = 2, 2, 700, 64               # 700 > 512, pads to 768
    q, k, v = _qkv(9, b, h, s, s, d)
    lengths = jnp.array([500, 700])
    mask = jnp.broadcast_to(
        (jnp.arange(s)[None, :] >= lengths[:, None])[:, None, None, :],
        (b, 1, s, s))
    out = flash_attention(q, k, v, mask=mask)
    ref = mha_reference(q, k, v, mask=mask)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_sm_scale_respected():
    q, k, v = _qkv(6, 1, 2, 128, 128, 64)
    out = flash_attention(q, k, v, sm_scale=0.05)
    ref = mha_reference(q, k, v, sm_scale=0.05)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(128, 256), (256, 128)])
def test_cross_shape_grads_match_oracle(causal, sq, sk):
    """sq != sk backward (decode/cross-attention): the causal offset
    (bottom-right diagonal alignment) must hold through the fused
    backward's dq accumulator and the dk/dv path.

    vjp with a RANDOM (everywhere-nonzero) cotangent, not grad of
    sum(out^2): a quadratic loss zeroes the cotangent exactly on
    fully-masked rows (out == 0 there), which would let a regression in
    the backward's masked-row guard ship undetected."""
    q, k, v = _qkv(13, 1, 2, sq, sk, 64)
    dout = _rand(jax.random.key(14), 1, 2, sq, 64) + 0.1

    def gl(attn):
        _, vjp = jax.vjp(
            lambda q, k, v: attn(q, k, v, causal=causal), q, k, v)
        return vjp(dout)

    gk = gl(flash_attention)
    gr = gl(mha_reference)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(a, b_, atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_fused_and_split_backward_agree(causal, monkeypatch):
    """The one-pass fused backward and the split dq/dkv kernels must
    produce identical grads (the VMEM gate picks between them by shape,
    so both paths need coverage at the same shape).  Blocks of 128 on
    s=512 force a REAL 4x4 grid — the fused kernel's multi-block
    machinery (full-sequence dq scratch accumulation across ki, per-ki
    dk/dv reinit, the two finalize predicates, causal block skipping)
    all run multiple times."""
    import apex_tpu.ops.attention as attn_mod

    q, k, v = _qkv(11, 1, 2, 512, 512, 64)

    def grads(q, k, v):
        def loss(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal,
                                           block_q=128, block_k=128) ** 2)
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    g_fused = grads(q, k, v)                     # under the 2 MB gate
    monkeypatch.setattr(attn_mod, "_FUSED_BWD_MAX_BYTES", 0)
    g_split = grads(q, k, v)                     # forced two-kernel path
    for a, b_ in zip(g_fused, g_split):
        np.testing.assert_allclose(a, b_, atol=1e-5, rtol=1e-5)


def test_fused_backward_masked_padded(monkeypatch):
    """Fused backward under mask + REAL lane padding matches the oracle
    (s=700 > the 512 fit threshold, so it pads to 768 and the fused
    kernel's valid-window masking is actually exercised)."""
    b, h, s, d = 2, 2, 700, 64                   # pads to 768
    q, k, v = _qkv(12, b, h, s, s, d)
    lengths = jnp.array([500, 700])
    mask = jnp.broadcast_to(
        (jnp.arange(s)[None, :] >= lengths[:, None])[:, None, None, :],
        (b, 1, s, s))

    def loss(attn):
        def f(q, k, v):
            return jnp.sum(attn(q, k, v, mask=mask) ** 2)
        return f

    gk = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss(mha_reference), argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(a, b_, atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# XLA short-sequence path (use_kernel=False — on TPU it auto-dispatches at
# padded seq <= _XLA_PATH_MAX_SEQ; forced here so CPU covers it)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sq,sk", [(128, 128), (128, 256), (96, 96),
                                   (256, 128)])
def test_xla_path_matches_oracle(causal, sq, sk):
    q, k, v = _qkv(7, 2, 4, sq, sk, 64)
    out = flash_attention(q, k, v, causal=causal, use_kernel=False)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)


def test_xla_path_mask_and_grads_match_kernel():
    q, k, v = _qkv(9, 2, 2, 128, 128, 64)
    mask = jax.random.bernoulli(jax.random.PRNGKey(3), 0.2,
                                (2, 1, 128, 128))

    def loss(f):
        def inner(q, k, v):
            return jnp.sum(f(q, k, v) ** 2)
        return jax.grad(inner, argnums=(0, 1, 2))(q, k, v)

    g_x = loss(lambda q, k, v: flash_attention(q, k, v, mask=mask,
                                               use_kernel=False))
    g_k = loss(lambda q, k, v: flash_attention(q, k, v, mask=mask,
                                               use_kernel=True))
    for a, b in zip(g_x, g_k):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-2, rtol=2e-2)


def test_xla_path_fully_masked_rows_zero():
    q, k, v = _qkv(11, 1, 2, 64, 64, 64)
    mask = jnp.zeros((1, 1, 64, 64), bool).at[:, :, 5, :].set(True)
    out = flash_attention(q, k, v, mask=mask, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(out[:, :, 5, :]), 0.0)


def test_xla_path_dropout_stream_matches_kernel():
    q, k, v = _qkv(13, 1, 2, 128, 128, 64)
    a = flash_attention(q, k, v, dropout_rate=0.15, dropout_seed=99,
                        use_kernel=False)
    b = flash_attention(q, k, v, dropout_rate=0.15, dropout_seed=99,
                        use_kernel=True)
    # identical coordinate-hash mask => identical zeros, close values
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               atol=2e-2, rtol=2e-2)
    za = np.isclose(np.asarray(a), 0.0, atol=1e-6)
    zb = np.isclose(np.asarray(b), 0.0, atol=1e-6)
    assert (za == zb).mean() > 0.999


def test_auto_dispatch_predicate(monkeypatch):
    """On the TPU short seqs take the XLA path, long seqs and explicit
    blocks take the kernel; the CPU platform always takes the kernel."""
    import apex_tpu.ops.attention as A
    calls = {}
    real_xla, real_fwd = A._xla_attention, A._fwd

    def spy_xla(*a, **k):
        calls["xla"] = True
        return real_xla(*a, **k)

    def spy_fwd(*a, **k):
        calls["kernel"] = True
        # the dispatch under test saw "compiled"; the kernel itself must
        # still interpret on this CPU host
        with monkeypatch.context() as m:
            m.setattr(A, "interpret_mode", lambda: True)
            return real_fwd(*a, **k)

    monkeypatch.setattr(A, "_xla_attention", spy_xla)
    monkeypatch.setattr(A, "_fwd", spy_fwd)
    q, k, v = _qkv(21, 1, 2, 128, 128, 64)

    monkeypatch.setattr(A, "interpret_mode", lambda: False)
    calls.clear()
    A.flash_attention(q, k, v)
    assert calls == {"xla": True}            # short seq on tpu -> XLA

    calls.clear()
    A.flash_attention(q, k, v, block_q=128, block_k=128)
    assert calls == {"kernel": True}         # explicit blocks -> kernel

    monkeypatch.setattr(A, "interpret_mode", lambda: True)
    calls.clear()
    A.flash_attention(q, k, v)
    assert calls == {"kernel": True}         # cpu platform -> kernel


def _xla_kernel_parity_case(b, h, sq, sk, d, seed, **kw):
    """Assert XLA-path vs kernel parity on loss AND input grads."""
    q, k, v = _qkv(seed + 100, b, h, sq, sk, d)

    def loss(use_kernel):
        def inner(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, use_kernel=use_kernel, **kw) ** 2)
        return jax.value_and_grad(inner, argnums=(0, 1, 2))(q, k, v)

    lx, gx = loss(False)
    lk, gk = loss(True)
    np.testing.assert_allclose(float(lx), float(lk), rtol=2e-3)
    for a, bb in zip(gx, gk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_xla_kernel_random_parity(seed):
    """Seeded random-config sweep: the XLA path and the kernel must
    agree on outputs AND input grads across shapes, causal, masks, and
    dropout (the dispatch boundary's semantics contract)."""
    rng = np.random.default_rng(seed)
    b = int(rng.integers(1, 3))
    h = int(rng.choice([1, 2, 4]))
    sq = int(rng.choice([64, 96, 128, 192, 256]))
    sk = sq if rng.random() < 0.6 else int(rng.choice([64, 128, 256]))
    d = int(rng.choice([32, 64]))
    causal = bool(rng.random() < 0.5)
    with_mask = bool(rng.random() < 0.5) and not causal
    rate = float(rng.choice([0.0, 0.15]))
    kw = dict(causal=causal)
    if with_mask:
        kw["mask"] = jax.random.bernoulli(
            jax.random.PRNGKey(seed), 0.2, (b, 1, sq, sk))
    if rate:
        kw.update(dropout_rate=rate, dropout_seed=seed * 7 + 1)
    _xla_kernel_parity_case(b, h, sq, sk, d, seed, **kw)


@pytest.mark.parametrize("sq,sk", [(128, 256), (256, 128)])
def test_xla_kernel_rect_causal_parity(sq, sk):
    """Rectangular causal (decode / KV-cache alignment): the XLA path's
    ``cols <= rows + (sk - sq)`` must match the kernel's causal_off in
    both directions, through the backward — the one branch the random
    sweep's seeds never draw."""
    _xla_kernel_parity_case(1, 2, sq, sk, 64, seed=50, causal=True)


def test_xla_max_seq_override_env_and_kwarg(monkeypatch):
    """The kernel/XLA auto-dispatch crossover is tunable without a code
    edit: APEX_TPU_ATTN_XLA_MAX_SEQ env var, overridden in turn by the
    per-call kwarg (the 256 default is interpolated,
    not densely measured)."""
    from apex_tpu.ops.attention import (_XLA_PATH_MAX_SEQ,
                                        xla_path_max_seq)

    monkeypatch.delenv("APEX_TPU_ATTN_XLA_MAX_SEQ", raising=False)
    assert xla_path_max_seq() == _XLA_PATH_MAX_SEQ
    monkeypatch.setenv("APEX_TPU_ATTN_XLA_MAX_SEQ", "512")
    assert xla_path_max_seq() == 512
    assert xla_path_max_seq(1024) == 1024      # kwarg beats env
    assert xla_path_max_seq(0) == 0            # 0 disables the XLA path
    monkeypatch.setenv("APEX_TPU_ATTN_XLA_MAX_SEQ", "not-an-int")
    with pytest.raises(ValueError, match="APEX_TPU_ATTN_XLA_MAX_SEQ"):
        xla_path_max_seq()


def test_flash_attention_accepts_xla_max_seq_kwarg():
    """The kwarg threads through flash_attention and does not change
    values (on CPU the kernel path is taken either way; the dispatch
    decision itself is pinned by test_xla_max_seq_override_env_and_kwarg)."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 128, 32),
                          jnp.bfloat16)
    base = flash_attention(q, q, q, causal=True)
    via_kwarg = flash_attention(q, q, q, causal=True, xla_max_seq=0)
    np.testing.assert_array_equal(np.asarray(base, np.float32),
                                  np.asarray(via_kwarg, np.float32))
