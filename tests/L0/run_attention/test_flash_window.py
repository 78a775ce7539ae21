"""A static sliding window in ``flash_attention`` and the window layers'
decode against a ring (ISSUE 30), in interpret mode against the oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.attention import (decode_attention, flash_attention,
                                    mha_reference, ring_decode_attention)
from apex_tpu.ops.paged_attention import paged_decode_attention


def qkv(seed, b, h, s, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, h, s, d), dtype) for k in ks)


def band(s, window):
    """True = masked, the oracle's convention: keep i - window < j <= i."""
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    return jnp.asarray(~((j <= i) & (j > i - window)))[None, None]


@pytest.mark.parametrize("s,window,block", [
    (256, 40, 128),      # the window is not a multiple of the block
    (384, 130, 128),     # ... and wider than one block
    (200, 7, None),      # a sequence padded up to the lane grid
    (256, 300, 128),     # a window wider than the sequence: plain causal
])
def test_window_matches_the_band_masked_oracle(s, window, block):
    q, k, v = qkv(s + window, 1, 3, s, 32)
    kw = {} if block is None else dict(block_q=block, block_k=block)
    got = flash_attention(q, k, v, causal=True, window=window, **kw)
    want = mha_reference(q, k, v, mask=band(s, window))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_xla_path_takes_the_window_too():
    q, k, v = qkv(3, 2, 2, 64, 16)
    got = flash_attention(q, k, v, causal=True, window=9, use_kernel=False)
    want = mha_reference(q, k, v, mask=band(64, 9))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_window_none_is_todays_kernel_bit_for_bit():
    """``window=None`` traces to the very program the call without the
    argument traces to (same jaxpr, so the same executable), and its output
    is bit-identical."""
    q, k, v = qkv(11, 1, 2, 256, 32, jnp.bfloat16)
    plain = lambda q, k, v: flash_attention(q, k, v, causal=True)  # noqa
    none = lambda q, k, v: flash_attention(q, k, v, causal=True,  # noqa
                                           window=None)
    assert str(jax.make_jaxpr(plain)(q, k, v)) \
        == str(jax.make_jaxpr(none)(q, k, v))
    np.testing.assert_array_equal(
        np.asarray(plain(q, k, v).astype(jnp.float32)),
        np.asarray(none(q, k, v).astype(jnp.float32)))


def test_window_needs_causal_and_has_no_backward():
    q, k, v = qkv(5, 1, 1, 128, 16)
    with pytest.raises(ValueError, match="needs causal=True"):
        flash_attention(q, k, v, window=8)
    with pytest.raises(NotImplementedError, match="forward-only"):
        jax.grad(lambda q: flash_attention(
            q, k, v, causal=True, window=8).sum())(q)


@pytest.mark.parametrize("positions", [[0, 3, 11], [12, 25, 40], [7, 8, 100]])
def test_ring_decode_reads_exactly_the_window(positions):
    """Ring of 12 rows, window 8: slot b is at position p; its ring holds
    positions p-11..p at rows t % 12 (older rows hold garbage).  Against
    plain attention over the last min(p + 1, 8) positions in order."""
    window, ring, kvh, group, d = 8, 12, 2, 3, 16
    rng = np.random.RandomState(sum(positions))
    q = jnp.asarray(rng.randn(3, kvh * group, d), jnp.float32)
    k_ring = np.full((3, kvh, ring, d), 1e3, np.float32)    # loud garbage
    v_ring = np.full((3, kvh, ring, d), 1e3, np.float32)
    want = []
    for b, p in enumerate(positions):
        ks = rng.randn(p + 1, kvh, d).astype(np.float32)
        vs = rng.randn(p + 1, kvh, d).astype(np.float32)
        for t in range(max(0, p - ring + 1), p + 1):
            k_ring[b, :, t % ring], v_ring[b, :, t % ring] = ks[t], vs[t]
        lo = max(0, p - window + 1)
        kk = jnp.asarray(ks[lo:]).transpose(1, 0, 2)[None]   # [1,kvh,n,d]
        vv = jnp.asarray(vs[lo:]).transpose(1, 0, 2)[None]
        kk, vv = (jnp.repeat(t, group, axis=1) for t in (kk, vv))
        want.append(mha_reference(q[b][None, :, None, :], kk, vv)[0, :, 0])
    got = ring_decode_attention(q, jnp.asarray(k_ring), jnp.asarray(v_ring),
                                jnp.asarray(positions, jnp.int32),
                                window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.stack(want)),
                               atol=2e-5, rtol=2e-5)


def test_paged_kernel_indexes_a_layer_of_the_whole_pool():
    """``layer=`` hands the kernel the pool and lets its blocks pick the
    layer: the numbers of the dense decode over that layer's gathered
    windows, with a query group of 6 (48 heads over 8 KV heads at the
    published sizes)."""
    pages, layers, kvh, ps, d, slots, group = 9, 2, 2, 8, 16, 3, 6
    rng = np.random.RandomState(0)
    pool_k = jnp.asarray(rng.randn(pages, layers, kvh, ps, d), jnp.float32)
    pool_v = jnp.asarray(rng.randn(pages, layers, kvh, ps, d), jnp.float32)
    table = jnp.asarray([[0, 1, 2], [3, 4, 8], [5, 8, 8]], jnp.int32)
    lengths = jnp.asarray([20, 11, 3], jnp.int32)
    q = jnp.asarray(rng.randn(slots, kvh * group, d), jnp.float32)

    def window(pool, layer):
        g = jnp.take(pool[:, layer], table, axis=0)    # [slots, 3, kvh, ps, d]
        return jnp.moveaxis(g, 2, 1).reshape(slots, kvh, 3 * ps, d)

    for layer in range(layers):
        got = paged_decode_attention(q, pool_k, pool_v, table, lengths,
                                     layer=layer)
        want = decode_attention(q, window(pool_k, layer),
                                window(pool_v, layer), lengths,
                                use_kernel=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError, match="whole pool"):
        paged_decode_attention(q, pool_k[:, 0], pool_v[:, 0], table,
                               lengths, layer=0)
