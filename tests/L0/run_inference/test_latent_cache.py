"""The latent paged pool (ISSUE 34, ``inference/kv_cache.py``): ONE array
``[pages, layers, width, page]`` with no KV-head axis and no value array,
under the paged pool's own page table, lengths, capacity and mutators."""
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import InferenceEngine, kv_cache
from apex_tpu.transformer.testing import standalone_axk1 as SA

PAGES, LAYERS, PS, WIDTH, SLOTS, MPPS = 12, 3, 4, 10, 3, 5


def pool():
    return kv_cache.init_paged_cache(
        PAGES, LAYERS, 0, PS, 0, slots=SLOTS, max_pages_per_slot=MPPS,
        dtype=jnp.float32, latent=WIDTH)


def rows(n, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(
        LAYERS, n, WIDTH).astype(np.float32))


def slot_rows(cache, slot, n):
    """The first ``n`` positions of ``slot`` read back through its row."""
    row = np.asarray(cache.page_table[slot])
    flat = np.asarray(cache.k)[row].transpose(1, 0, 3, 2).reshape(
        LAYERS, MPPS * PS, WIDTH)           # [mpps, layers, w, ps] ->
    return flat[:, :n]


def test_one_array_and_no_value_array():
    c = pool()
    assert c.latent and c.v is None and c.wk is None and c.wv is None
    assert c.k.shape == (PAGES + 1, LAYERS, WIDTH, PS)
    assert (c.pages, c.layers, c.page_size, c.head_dim, c.kv_heads) == (
        PAGES + 1, LAYERS, PS, WIDTH, 0)
    assert c.row_shape == (WIDTH,) and c.max_seq == MPPS * PS
    assert c.ring == 0 and int(kv_cache.window_pages_live(c)) == 0
    # the window layers of a latent pool keep latent rows of their own
    # width: asked for without one, rings are refused
    with pytest.raises(ValueError, match="keep latent rows"):
        kv_cache.init_paged_cache(4, 1, 0, 4, 0, slots=1,
                                  max_pages_per_slot=2, latent=8,
                                  window_layers=1, window=4)


def test_page_bytes_come_from_the_record():
    """``page x 576 x 2 x layers`` at the published widths: the engine
    asks the kind's record, not ``2 * kv_heads * head_dim``."""
    import jax
    cfg = SA.AXK1Config()
    params = SA.axk1_model_provider(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    eng = InferenceEngine("axk1", cfg, params, slots=2, max_seq=64,
                          page_size=8, num_pages=10)
    assert eng.page_host_bytes() == 8 * cfg.latent_dim * 2 * cfg.num_layers
    assert eng.cache_hbm_bytes() == 11 * eng.page_host_bytes()
    assert eng.init_cache().k.nbytes == eng.cache_hbm_bytes()
    from apex_tpu.inference.models import cache_row_values
    assert cache_row_values({"latent": 576, "head_dim": 192}, 0) == 576
    assert cache_row_values({"latent": 0, "head_dim": 128}, 8) == 2048


@pytest.mark.parametrize("start,n", [(0, 8), (0, 6), (3, 7), (5, 1)],
                         ids=["whole_pages", "ends_mid_page", "mid_to_mid",
                              "one_row"])
def test_insert_tokens_at_any_alignment(start, n):
    c = pool()
    row = kv_cache.page_row([7, 2, 9], MPPS, PAGES)
    first = rows(start, seed=1)
    if start:
        c = kv_cache.insert_tokens(c, 1, first, None, start, row, 0)
    x = rows(n, seed=2)
    c = kv_cache.insert_tokens(c, 1, x, None, start + n, row, start)
    assert int(c.lengths[1]) == start + n and int(c.capacity[1]) == 3 * PS
    got = slot_rows(c, 1, start + n)
    np.testing.assert_array_equal(got[:, start:], np.asarray(x))
    np.testing.assert_array_equal(got[:, :start], np.asarray(first))
    with pytest.raises(ValueError, match="v None"):
        kv_cache.insert_tokens(c, 1, x, x, start + n, row, start)


def test_insert_pages_append_evict():
    c = pool()
    row = kv_cache.page_row([3, 11], MPPS, PAGES)
    x = rows(PS, seed=3)
    c = kv_cache.insert_pages(c, 0, x, None, 3, row)       # 3 real tokens
    tok = jnp.asarray(np.random.RandomState(4).randn(
        SLOTS, WIDTH).astype(np.float32))
    for layer in range(LAYERS):
        c = kv_cache.append_layer(c, layer, tok + layer, None)
    c, truncated = kv_cache.advance(c, np.array([True, False, False]))
    assert not np.asarray(truncated).any() and int(c.lengths[0]) == 4
    got = slot_rows(c, 0, 4)
    np.testing.assert_array_equal(got[:, :3], np.asarray(x)[:, :3])
    for layer in range(LAYERS):
        np.testing.assert_array_equal(got[layer, 3],
                                      np.asarray(tok[0] + layer))
    with pytest.raises(ValueError, match="latent pool"):
        kv_cache.append_layer(c, 0, tok[:, None], None)
    c = kv_cache.evict(c, 0)
    assert int(c.lengths[0]) == 0 and int(c.capacity[0]) == 0
    assert (np.asarray(c.page_table[0]) == PAGES).all()


def test_extract_restore_and_cow_round_trip():
    c = pool()
    row = kv_cache.page_row([5, 1, 8], MPPS, PAGES)
    x = rows(3 * PS, seed=5)
    c = kv_cache.insert_pages(c, 2, x, None, 3 * PS, row)
    ids = jnp.asarray([5, 8, PAGES], jnp.int32)            # + trash padding
    k_slab, v_slab, _ = kv_cache.extract_pages(c, ids)
    assert v_slab is None and k_slab.shape == (3, LAYERS, WIDTH, PS)
    fresh = kv_cache.restore_pages(
        pool(), jnp.asarray([0, 4, PAGES + 1], jnp.int32), k_slab, None)
    np.testing.assert_array_equal(np.asarray(fresh.k[0]),
                                  np.asarray(c.k[5]))
    np.testing.assert_array_equal(np.asarray(fresh.k[4]),
                                  np.asarray(c.k[8]))
    assert not np.asarray(fresh.k[1]).any()
    c2 = kv_cache.cow_page(c, 1, 10)
    np.testing.assert_array_equal(np.asarray(c2.k[10]), np.asarray(c.k[1]))
    assert c2.v is None
