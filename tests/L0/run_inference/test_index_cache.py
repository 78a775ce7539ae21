"""The index-key pool (ISSUE 36, ``inference/kv_cache.py``): a third kind of
per-position state — one small index key a position a layer, ``[pages,
layers, width, page]`` — beside ``k`` and ``v`` under the SAME page table,
lengths, capacity and allocator; every mutator treats a page as all its
arrays, and a kind without an indexer holds no such array."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.inference import InferenceEngine, kv_cache
from apex_tpu.inference.models import cache_row_values, model_dims
from apex_tpu.transformer.testing import standalone_keye as SK
from apex_tpu.transformer.testing.standalone_laguna import LagunaConfig

PAGES, LAYERS, KVH, PS, D, WIDTH, SLOTS, MPPS = 12, 3, 2, 4, 6, 5, 3, 5


def pool(index=WIDTH):
    return kv_cache.init_paged_cache(
        PAGES, LAYERS, KVH, PS, D, slots=SLOTS, max_pages_per_slot=MPPS,
        dtype=jnp.float32, index=index)


def rows(n, seed=0):
    """A prompt's k, v ``[layers, kvh, n, d]`` and index keys ``[layers, n,
    width]``."""
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(LAYERS, KVH, n, D).astype(np.float32)),
            jnp.asarray(rng.randn(LAYERS, KVH, n, D).astype(np.float32)),
            jnp.asarray(rng.randn(LAYERS, n, WIDTH).astype(np.float32)))


def slot_rows(cache, slot, n):
    """The first ``n`` positions of ``slot`` read back through its row:
    k ``[layers, kvh, n, d]`` and index keys ``[layers, n, width]``."""
    row = np.asarray(cache.page_table[slot])
    k = np.asarray(cache.k)[row].transpose(1, 2, 0, 3, 4).reshape(
        LAYERS, KVH, MPPS * PS, D)
    v = np.asarray(cache.v)[row].transpose(1, 2, 0, 3, 4).reshape(
        LAYERS, KVH, MPPS * PS, D)
    ik = np.asarray(cache.ik)[row].transpose(1, 0, 3, 2).reshape(
        LAYERS, MPPS * PS, WIDTH)
    return k[:, :, :n], v[:, :, :n], ik[:, :n]


def test_three_arrays_under_one_table():
    c = pool()
    assert c.k.shape == c.v.shape == (PAGES + 1, LAYERS, KVH, PS, D)
    assert c.ik.shape == (PAGES + 1, LAYERS, WIDTH, PS)
    assert not c.latent and c.wk is None and c.row_shape == (KVH, D)
    assert (c.pages, c.page_size, c.max_seq) == (PAGES + 1, PS, MPPS * PS)
    # a kind without an indexer has NO such array, not a zero-sized one
    plain = pool(index=0)
    assert plain.ik is None
    assert len(jax.tree.leaves(plain)) == len(jax.tree.leaves(c)) - 1
    assert jax.tree.structure(kv_cache.paged_cache_partition_specs()) \
        == jax.tree.structure(plain)


def test_page_bytes_come_from_the_record():
    """``page x (2 x kv_heads x d + index) x 2 B x layers`` — 2,176 B a
    position a layer at the published widths — and one allocator."""
    cfg = SK.KeyeConfig()
    params = SK.keye_model_provider(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    eng = InferenceEngine("keye", cfg, params, slots=2, max_seq=64,
                          page_size=8, num_pages=10)
    per_position = 2 * cfg.num_kv_heads * cfg.head_dim + cfg.index_head_dim
    assert eng.page_host_bytes() == 8 * per_position * 2 * cfg.num_layers
    assert eng.cache_hbm_bytes() == 11 * eng.page_host_bytes()
    cache = eng.init_cache()
    assert cache.k.nbytes + cache.v.nbytes + cache.ik.nbytes \
        == eng.cache_hbm_bytes()
    assert eng.new_allocator().num_pages == 10
    published = {"latent": 0, "head_dim": 128, "index": 64}
    assert cache_row_values(published, 4) * 2 == 2176
    assert model_dims("keye", cfg)["index"] == cfg.index_head_dim
    assert model_dims("laguna", LagunaConfig())["index"] == 0
    with pytest.raises(ValueError, match="paged cache only"):
        InferenceEngine("keye", cfg, params, slots=2, max_seq=64)


@pytest.mark.parametrize("start,n", [(0, 8), (0, 6), (3, 7), (5, 1)],
                         ids=["whole_pages", "ends_mid_page", "mid_to_mid",
                              "one_row"])
def test_insert_tokens_carries_the_index_keys(start, n):
    c = pool()
    row = kv_cache.page_row([7, 2, 9], MPPS, PAGES)
    k0, v0, i0 = rows(start, seed=1)
    if start:
        c = kv_cache.insert_tokens(c, 1, k0, v0, start, row, 0, i0)
    k, v, ik = rows(n, seed=2)
    c = kv_cache.insert_tokens(c, 1, k, v, start + n, row, start, ik)
    assert int(c.lengths[1]) == start + n and int(c.capacity[1]) == 3 * PS
    gk, gv, gi = slot_rows(c, 1, start + n)
    np.testing.assert_array_equal(gk[:, :, start:], np.asarray(k))
    np.testing.assert_array_equal(gv[:, :, start:], np.asarray(v))
    np.testing.assert_array_equal(gi[:, start:], np.asarray(ik))
    np.testing.assert_array_equal(gi[:, :start], np.asarray(i0))
    # a page is all its arrays: k/v without the index keys is refused,
    # and so are index keys for a cache without the pool
    with pytest.raises(ValueError, match="index"):
        kv_cache.insert_tokens(c, 1, k, v, start + n, row, start)
    with pytest.raises(ValueError, match="index"):
        kv_cache.insert_tokens(pool(index=0), 1, k, v, start + n, row,
                               start, ik)
    with pytest.raises(ValueError, match="index keys must be"):
        kv_cache.insert_tokens(c, 1, k, v, start + n, row, start,
                               ik[:, :, :3])


def test_insert_pages_append_evict():
    c = pool()
    row = kv_cache.page_row([3, 11], MPPS, PAGES)
    k, v, ik = rows(PS, seed=3)
    c = kv_cache.insert_pages(c, 0, k, v, 3, row, ik)      # 3 real tokens
    rng = np.random.RandomState(4)
    tok = jnp.asarray(rng.randn(SLOTS, KVH, D).astype(np.float32))
    itok = jnp.asarray(rng.randn(SLOTS, WIDTH).astype(np.float32))
    for layer in range(LAYERS):
        c = kv_cache.append_layer(c, layer, tok + layer, tok - layer,
                                  itok * (layer + 1))
    c, truncated = kv_cache.advance(c, np.array([True, False, False]))
    assert not np.asarray(truncated).any() and int(c.lengths[0]) == 4
    gk, gv, gi = slot_rows(c, 0, 4)
    np.testing.assert_array_equal(gi[:, :3], np.asarray(ik)[:, :3])
    np.testing.assert_array_equal(gk[:, :, :3], np.asarray(k)[:, :, :3])
    for layer in range(LAYERS):
        np.testing.assert_array_equal(gi[layer, 3],
                                      np.asarray(itok[0] * (layer + 1)))
        np.testing.assert_array_equal(gk[layer, :, 3],
                                      np.asarray(tok[0] + layer))
        np.testing.assert_array_equal(gv[layer, :, 3],
                                      np.asarray(tok[0] - layer))
    with pytest.raises(ValueError, match="index"):
        kv_cache.append_layer(c, 0, tok, tok)
    with pytest.raises(ValueError, match="index keys must be"):
        kv_cache.append_layer(c, 0, tok, tok, itok[:, :2])
    before = np.asarray(c.ik).copy()
    c = kv_cache.evict(c, 0)                # metadata only: nothing moves
    assert int(c.lengths[0]) == 0 and int(c.capacity[0]) == 0
    assert (np.asarray(c.page_table[0]) == PAGES).all()
    np.testing.assert_array_equal(np.asarray(c.ik), before)


def test_extract_restore_and_cow_round_trip():
    c = pool()
    row = kv_cache.page_row([5, 1, 8], MPPS, PAGES)
    k, v, ik = rows(3 * PS, seed=5)
    c = kv_cache.insert_pages(c, 2, k, v, 3 * PS, row, ik)
    ids = jnp.asarray([5, 8, PAGES], jnp.int32)            # + trash padding
    k_slab, v_slab, ik_slab = kv_cache.extract_pages(c, ids)
    assert k_slab.shape == v_slab.shape == (3, LAYERS, KVH, PS, D)
    assert ik_slab.shape == (3, LAYERS, WIDTH, PS)
    to = jnp.asarray([0, 4, PAGES + 1], jnp.int32)
    fresh = kv_cache.restore_pages(pool(), to, k_slab, v_slab, ik_slab)
    for name in ("k", "v", "ik"):
        got, want = np.asarray(getattr(fresh, name)), np.asarray(
            getattr(c, name))
        np.testing.assert_array_equal(got[0], want[5])
        np.testing.assert_array_equal(got[4], want[8])
        assert not got[1].any()
    # whole or not at all
    with pytest.raises(ValueError, match="index"):
        kv_cache.restore_pages(pool(), to, k_slab, v_slab)
    assert kv_cache.extract_pages(pool(index=0), ids)[2] is None
    c2 = kv_cache.cow_page(c, 1, 10)
    for name in ("k", "v", "ik"):
        np.testing.assert_array_equal(np.asarray(getattr(c2, name))[10],
                                      np.asarray(getattr(c, name))[1])
