"""Ragged paged decode attention vs the dense decode path: the one
``apex_paged_decode`` kernel, reading a layer of the WHOLE pool through
the page table, must match the dense cache's decode within fp tolerance
on ragged batches (straggler + shorts) across MHA/GQA/MQA, for every
layer of a multi-layer pool, in fp32 and bf16."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.attention import decode_attention
from apex_tpu.ops.paged_attention import (paged_decode_attention,
                                          paged_work_list)

LAYERS = 3


def _paged_twin(slots, h, kvh, ps, mpps, lengths, d=16, seed=0,
                layers=LAYERS):
    """(q, dense k/v per layer, the whole paged pool k/v + scrambled
    page table, lengths): the SAME cache contents laid out both ways,
    every layer different, with dead pool pages holding garbage so
    masking bugs can't hide."""
    rng = np.random.RandomState(seed)
    max_seq = ps * mpps
    n_pages = slots * mpps
    q = rng.randn(slots, h, d).astype(np.float32)
    k = rng.randn(layers, slots, kvh, max_seq, d).astype(np.float32)
    v = rng.randn(layers, slots, kvh, max_seq, d).astype(np.float32)
    pool_k = rng.randn(n_pages + 1, layers, kvh, ps, d).astype(np.float32)
    pool_v = rng.randn(n_pages + 1, layers, kvh, ps, d).astype(np.float32)
    perm = rng.permutation(n_pages)       # non-contiguous assignment
    pt = np.empty((slots, mpps), np.int32)
    i = 0
    for s in range(slots):
        for j in range(mpps):
            pid = perm[i]
            i += 1
            pt[s, j] = pid
            pool_k[pid] = k[:, s, :, j * ps:(j + 1) * ps, :]
            pool_v[pid] = v[:, s, :, j * ps:(j + 1) * ps, :]
    return (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(pool_k), jnp.asarray(pool_v), jnp.asarray(pt),
            jnp.asarray(lengths, jnp.int32))


RAGGED = [32, 0, 1, 7, 8, 9]              # straggler + shorts around ps

#: dtype -> tolerance of the kernel against the dense XLA decode
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
@pytest.mark.parametrize("layer", [0, LAYERS - 1])
@pytest.mark.parametrize("h,kvh", [(4, 4), (8, 2), (4, 1)],
                         ids=["mha", "gqa", "mqa"])
def test_kernel_matches_dense_on_ragged_batch(h, kvh, layer, dtype):
    q, k, v, pk, pv, pt, ln = _paged_twin(6, h, kvh, 8, 4, RAGGED)
    q, k, v, pk, pv = (t.astype(dtype) for t in (q, k, v, pk, pv))
    dense = decode_attention(q, k[layer], v[layer], ln, use_kernel=False)
    kern = paged_decode_attention(q, pk, pv, pt, ln, layer=layer)
    assert kern.dtype == dtype and kern.shape == q.shape
    np.testing.assert_allclose(
        np.asarray(kern, np.float32), np.asarray(dense, np.float32),
        rtol=TOL[dtype], atol=TOL[dtype])


#: one slot's length by name, at page size 8 and 4 pages a slot
EDGES = {"empty": 0, "one": 1, "page": 8, "page_plus_one": 9, "full": 32}


@pytest.mark.parametrize("edge", list(EDGES))
@pytest.mark.parametrize("h,kvh", [(4, 4), (8, 2), (4, 1)],
                         ids=["mha", "gqa", "mqa"])
def test_kernel_matches_dense_at_a_lengths_edge(h, kvh, edge):
    """A slot at the edge — first, between others and last in the list —
    beside slots of other lengths: each slot's answer is its own."""
    n = EDGES[edge]
    lengths = [n, 13, n, 32, 0, n]
    q, k, v, pk, pv, pt, ln = _paged_twin(6, h, kvh, 8, 4, lengths)
    dense = decode_attention(q, k[1], v[1], ln, use_kernel=False)
    kern = paged_decode_attention(q, pk, pv, pt, ln, layer=1)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)
    if n == 0:
        assert np.all(np.asarray(kern)[[0, 2, 5]] == 0)


def _work_list_reference(pt, lengths, ps):
    """The live (slot, page) pairs in NumPy: slot-major, page-minor; an
    empty slot's one item names page 0."""
    slot, page, start = [], [], [0]
    for s, n in enumerate(lengths):
        live = min(-(-int(n) // ps), pt.shape[1])
        slot += [s] * max(live, 1)
        page += list(pt[s, :live]) if live else [0]
        start.append(len(slot))
    return np.array(slot), np.array(page), np.array(start)


@pytest.mark.parametrize("lengths", [
    RAGGED, [0, 0, 0], [1, 1, 1, 1], [32, 32], [8, 9, 0, 16, 17, 0, 32, 1],
    [40, 5]], ids=["ragged", "all_empty", "all_one", "all_full", "edges",
                   "beyond_the_table"])
def test_work_list_against_numpy(lengths):
    ps, mpps, slots = 8, 4, len(lengths)
    rng = np.random.RandomState(len(lengths))
    # every entry of the table its own page, from 1: a page names its entry
    pt = 1 + rng.permutation(slots * mpps).reshape(slots, mpps).astype(
        np.int32)
    work = paged_work_list(jnp.asarray(pt), jnp.asarray(lengths, jnp.int32),
                           page_size=ps)
    slot, page, start = _work_list_reference(pt, lengths, ps)
    count = int(work.start[-1])
    assert count == sum(max(min(-(-n // ps), mpps), 1) for n in lengths)
    assert work.slot.shape == work.page.shape == (slots * mpps,)
    np.testing.assert_array_equal(np.asarray(work.start), start)
    np.testing.assert_array_equal(np.asarray(work.slot)[:count], slot)
    np.testing.assert_array_equal(np.asarray(work.page)[:count], page)
    np.testing.assert_array_equal(np.asarray(work.lengths), lengths)
    # no dead entry of the table among the items
    dead = {int(pt[s, j]) for s, n in enumerate(lengths)
            for j in range(mpps) if j * ps >= n}
    assert not dead & set(np.asarray(work.page)[:count].tolist())
    # what lies past the count is never walked, but stays in range
    assert np.all((np.asarray(work.slot) >= 0)
                  & (np.asarray(work.slot) < slots))
    assert np.all((np.asarray(work.page) >= 0)
                  & (np.asarray(work.page) <= slots * mpps))


def test_a_handed_work_list_is_the_one_built_inside():
    q, k, v, pk, pv, pt, ln = _paged_twin(6, 8, 2, 8, 4, RAGGED)
    work = paged_work_list(pt, ln, page_size=8)
    for layer in range(LAYERS):
        np.testing.assert_array_equal(
            np.asarray(paged_decode_attention(q, pk, pv, pt, ln,
                                              layer=layer, work=work)),
            np.asarray(paged_decode_attention(q, pk, pv, pt, ln,
                                              layer=layer)))


def test_layers_of_one_pool_differ():
    """``layer`` really indexes the pool: two layers of one pool give
    two answers (a block spec stuck on layer 0 would pass a one-layer
    comparison)."""
    q, k, v, pk, pv, pt, ln = _paged_twin(3, 4, 2, 4, 3, [5, 3, 1])
    outs = [np.asarray(paged_decode_attention(q, pk, pv, pt, ln, layer=i))
            for i in range(LAYERS)]
    for i in range(1, LAYERS):
        assert np.abs(outs[i] - outs[0]).max() > 1e-2


@pytest.mark.parametrize("h,kvh", [(4, 4), (4, 2), (4, 1)],
                         ids=["mha", "gqa", "mqa"])
def test_zero_length_slots_emit_zeros_finite(h, kvh):
    q, k, v, pk, pv, pt, ln = _paged_twin(3, h, kvh, 4, 3, [0, 5, 0])
    out = np.asarray(paged_decode_attention(q, pk, pv, pt, ln, layer=1))
    assert np.all(out[0] == 0) and np.all(out[2] == 0)
    assert np.any(out[1] != 0)
    assert np.all(np.isfinite(out))


def test_dead_table_entries_may_hold_any_page():
    """Entries of the page table beyond a slot's live pages are never
    read into the result, whatever valid page they name."""
    q, k, v, pk, pv, pt, ln = _paged_twin(3, 4, 2, 4, 3, [5, 3, 12])
    want = np.asarray(paged_decode_attention(q, pk, pv, pt, ln, layer=0))
    live = (np.asarray(ln)[:, None] + 3) // 4 > np.arange(3)[None, :]
    trash = pk.shape[0] - 1
    got = np.asarray(paged_decode_attention(
        q, pk, pv, jnp.where(live, pt, trash), ln, layer=0))
    np.testing.assert_array_equal(got, want)


def test_four_dim_q_round_trips():
    q, k, v, pk, pv, pt, ln = _paged_twin(3, 4, 2, 4, 3, [5, 3, 1])
    out3 = paged_decode_attention(q, pk, pv, pt, ln, layer=2)
    out4 = paged_decode_attention(q[:, :, None, :], pk, pv, pt, ln,
                                  layer=2)
    assert out3.shape == (3, 4, 16)
    assert out4.shape == (3, 4, 1, 16)
    np.testing.assert_array_equal(np.asarray(out4[:, :, 0]),
                                  np.asarray(out3))


def test_sm_scale_is_applied():
    q, k, v, pk, pv, pt, ln = _paged_twin(3, 4, 2, 4, 3, [5, 3, 9])
    dense = decode_attention(q, k[1], v[1], ln, sm_scale=0.5,
                             use_kernel=False)
    kern = paged_decode_attention(q, pk, pv, pt, ln, layer=1,
                                  sm_scale=0.5)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_every_call_is_the_one_kernel():
    """One path: the traced program is the ``apex_paged_decode``
    pallas_call, whatever the window, and gathers nothing but what the
    work list's builder does (int32 table entries): never the pool."""
    for mpps in (1, 3, 64):
        q, k, v, pk, pv, pt, ln = _paged_twin(2, 4, 2, 4, mpps, [3, 1])
        jaxpr = str(jax.make_jaxpr(
            lambda *a: paged_decode_attention(*a, layer=0))(
                q, pk, pv, pt, ln))
        assert jaxpr.count("pallas_call") == 1
        assert "apex_paged_decode" in jaxpr
        assert jaxpr.count("gather") == str(jax.make_jaxpr(
            lambda *a: paged_work_list(*a, page_size=4))(pt, ln)).count(
                "gather")


def _bad_pool(case):
    q, k, v, pk, pv, pt, ln = _paged_twin(3, 4, 2, 4, 3, [5, 3, 1])
    return {
        "q_len": (jnp.zeros((3, 4, 2, 16)), pk, pv, pt, ln, 0,
                  "q_len == 1"),
        "k_v_differ": (q, pk, pv[:, :, :, :2], pt, ln, 0, "equal-shaped"),
        "one_layer_slice": (q, pk[:, 0], pv[:, 0], pt, ln, 0,
                            "whole pool"),
        "head_dim": (q, pk[..., :8], pv[..., :8], pt, ln, 0,
                     "whole pool"),
        "layer_high": (q, pk, pv, pt, ln, LAYERS, "outside the pool"),
        "layer_negative": (q, pk, pv, pt, ln, -1, "outside the pool"),
        "kv_heads": (q, jnp.zeros((5, 2, 3, 4, 16)),
                     jnp.zeros((5, 2, 3, 4, 16)), pt, ln, 0,
                     "must divide"),
        "page_table": (q, pk, pv, pt[:2], ln, 0, "page_table"),
        "lengths": (q, pk, pv, pt, ln[:2], 0, "lengths"),
    }[case]


@pytest.mark.parametrize("case", [
    "q_len", "k_v_differ", "one_layer_slice", "head_dim", "layer_high",
    "layer_negative", "kv_heads", "page_table", "lengths"])
def test_validates_shapes(case):
    q, pk, pv, pt, ln, layer, match = _bad_pool(case)
    with pytest.raises(ValueError, match=match):
        paged_decode_attention(q, pk, pv, pt, ln, layer=layer)


def test_layer_is_required():
    q, k, v, pk, pv, pt, ln = _paged_twin(3, 4, 2, 4, 3, [5, 3, 1])
    with pytest.raises(TypeError):
        paged_decode_attention(q, pk, pv, pt, ln)


# --------------------------------------------------------------------------
# the latent form (ISSUE 34): one pool, no KV-head axis, no value array
# --------------------------------------------------------------------------

from apex_tpu.ops.attention import mha_reference  # noqa: E402

WIDTH, VALUES = 24, 16          # a cached row, and its leading columns


def _latent_twin(lengths, ps=8, mpps=4, h=4, seed=0, layers=LAYERS):
    """(q, the rows of every slot ``[layers, slots, max_seq, WIDTH]``, the
    same rows paged under a scrambled table, lengths); dead pages hold
    garbage."""
    rng = np.random.RandomState(seed)
    slots = len(lengths)
    n_pages = slots * mpps
    q = rng.randn(slots, h, WIDTH).astype(np.float32)
    rows = rng.randn(layers, slots, ps * mpps, WIDTH).astype(np.float32)
    pool = rng.randn(n_pages + 1, layers, WIDTH, ps).astype(np.float32)
    perm = rng.permutation(n_pages).reshape(slots, mpps)
    for s in range(slots):
        for j in range(mpps):       # a page: its positions the minor axis
            pool[perm[s, j]] = rows[:, s, j * ps:(j + 1) * ps].transpose(
                0, 2, 1)
    return (jnp.asarray(q), rows, jnp.asarray(pool),
            jnp.asarray(perm, jnp.int32), jnp.asarray(lengths, jnp.int32))


def _gathered(q, rows, lengths, layer, scale):
    """``mha_reference`` over each slot's LIVE rows, gathered: the keys the
    whole row, the values its leading columns; an empty slot gives zeros."""
    out = np.zeros((len(lengths), q.shape[1], VALUES), np.float32)
    for s, n in enumerate(np.asarray(lengths)):
        if n:
            live = jnp.asarray(rows[layer, s, :n])
            k = jnp.broadcast_to(live[None, None], (1, q.shape[1], n, WIDTH))
            out[s] = np.asarray(mha_reference(
                q[s][None, :, None, :], k, k[..., :VALUES],
                sm_scale=scale))[0, :, 0]
    return out


@pytest.mark.parametrize("lengths", [
    [0, 1, 29], [8, 9, 7], [32, 0, 16, 1], [0], [1], [17]],
    ids=["none_one_many", "a_page_boundary", "full_empty_two_one",
         "empty_slot", "one_row", "mid_page"])
@pytest.mark.parametrize("layer", [0, LAYERS - 1])
def test_latent_form_matches_a_gathered_reference(lengths, layer):
    q, rows, pool, pt, ln = _latent_twin(lengths)
    got = paged_decode_attention(q, pool, None, pt, ln, layer=layer,
                                 sm_scale=0.3, values=VALUES)
    assert got.shape == (len(lengths), 4, VALUES)
    np.testing.assert_allclose(
        np.asarray(got), _gathered(q, rows, lengths, layer, 0.3),
        rtol=2e-5, atol=2e-5)


def test_latent_form_in_bf16_and_through_a_handed_work_list():
    q, rows, pool, pt, ln = _latent_twin([5, 0, 32, 9])
    work = paged_work_list(pt, ln, page_size=8)
    got = paged_decode_attention(
        q.astype(jnp.bfloat16), pool.astype(jnp.bfloat16), None, pt, ln,
        layer=1, sm_scale=0.3, values=VALUES, work=work)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        _gathered(q, rows, [5, 0, 32, 9], 1, 0.3), rtol=3e-2, atol=3e-2)


def test_latent_form_is_its_own_named_call_on_the_same_work_list():
    """One ``pallas_call`` named for the form, one pool operand; the
    per-head-K/V form keeps its name."""
    q, _, pool, pt, ln = _latent_twin([5, 0, 32])
    text = str(jax.make_jaxpr(lambda *a: paged_decode_attention(
        a[0], a[1], None, a[2], a[3], layer=0, sm_scale=0.3,
        values=VALUES))(q, pool, pt, ln))
    assert text.count("apex_paged_decode_latent") >= 1
    assert "name=apex_paged_decode " not in text.replace(",", " ")


@pytest.mark.parametrize("case", ["no_scale", "no_values", "five_dim_pool",
                                  "values_too_wide"])
def test_latent_form_validates(case):
    q, _, pool, pt, ln = _latent_twin([5, 0, 32])
    kw = dict(layer=0, sm_scale=0.3, values=VALUES)
    if case == "no_scale":
        kw["sm_scale"] = None
    elif case == "no_values":
        kw["values"] = None
    elif case == "values_too_wide":
        kw["values"] = WIDTH + 1
    else:
        pool = pool[:, :, None]
    with pytest.raises(ValueError, match="latent form"):
        paged_decode_attention(q, pool, None, pt, ln, **kw)


# --------------------------------------------------------------------------
# attention over PICKED positions only (ISSUE 36): apex_dsa_attend
# --------------------------------------------------------------------------

def _picked_case(case, lengths, max_seq, rng):
    """Which positions each slot picked, ``[slots, max_seq]`` bool."""
    picked = np.zeros((len(lengths), max_seq), bool)
    for s, n in enumerate(lengths):
        if not n:
            continue
        if case == "all":
            picked[s, :n] = True
        elif case == "one_row":
            picked[s, rng.randint(n)] = True
        elif case == "one_page":            # all picks inside page 1 (or 0)
            lo = 8 if n > 8 else 0
            picked[s, lo:min(n, lo + 8)] = True
        elif case == "straddle":            # rows either side of a boundary
            picked[s, max(0, min(n, 8) - 3):min(n, 8 + 3)] = True
        elif case == "none":
            pass
        else:                               # scattered: about a third
            picked[s, :n] = rng.rand(n) < 0.35
            picked[s, n - 1] = True
    return picked


@pytest.mark.parametrize("case", ["scattered", "one_row", "one_page",
                                  "straddle", "all", "none"])
@pytest.mark.parametrize("h,kvh", [(8, 2), (4, 4)], ids=["gqa", "mha"])
def test_select_attention_matches_the_picked_rows_gathered_by_hand(
        case, h, kvh):
    """Slots of 0, 1 and many pages in one call — an empty slot, one of a
    single row, a straggler — against ``mha_reference`` over the picked
    rows gathered by hand from the dense twin."""
    from apex_tpu.ops.attention import mha_reference
    from apex_tpu.ops.paged_attention import paged_select_attention
    lengths = [32, 0, 1, 7, 9, 25]
    q, k, v, pk, pv, pt, ln = _paged_twin(6, h, kvh, 8, 4, lengths, seed=4)
    picked = _picked_case(case, lengths, 32, np.random.RandomState(5))
    work = paged_work_list(pt, ln, page_size=8)
    layer = 1
    got = np.asarray(paged_select_attention(
        q, pk, pv, jnp.asarray(picked), work, layer=layer))
    group = h // kvh
    for s in range(6):
        rows = np.flatnonzero(picked[s])
        if not len(rows):                   # nothing attended: zeros
            assert not got[s].any()
            continue
        ks = np.repeat(np.asarray(k[layer, s])[:, rows], group, axis=0)
        vs = np.repeat(np.asarray(v[layer, s])[:, rows], group, axis=0)
        want = mha_reference(q[s][None, :, None, :], jnp.asarray(ks)[None],
                             jnp.asarray(vs)[None])[0, :, 0]
        np.testing.assert_allclose(got[s], np.asarray(want), atol=2e-5,
                                   rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["fp32", "bf16"])
def test_a_slot_that_picked_everything_gets_the_dense_kernels_answer(dtype):
    """Every slot whose context is at most the selection's size attends
    all of it: ``apex_dsa_attend`` then gives ``apex_paged_decode``'s
    answer bit for bit (the same walk, the same products)."""
    from apex_tpu.ops.paged_attention import paged_select_attention
    q, k, v, pk, pv, pt, ln = _paged_twin(6, 8, 2, 8, 4, RAGGED)
    q, pk, pv = (t.astype(dtype) for t in (q, pk, pv))
    live = jnp.arange(32)[None] < ln[:, None]
    work = paged_work_list(pt, ln, page_size=8)
    for layer in (0, LAYERS - 1):
        want = paged_decode_attention(q, pk, pv, pt, ln, layer=layer,
                                      work=work)
        got = paged_select_attention(q, pk, pv, live, work, layer=layer)
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(want, np.float32))
        # picks beyond a slot's length are dead whatever they say
        got = paged_select_attention(q, pk, pv, jnp.ones_like(live), work,
                                     layer=layer)
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(want, np.float32))


def test_the_selecting_stages_are_named_calls_on_the_same_work_list():
    from apex_tpu.ops.paged_attention import (paged_index_scores,
                                              paged_select_attention)
    q, k, v, pk, pv, pt, ln = _paged_twin(6, 8, 2, 8, 4, RAGGED)
    ik = jnp.zeros((pk.shape[0], LAYERS, 4, 8), jnp.float32)
    work = paged_work_list(pt, ln, page_size=8)

    def step(q, pk, pv, ik, work):
        scores = paged_index_scores(q[:, :2, :4], jnp.ones((6, 2)), ik,
                                    work, layer=1)
        return paged_select_attention(q, pk, pv, scores > -1.0, work,
                                      layer=1)
    text = str(jax.make_jaxpr(step)(q, pk, pv, ik, work))
    assert text.count("name=apex_dsa_index") == 1
    assert text.count("name=apex_dsa_attend") == 1
    assert "name=apex_paged_decode" not in text
    with pytest.raises(ValueError, match="whole pool"):
        paged_select_attention(q, pk[:, 0], pv[:, 0],
                               jnp.ones((6, 32), bool), work, layer=0)
    with pytest.raises(ValueError, match="whole pages"):
        paged_select_attention(q, pk, pv, jnp.ones((6, 30), bool), work,
                               layer=0)
    with pytest.raises(ValueError, match="outside the pool"):
        paged_index_scores(q[:, :2, :4], jnp.ones((6, 2)), ik, work,
                           layer=LAYERS)
