"""Between two decode steps the host makes ONE read and launches NO eager
op (ISSUE 35): retiring a slot is one launch of one compiled, donated
metadata update, and everything the host needs of a step — tokens, flags,
counters — comes back in the one int32 vector ``step_vector.host_vector``
lays out as ``[tokens | flags | stats tail]`` and ``peel_step`` peels.
Toy sizes on the CPU: what is held here is what the program COUNTS
(launches, eager applications, transfers) and the layout, never a time."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import core as jax_core

import test_axk1_parity as axk1_toy
import test_laguna_parity as laguna_toy
from apex_tpu import observability as obs
from apex_tpu.inference import (InferenceEngine, SamplingConfig,
                                SlotScheduler, kv_cache, models)
from apex_tpu.inference.sampling import greedy
from apex_tpu.inference.step_vector import peel_step
from apex_tpu.observability import MetricsRegistry, ServeTelemetry
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider

SLOTS, PAGE = 2, 4


def _model(kind):
    """``(cfg, float32 params)`` of a toy model of ``kind``; the expert
    kinds' are their parity tests' own."""
    if kind == "gpt":
        parallel_state.destroy_model_parallel()
        parallel_state.initialize_model_parallel(1)
        cfg = GPTConfig(vocab_size=96, hidden_size=32, num_layers=2,
                        num_attention_heads=2, max_seq_length=64,
                        hidden_dropout=0.0, attention_dropout=0.0)
        return cfg, gpt_model_provider(cfg).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    toy = {"laguna": laguna_toy, "axk1": axk1_toy}[kind]
    cfg, shapes = toy.binding.model_of(toy.TINY)
    return (dataclasses.replace(cfg, params_dtype=jnp.float32),
            toy.seeded(shapes, 5))


@pytest.fixture(scope="module")
def model():
    made = {}
    return lambda kind: made.setdefault(kind, _model(kind))


def _engine(model, kind, dense=False, spec_k=0):
    cfg, params = model(kind)
    layout = {} if dense else dict(page_size=PAGE, num_pages=40)
    return InferenceEngine(kind, cfg, params, slots=SLOTS, max_seq=64,
                           cache_dtype=jnp.float32, spec_k=spec_k,
                           sampling=SamplingConfig(), **layout)


REQUESTS = 5


def _open_wave(engine, seed=11):
    """A fresh scheduler with one seeded wave submitted and begun (its
    cache built): what is left is the passes."""
    rng = np.random.RandomState(seed)
    sched = SlotScheduler(engine,
                          telemetry=ServeTelemetry(MetricsRegistry()))
    for _ in range(REQUESTS):
        sched.submit(rng.randint(1, 90, size=rng.randint(3, 20)),
                     max_new_tokens=int(rng.randint(2, 7)))
    sched.begin_run()
    return sched


def _passes(sched):
    while sched.run_pending():
        sched.run_pass()
    assert len(sched.finish_run()) == REQUESTS


DISPATCHES = ("prefill", "decode", "evict", "cow", "verify", "swap_in",
              "swap_out")


def _dispatched():
    reg = obs.global_registry()
    return {n: reg.declared(f"infer_{n}_dispatch_total").total()
            for n in DISPATCHES}


@pytest.fixture()
def eager_ops(monkeypatch):
    """The names of the primitives applied eagerly, in order.  Every
    primitive bound outside a trace goes through
    ``jax.core.EvalTrace.process_primitive`` (an eager ``jnp.zeros``,
    ``jnp.asarray`` of a python int, ``dynamic_update_slice`` — each its
    own host→device launch).  A call of a ``jax.jit`` never reaches it.
    Both halves are private to jax, so both are held to a positive control
    here: a spy that a jax bump has silently unhooked fails the fixture and
    passes no test vacuously."""
    eager = []
    bind = jax_core.EvalTrace.process_primitive

    def spy(self, primitive, args, params):
        eager.append(primitive.name)
        return bind(self, primitive, args, params)
    monkeypatch.setattr(jax_core.EvalTrace, "process_primitive", spy)
    # what the parent's evict did, in small: seen, op by op
    row = jax.lax.dynamic_update_slice(
        jnp.zeros((4,), jnp.int32), jnp.full((1,), 7, jnp.int32), (1,))
    assert "dynamic_update_slice" in eager and len(eager) >= 3, eager
    del eager[:]
    assert np.asarray(jax.jit(lambda x: x + 1)(row)).tolist() == [1, 8, 1, 1]
    assert eager == []                              # a jit's launch is not
    return eager


@pytest.mark.parametrize("kind", ["gpt", "laguna", "axk1"])
def test_a_warm_wave_launches_its_steps_and_one_evict_a_request(
        kind, model, eager_ops):
    """After warm-up the passes of a wave of N requests launch its N
    prefills, its decode steps and N evictions — by the engine's dispatch
    counters — and apply NO primitive eagerly.

    How eager applications are counted: ``eager_ops`` (above) sees every
    primitive bound outside a trace and no launch of a ``jax.jit`` —
    those launches are the engine's own, and its dispatch counters count
    them.  The parent's ``evict_slot`` applied seven primitives here a
    retirement."""
    eng = _engine(model, kind)
    _passes(_open_wave(eng))                        # every shape compiled
    sched = _open_wave(eng)
    del eager_ops[:]
    before, compiles = _dispatched(), obs.compile_count()
    _passes(sched)
    after = _dispatched()
    assert eager_ops == [], eager_ops
    assert obs.compile_count() == compiles
    steps = int(sched.telemetry.decode_steps.total())
    assert steps > 0
    assert {n: after[n] - before[n] for n in DISPATCHES} == {
        "prefill": REQUESTS, "decode": steps, "evict": REQUESTS, "cow": 0,
        "verify": 0, "swap_in": 0, "swap_out": 0}


class _Counted:
    """Stands where an output of a step stood.  The host can get at its
    value only through ``__array__`` — ``np.asarray`` and
    ``jax.device_get`` both end there — and each time it does is one
    device→host transfer of the array behind, noted by its shape.  No hook
    inside jax does for this: on the CPU ``np.asarray(jax.Array)`` reads
    the buffer in place through the buffer protocol and passes every
    python-level method by."""

    def __init__(self, array, reads):
        self._array, self._reads = array, reads

    def copy_to_host_async(self):           # a request is not a read
        self._array.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self._reads.append(self._array.shape)
        return np.asarray(jax.device_get(self._array), dtype=dtype)


@pytest.fixture()
def host_reads(monkeypatch):
    """``host_reads(engine)``: the shapes of the step outputs the host
    reads from here on, in order — ``prefill``, ``decode`` and ``verify``
    hand the scheduler every output but the cache as a :class:`_Counted`.
    On a chip ``jax.transfer_guard_device_to_host`` refuses the implicit
    reads besides (the tests below run under it; the CPU's arrays are host
    memory and never trip it)."""
    reads = []
    # the positive control: an implicit and an explicit read are each
    # counted, a mere request is not
    one, two = (_Counted(a, reads)
                for a in jax.jit(lambda: (jnp.arange(3), jnp.arange(2)))())
    two.copy_to_host_async()
    assert reads == []
    assert np.asarray(one).tolist() == [0, 1, 2]
    assert jax.device_get({"a": two})["a"].tolist() == [0, 1]
    assert reads == [(3,), (2,)], reads
    del reads[:]

    def count(engine):
        for name in ("prefill", "decode", "verify"):
            step = getattr(engine, name)

            def counted(*args, _step=step, **kw):
                cache, *outs = _step(*args, **kw)
                return (cache, *(_Counted(o, reads) for o in outs))
            monkeypatch.setattr(engine, name, counted)
        return reads
    return count


@pytest.mark.parametrize("step", ["decode", "verify"])
def test_a_pass_makes_one_device_to_host_transfer(step, model, host_reads):
    """A pass that only decodes (or verifies) reads ONE of a step's
    outputs, once and explicitly: the ``[tokens | flags | tail]`` vector
    — a verify step's own, a decode step's of the step the pass BEFORE
    launched (ISSUE 37: the pass launches the next step first; the order
    is held in ``test_run_ahead.py``).  Until ISSUE 35 a pass read
    ``toks`` then ``truncated`` (verify: and ``n_emit``)."""
    k = 3 if step == "verify" else 0
    eng = _engine(model, "gpt", spec_k=k)
    reads = host_reads(eng)
    sched = SlotScheduler(eng, prefix_cache=False,
                          telemetry=ServeTelemetry(MetricsRegistry()))
    for prompt in ([5, 6, 7, 8, 9], [11, 12, 13]):
        sched.submit(prompt, max_new_tokens=24)
    sched.begin_run()
    sched.run_pass()            # admits and prefills both, launches a step
    steps = {"decode": sched.telemetry.decode_steps,
             "verify": sched.telemetry.spec_verify_steps}[step]
    done = steps.total()
    for _ in range(3):
        del reads[:]
        with jax.transfer_guard_device_to_host("disallow"):
            sched.run_pass()
        assert steps.total() == done + 1
        done += 1
        assert reads == [(SLOTS * (k + 1) + SLOTS * (2 if k else 1),)]
    while sched.run_pending():
        sched.run_pass()
    sched.finish_run()


def test_a_prefill_makes_one_device_to_host_transfer(model, host_reads):
    eng = _engine(model, "laguna")
    reads = host_reads(eng)
    sched = SlotScheduler(eng, telemetry=ServeTelemetry(MetricsRegistry()))
    sched.submit([5, 6, 7, 8, 9], max_new_tokens=1)   # retires at prefill
    sched.begin_run()
    with jax.transfer_guard_device_to_host("disallow"):
        sched.run_pass()
    assert reads == [(1 + eng.stats_tail,)]
    assert not sched.run_pending()
    sched.finish_run()


def _admit(eng, prompts, room):
    """A cache with ``prompts`` prefilled into slots 0.., each with
    ``room[slot]`` positions beyond its prompt; the prefills' tokens."""
    cache, last = eng.init_cache(), np.zeros((eng.slots,), np.int32)
    alloc = eng.new_allocator() if eng.paged else None
    for slot, prompt in enumerate(prompts):
        pages = alloc.acquire(alloc.pages_needed(
            len(prompt) + room[slot])) if eng.paged else None
        cache, tok, _ = eng.prefill(cache, prompt, slot, pages=pages)
        last[slot] = np.asarray(tok).reshape(-1)[0]
    return cache, last


@pytest.mark.parametrize("kind,dense", [("gpt", False), ("gpt", True),
                                        ("laguna", False),
                                        ("axk1", False)])
def test_a_decode_steps_vector_peels_back_to_what_the_step_returns(
        kind, dense, model):
    """``[tokens | truncated | stats tail]``: the tokens are the greedy
    ones of the logits the step returns, the flags the ``truncated`` it
    returns beside (slot 0 sits AT its capacity, so one flag is set), the
    tail the record's counters as a plain unpacked step computes them —
    for a kind with ``stats`` and without, paged and dense."""
    eng = _engine(model, kind, dense=dense)
    rec = models.KINDS[kind]
    # slot 0 is full to its last position: 8 of 2 pages, or all of max_seq
    full = 64 if dense else 2 * PAGE
    cache, last = _admit(eng, [list(range(1, full + 1)), [3, 4, 5]], [0, 9])
    active = np.ones((SLOTS,), bool)

    @jax.jit
    def unpacked(cache, params):
        _, cache, stats = models.decode_forward(
            kind, eng.cfg, params, cache, last, active=active)
        cache, _ = kv_cache.advance(cache, active)
        return models.stats_tail(rec.stats, stats, cache)
    want_tail = np.asarray(unpacked(cache, eng.params)) if rec.stats \
        else np.zeros((0,), np.int32)
    cache, host, logits, truncated = eng.decode(cache, last, active)
    host = np.asarray(host)
    assert host.dtype == np.int32 \
        and host.shape == (2 * SLOTS + len(rec.stats),)
    toks, flags, tail = peel_step(host, SLOTS, eng.stats_tail)
    np.testing.assert_array_equal(toks, np.asarray(greedy(logits)))
    np.testing.assert_array_equal(flags, np.asarray(truncated))
    assert flags.tolist() == [1, 0]
    np.testing.assert_array_equal(tail, want_tail)
    assert tail.shape == (len(rec.stats),)


@pytest.mark.parametrize("dense", [False, True])
def test_a_verify_steps_vector_peels_back_to_what_the_step_returns(
        dense, model):
    """``[tokens [slots * (k+1)] | n_emit | truncated]`` against the
    ``n_emit`` and ``truncated`` the step returns beside and the greedy
    tokens of a plain verify forward."""
    k = 3
    eng = _engine(model, "gpt", dense=dense, spec_k=k)
    # slot 0 has room for ONE more position: its two tokens do not fit
    full = 63 if dense else 2 * PAGE - 1
    cache, last = _admit(eng, [list(range(1, full + 1)), [3, 4, 5]], [1, 9])

    @jax.jit
    def unpacked(cache, params, slab):
        logits, _ = models.verify_forward("gpt", eng.cfg, params, cache,
                                          slab)
        return greedy(logits.astype(jnp.float32))
    slab = np.full((SLOTS, k + 1), 7, np.int32)
    slab[:, 0] = last
    first = np.asarray(unpacked(cache, eng.params, slab))[:, 0]
    slab[:, 1] = first                      # one draft right, the next wrong
    slab[:, 2] = 95 - np.asarray(unpacked(cache, eng.params, slab))[:, 1]
    want = np.asarray(unpacked(cache, eng.params, slab))
    cache, host, n_emit, truncated = eng.verify(cache, slab)
    host = np.asarray(host)
    assert host.dtype == np.int32 and host.shape == (SLOTS * (k + 3),)
    toks, flags, tail = peel_step(host, SLOTS * (k + 1))
    emit, flags = flags.reshape(2, SLOTS)
    np.testing.assert_array_equal(toks.reshape(SLOTS, k + 1), want)
    np.testing.assert_array_equal(emit, np.asarray(n_emit))
    np.testing.assert_array_equal(flags, np.asarray(truncated))
    assert emit.tolist() == [2, 2] and flags.tolist() == [1, 0]
    assert tail.size == 0


@pytest.mark.parametrize("kind", ["gpt", "laguna", "axk1"])
def test_a_compiled_evict_parks_the_row_before_its_pages_move_on(
        kind, model):
    """The stale-row guard at the engine's level: after ``evict_slot``
    the slot's page-table row is the trash page and its ``capacity`` 0
    BEFORE its pages are released, so when the next admission gets the
    very same pages the idle slot's masked appends land in the trash page
    and the newcomer's rows stay what its prefill wrote.  The program is
    the one jitted in the constructor: the pool is donated through it and
    no slot compiles it again."""
    eng = _engine(model, kind)
    alloc, cache = eng.new_allocator(), eng.init_cache()
    pages = alloc.acquire(3)
    cache, _, _ = eng.prefill(cache, list(range(1, 10)), 0, pages=pages)
    pool = cache.k
    cache = eng.evict_slot(cache, 0)
    assert pool.is_deleted()                        # donated, not copied
    table = np.asarray(cache.page_table)
    assert (table[0] == cache.null_page).all()
    assert np.asarray(cache.capacity).tolist() == [0, 0]
    assert np.asarray(cache.lengths).tolist() == [0, 0]
    # only now do the pages go back — and straight out again
    alloc.release(pages)
    again = alloc.acquire(3)
    assert sorted(again) == sorted(pages)
    cache, tok, _ = eng.prefill(cache, [7, 8, 9, 10, 11], 1, pages=again)
    wrote = np.asarray(cache.k)[again].copy()
    last = np.array([0, np.asarray(tok).reshape(-1)[0]], np.int32)
    cache, _, _, _ = eng.decode(cache, last, np.array([False, True]))
    now = np.asarray(cache.k)[again]
    changed = np.argwhere((now != wrote).reshape(3, -1).any(axis=1))
    # slot 1's own append (position 5, its second page) and nothing else
    assert changed.reshape(-1).tolist() == [1]
    compiles = obs.compile_count()
    cache = eng.evict_slot(cache, 1)                # another slot, no compile
    assert obs.compile_count() == compiles
    assert (np.asarray(cache.page_table) == cache.null_page).all()
