"""The device runs one decode step ahead of the host's read (ISSUE 37): a
pass launches its prefills and decode step N+1 BEFORE it reads step N, the
tokens are fed back on the device (``cache.last_tokens``), and everything
that ends a request except an EOS is a count the host keeps of what it has
LAUNCHED.  Toy sizes on the CPU: what is held here is the ORDER of launches
and reads, what is counted, and that the tokens are those of a loop that
reads every vector before its next launch and uploads every token — never a
time."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_keye_parity as keye_toy
import test_pass_boundary as pass_boundary
from apex_tpu.inference import (InferenceEngine, SamplingConfig,
                                SlotScheduler)
from apex_tpu.inference.step_vector import peel_step
from apex_tpu.observability import MetricsRegistry, ServeTelemetry

SLOTS = 2
#: per kind: (max_seq, page) — the parity suites' own toy geometry
GEOMETRY = {"gpt": (64, 4), "laguna": (64, 4), "axk1": (64, 4),
            "keye": (128, 8)}


def _model(kind):
    """``(cfg, float32 params)`` of a toy model of ``kind``:
    ``test_pass_boundary``'s, and the selecting kind's parity suite's own."""
    if kind != "keye":
        return pass_boundary._model(kind)
    cfg, shapes = keye_toy.binding.model_of(keye_toy.TINY)
    return (dataclasses.replace(cfg, params_dtype=jnp.float32),
            keye_toy.seeded(shapes, 5))


@pytest.fixture(scope="module")
def model():
    made = {}
    return lambda kind: made.setdefault(kind, _model(kind))


def _engine(model, kind, dense=False, spec_k=0, slots=SLOTS):
    cfg, params = model(kind)
    max_seq, page = GEOMETRY[kind]
    layout = {} if dense else dict(page_size=page, num_pages=40)
    return InferenceEngine(kind, cfg, params, slots=slots, max_seq=max_seq,
                           cache_dtype=jnp.float32, spec_k=spec_k,
                           sampling=SamplingConfig(), **layout)


def _scheduler(eng, **kw):
    return SlotScheduler(eng, telemetry=ServeTelemetry(MetricsRegistry()),
                         **kw)


def _wave(seed, n=5, top=90):
    """``[(prompt, budget), ...]`` of one seeded wave."""
    rng = np.random.RandomState(seed)
    return [(rng.randint(1, top, size=rng.randint(3, 20)).tolist(),
             int(rng.randint(2, 8))) for _ in range(n)]


def _read_first(eng, prompt, budget):
    """The request's greedy stream from a loop that READS every step's
    vector before it launches the next and UPLOADS every token — the order
    the serving loop kept until ISSUE 37 — alone in slot 0 of a fresh
    cache.  Stops where the slot's reservation does."""
    cache = eng.init_cache()
    pages = None
    capacity = eng.max_seq
    if eng.paged:
        alloc = eng.new_allocator()
        pages = alloc.acquire(alloc.pages_needed(len(prompt) + budget))
        capacity = min(len(pages) * eng.page_size, eng.max_seq)
    cache, tok, _ = eng.prefill(cache, prompt, 0, pages=pages)
    out = [int(np.asarray(tok).reshape(-1)[0])]
    active = np.zeros((eng.slots,), bool)
    active[0] = True
    while len(out) < budget and len(prompt) + len(out) - 1 < capacity:
        last = np.zeros((eng.slots,), np.int32)
        last[0] = out[-1]
        cache, host, _, _ = eng.decode(cache, last, active)
        out.append(int(peel_step(np.asarray(host), eng.slots)[0][0]))
    return out


def _drain(sched):
    while sched.run_pending():
        sched.run_pass()
    return sched.finish_run()


# -- (a) the tokens are those of a loop that reads before it launches --------

@pytest.mark.parametrize("kind,dense", [
    ("gpt", False), ("gpt", True), ("laguna", False), ("axk1", False),
    ("keye", False)])
def test_a_wave_served_ahead_gives_the_tokens_of_a_loop_that_reads_first(
        kind, dense, model):
    """GPT on both layouts, a window kind, the latent kind and the
    selecting kind: five requests through two slots, served with the device
    a step ahead and the tokens fed back on the device, get token for token
    what each gets alone from a loop that reads before every launch and
    feeds the tokens from the host."""
    eng = _engine(model, kind, dense=dense)
    wave = _wave(11)
    sched = _scheduler(eng)
    uids = [sched.submit(p, max_new_tokens=b) for p, b in wave]
    sched.begin_run()
    out = _drain(sched)
    tel = sched.telemetry
    for uid, (prompt, budget) in zip(uids, wave):
        assert out[uid] == _read_first(eng, prompt, budget), uid
    # every decode launch found the step before it, or its pass's prefill,
    # unread; no token was thrown away (no request names an EOS)
    assert tel.decode_steps_ahead.total() == tel.decode_steps.total() > 0
    assert tel.ahead_tokens_discarded.total() == 0


# -- (b) the order and the count of what crosses the boundary ---------------

class _Vector:
    """Stands where a step's vector stood and notes when the host reads
    it (``np.asarray`` and ``jax.device_get`` both end in ``__array__``)."""

    def __init__(self, array, log, name):
        self._array, self._log, self._name = array, log, name

    def __array__(self, dtype=None, copy=None):
        self._log.append(("read", self._name))
        return np.asarray(jax.device_get(self._array), dtype=dtype)


@pytest.fixture()
def boundary(monkeypatch):
    """``boundary(engine)``: the log of what crosses between host and
    device from here on, in order — ``("launch", "decode 3")`` with the
    host arrays the launch uploads (``uploads``), ``("read", "decode 3")``
    when the host gets at that step's vector."""
    def watch(engine):
        log, uploads, n = [], [], {"prefill": 0, "decode": 0, "verify": 0}
        for name in ("prefill", "decode", "verify"):
            step = getattr(engine, name)

            def logged(*args, _step=step, _name=name, **kw):
                n[_name] += 1
                tag = f"{_name} {n[_name]}"
                log.append(("launch", tag))
                cache, host, *rest = _step(*args, **kw)
                return (cache, _Vector(host, log, tag), *rest)
            monkeypatch.setattr(engine, name, logged)
        jitted = engine._decode

        def counted(*args):
            # what a decode launch hands the runtime from the HOST: numpy
            # arrays (the cache, the weights and the key live on the
            # device; the launch counter is a numpy scalar, not an array)
            uploads.append([a.shape for a in jax.tree_util.tree_leaves(
                args[2:]) if isinstance(a, np.ndarray)])
            return jitted(*args)
        monkeypatch.setattr(engine, "_decode", counted)
        return log, uploads
    return watch


def test_a_pass_launches_the_next_step_before_it_reads_the_last(
        model, boundary):
    """One request, six tokens: its prefill and its first decode step are
    launched before the prefill's vector is read; every later pass launches
    step N+1 and THEN reads step N; the last pass launches nothing and reads
    the step in flight — one read a launch, each explicit and inside
    ``_read_step`` (the rest of the pass runs under a guard that refuses
    any other), and a decode launch uploads ``active`` and no token."""
    eng = _engine(model, "gpt")
    log, uploads = boundary(eng)
    sched = _scheduler(eng)
    sched.submit([5, 6, 7, 8, 9], max_new_tokens=6)
    sched.begin_run()
    passes = []
    while sched.run_pending():
        del log[:]
        with jax.transfer_guard_device_to_host("disallow"):
            sched.run_pass()
        passes.append(list(log))
    (tokens,) = sched.finish_run().values()
    assert passes == [
        [("launch", "prefill 1"), ("launch", "decode 1"),
         ("read", "prefill 1")],
        [("launch", "decode 2"), ("read", "decode 1")],
        [("launch", "decode 3"), ("read", "decode 2")],
        [("launch", "decode 4"), ("read", "decode 3")],
        [("launch", "decode 5"), ("read", "decode 4")],
        [("read", "decode 5")]]
    assert len(tokens) == 6
    assert uploads == [[(SLOTS,)]] * 5              # active, nothing else
    tel = sched.telemetry
    assert tel.decode_steps.total() == tel.decode_steps_ahead.total() == 5


def test_a_prefill_goes_behind_the_step_in_flight_and_is_read_in_its_pass(
        model, boundary):
    """A request admitted while another decodes: its prefill is launched
    behind the step in flight, the next step — which carries the new slot,
    its first token read from the device — behind the prefill, and only
    then does the host read: the step in flight first, the prefill after."""
    eng = _engine(model, "gpt")
    log, _ = boundary(eng)
    sched = _scheduler(eng)
    first = sched.submit([5, 6, 7, 8, 9], max_new_tokens=8)
    sched.begin_run()
    sched.run_pass()
    sched.run_pass()
    second = sched.submit([11, 12, 13], max_new_tokens=3)
    del log[:]
    sched.run_pass()
    assert log == [("launch", "prefill 2"), ("launch", "decode 3"),
                   ("read", "decode 2"), ("read", "prefill 2")]
    states = {st.uid: st for st in sched.slot_states() if st is not None}
    assert len(states[second].generated) == 1       # its first token
    assert states[second].issued == 2               # and one step launched
    out = _drain(sched)
    assert out[first] == _read_first(eng, [5, 6, 7, 8, 9], 8)
    assert out[second] == _read_first(eng, [11, 12, 13], 3)


# -- (c) an EOS is seen one step late ---------------------------------------

def test_an_eos_ends_the_request_and_its_one_extra_token_is_thrown_away(
        model, monkeypatch):
    """The request's EOS is sampled at step N; step N+1, launched before
    step N was read, still carried the slot.  The request ends AT the EOS,
    the extra token is counted and thrown away, the slot's ``evict_slot``
    is launched before its pages are released (so behind step N+1 and
    before anything that could reuse them), and the request admitted into
    the freed slot — whose entry in step N+1's vector belongs to nobody —
    is served token for token."""
    eng = _engine(model, "gpt", slots=1)
    prompt, later = [5, 6, 7, 8, 9], [21, 22, 23, 24]
    plain = _read_first(eng, prompt, 8)
    cut = next(i for i in range(1, 7) if plain[i] not in plain[:i])
    sched = _scheduler(eng, prefix_cache=False)
    order = []
    evict, release = eng.evict_slot, sched.alloc.release
    monkeypatch.setattr(eng, "evict_slot", lambda cache, slot: (
        order.append("evict"), evict(cache, slot))[1])
    monkeypatch.setattr(sched.alloc, "release", lambda ids: (
        order.append("release"), release(ids))[1])
    u1 = sched.submit(prompt, max_new_tokens=8, eos_id=plain[cut])
    u2 = sched.submit(later, max_new_tokens=4)
    sched.begin_run()
    out = _drain(sched)
    assert out[u1] == plain[:cut + 1]
    assert sched.finish_reasons[u1] == "eos"
    assert sched.telemetry.ahead_tokens_discarded.total() == 1
    assert order == ["evict", "release"] * 2
    assert out[u2] == _read_first(eng, later, 4)
    assert sched.finish_reasons[u2] == "length"
    assert sched.alloc.live_pages == 0


def test_an_eos_for_a_first_token_is_thrown_one_token_too(model):
    """The prefill's own token is the EOS: the decode step launched behind
    the prefill carried the slot, and its token is thrown away."""
    eng = _engine(model, "gpt")
    first = _read_first(eng, [5, 6, 7, 8, 9], 1)[0]
    sched = _scheduler(eng)
    uid = sched.submit([5, 6, 7, 8, 9], max_new_tokens=8, eos_id=first)
    sched.begin_run()
    assert _drain(sched)[uid] == [first]
    assert sched.finish_reasons[uid] == "eos"
    assert sched.telemetry.ahead_tokens_discarded.total() == 1


# -- (d) the capacity guard --------------------------------------------------

@pytest.mark.parametrize("dense", [False, True])
def test_a_slot_at_its_capacity_is_in_no_step_launched_ahead(
        dense, model, boundary):
    """Prompt + budget overrun the slot's capacity: the host names the
    steps' active sets from what it has LAUNCHED, so the slot is in no step
    past its capacity — the device's ``truncated`` flag, the belt to that
    suspender, never comes up — and it retires ``truncated`` with exactly
    the tokens a loop that reads first gives it."""
    eng = _engine(model, "gpt", dense=dense)
    log, _ = boundary(eng)
    sched = _scheduler(eng)
    flags = []
    read = sched._read_step

    def noting(host, phase, tokens):
        toks, fl = read(host, phase, tokens)
        if phase == "decode":
            flags.append(np.asarray(fl).copy())
        return toks, fl
    sched._read_step = noting
    prompt = list(range(1, 61))                     # 60 of 64 positions
    uid = sched.submit(prompt, max_new_tokens=30)
    other = sched.submit([7, 8, 9], max_new_tokens=12)
    sched.begin_run()
    out = _drain(sched)
    launches = sum(1 for what, tag in log
                   if what == "launch" and tag.startswith("decode"))
    assert launches == 11                           # the longer stream's
    assert flags and not np.concatenate(flags).any()
    assert sched.finish_reasons[uid] == "truncated"
    assert len(out[uid]) == 5                       # 60 + 5 - 1 = capacity
    assert out[uid] == _read_first(eng, prompt, 30)
    assert out[other] == _read_first(eng, [7, 8, 9], 12)


# -- (e) nothing stays in flight ---------------------------------------------

def test_a_drain_and_a_closed_wave_leave_no_launched_step_unread(
        model, boundary):
    eng = _engine(model, "gpt")
    log, _ = boundary(eng)
    sched = _scheduler(eng)
    sched.submit([5, 6, 7, 8, 9], max_new_tokens=4)
    sched.begin_run()
    sched.run_pass()
    sched.run_pass()
    sched.run_pass()
    # the request's last step is launched and unread: the wave is pending
    # though its next pass will launch nothing
    assert sched._ahead is not None and sched.run_pending()
    sched.run_pass()
    assert sched._ahead is None and not sched.run_pending()
    assert len(sched.finish_run()) == 1
    # a wave closed BEFORE it drained: finish_run reads what is in flight
    sched.submit([5, 6, 7, 8, 9], max_new_tokens=6)
    sched.begin_run()
    sched.run_pass()
    sched.run_pass()
    assert sched._ahead is not None
    del log[:]
    sched.finish_run()
    assert sched._ahead is None and log == [("read", "decode 5")]
    launched = {tag for what, tag in log if what == "launch"}
    assert not launched


# -- (f) a speculative wave launches, then reads -----------------------------

def test_a_speculative_wave_still_launches_then_reads(model, boundary):
    """The drafter drafts from tokens the host has read, so a wave of an
    engine built with ``spec_k`` reads each prefill and each verify step
    right after its launch, and counts no step as launched ahead."""
    eng = _engine(model, "gpt", spec_k=3)
    log, _ = boundary(eng)
    sched = _scheduler(eng, prefix_cache=False)
    uids = [sched.submit(p, max_new_tokens=9)
            for p in ([5, 6, 7, 8, 9], [11, 12, 13])]
    sched.begin_run()
    out = _drain(sched)
    assert [len(out[u]) for u in uids] == [9, 9]
    verifies = [i for i, (what, tag) in enumerate(log)
                if what == "launch" and tag.startswith("verify")]
    assert len(verifies) > 2
    for i in verifies:
        # read right behind its launch, with every earlier vector — the
        # prefills' first tokens, which the drafter drafts from — read
        assert log[i + 1] == ("read", log[i][1])
        assert ({tag for what, tag in log[:i] if what == "launch"}
                == {tag for what, tag in log[:i] if what == "read"})
    assert not any(tag.startswith("decode") for _, tag in log)
    tel = sched.telemetry
    assert tel.spec_verify_steps.total() > 0
    assert tel.decode_steps_ahead.total() == 0
    assert tel.ahead_tokens_discarded.total() == 0
    assert sched._ahead is None
    # the speculative stream is the plain engine's greedy stream
    plain = _engine(model, "gpt")
    assert out[uids[0]] == _read_first(plain, [5, 6, 7, 8, 9], 9)


# -- a sampled stream: a function of the seed and the requests ---------------

def test_a_sampled_wave_is_a_pure_function_of_the_seed_and_the_requests(
        model):
    """A stream sampled with a temperature folds the engine's LAUNCH
    counter into its key, and launches interleave differently since
    ISSUE 37 (a pass launches its decode step before a prefill's read;
    the next request's prefill goes behind a step already queued): the
    same seed and requests give the same streams, pinned here as this tree
    serves them — the tree before served ``[77, 9, 46, 82, 65, 52]``,
    ``[4, 87, 43, 74]``, ``[19, 73, 18, 76, 34]``."""
    cfg, params = model("gpt")
    wave = (([5, 6, 7, 8, 9], 6), ([11, 12, 13], 4), ([3, 4], 5))

    def serve():
        eng = InferenceEngine(
            "gpt", cfg, params, slots=SLOTS, max_seq=64, page_size=4,
            num_pages=40, cache_dtype=jnp.float32, seed=7,
            sampling=SamplingConfig(temperature=0.9))
        sched = _scheduler(eng)
        uids = [sched.submit(p, max_new_tokens=b) for p, b in wave]
        out = sched.run()
        return [out[u] for u in uids]
    streams = serve()
    assert streams == serve()
    assert streams == [[77, 9, 46, 82, 59, 59], [4, 87, 43, 74],
                       [73, 18, 76, 34, 43]]
