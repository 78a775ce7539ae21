"""Prefill/decode inference engine: each phase is ONE donated XLA
executable.

Workload split (the flash-attention/Megatron serving shape):

* **Prefill** — the whole prompt in one causal forward through the flash
  kernels, k/v for every layer parked into one cache slot
  (``kv_cache.insert``), the first token sampled from the last real
  position's logits.  Compiled once per prompt *bucket* (prompts pad up
  to a power-of-two length) with the cache donated.
* **Decode** — one token for EVERY slot per step: embed, per-layer
  qkv + cache append + ``decode_attention`` over the slot's live
  length, lm head, sampling, length advance — all in one jitted program
  with the cache donated, so the executable's cache output aliases its
  input and no per-step reallocation exists.  The step's PRNG key is
  derived in-program (``fold_in(key, step)``), so sampled decoding adds
  no second executable.  The step's INPUT tokens are device state too
  (``cache.last_tokens``, ISSUE 37): each step writes the tokens it
  sampled where the next reads them, so the serving loop launches step
  N+1 before it has read step N and uploads no token.

Cache layouts (ISSUE 6): the dense slot cache provisions ``max_seq``
per slot; ``page_size=``/``num_pages=`` switch to the ragged paged
pool — k/v in fixed-size pages threaded through a traced per-slot page
table (``paged_decode_attention`` per layer: the ``apex_paged_decode``
kernel walking the step's list of live pages straight through the
whole pool, whatever the kind or window), the host-side
``PageAllocator`` handing out reservations.  Same two executables,
same donation discipline; only the memory model (and the scheduler's
admission unit — pages, not slots) changes.

No host transfer appears anywhere in either jaxpr (audited by
``analysis/jaxpr_audit.py`` — the inference entries trace these exact
step builders); the only device<->host traffic is the scheduler reading
ONE small int32 vector a step (:func:`host_vector`: sampled tokens,
``truncated`` / ``n_emit`` flags, a kind's counters) — one step late,
while the next step runs — and uploading a decode step's ``active``
mask, which is the continuous-batching control loop by construction.
Retiring a slot is one more donated executable
(:meth:`InferenceEngine.evict_slot`), never an eagerly applied op.

Weights: any checkpoint that can produce the flat fp32 master restores
straight into the engine — :meth:`InferenceEngine.from_train_state`
exports bf16 params from ``FlatState.params(dtype=...)`` (gathering
shards if the state is ZeRO-sharded), and
:meth:`InferenceEngine.from_state_dict` consumes the contrib
``DistributedFused*`` shard-aware ``state_dict`` written at ANY dp.

BERT rides along as the encode-only path (``kind="bert"``): one jitted
bidirectional forward, no cache — prefill and decode degenerate to the
same executable-shape discipline with nothing to split.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from apex_tpu import observability as obs
from apex_tpu.inference import kv_cache, models
from apex_tpu.inference.step_vector import host_vector
from apex_tpu.inference.sampling import SamplingConfig, greedy, sample_token
from apex_tpu.inference.speculative import default_spec_k
from apex_tpu.ops.paged_attention import (decode_fusion as
                                          resolve_fusion_mode,
                                          resolve_decode_fusion)
from apex_tpu.transformer.parallel_state import serving_mesh

__all__ = ["InferenceEngine", "make_prefill_fn", "make_decode_fn",
           "make_verify_fn", "prefill_bucket", "serve_tp",
           "host_kv_tier_bytes"]

_HOST_TIER_ENV = "APEX_TPU_HOST_KV_TIER_BYTES"


def serve_tp() -> int:
    """Effective serving tensor-parallel width from ``APEX_TPU_SERVE_TP``
    (registered in ``analysis/env_registry.py``): unset/``0`` means
    single-chip; an explicit ``InferenceEngine(tp=)`` always wins."""
    raw = os.environ.get("APEX_TPU_SERVE_TP", "0").strip() or "0"
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"APEX_TPU_SERVE_TP must be an integer, got {raw!r}")
    if v < 0:
        raise ValueError(f"APEX_TPU_SERVE_TP must be >= 0, got {v}")
    return v or 1


def host_kv_tier_bytes() -> int:
    """Host-DRAM KV page tier byte budget from
    ``APEX_TPU_HOST_KV_TIER_BYTES`` (registered in
    ``analysis/env_registry.py``): unset/``0`` disables the tier (LRU
    eviction discards, the pre-ISSUE-18 behavior); an explicit
    ``InferenceEngine(host_tier_bytes=)`` always wins."""
    raw = os.environ.get(_HOST_TIER_ENV, "0").strip() or "0"
    try:
        v = int(raw)
    except ValueError:
        raise ValueError(
            f"{_HOST_TIER_ENV} must be an integer, got {raw!r}")
    if v < 0:
        raise ValueError(f"{_HOST_TIER_ENV} must be >= 0, got {v}")
    return v


def make_prefill_fn(kind: str, cfg, sampling: SamplingConfig,
                    paged: bool = False, tp: int = 1):
    """Pure prefill step.  Dense: ``(cache, params, tokens [s], slot,
    length, key, step) -> (cache, next_token, last_logits)``; paged
    takes extra ``row`` (the slot's ``[max_pages_per_slot]`` page-table
    row) and ``prefill_from`` operands after ``length``.

    ``prefill_from`` (ISSUE 12) is the number of prompt tokens already
    sitting in the slot's pages: ``tokens`` is then the bucket-padded
    UNCACHED TAIL, the forward attends to the cached prefix through the
    page window, and the insert scatters only the tail's rows —
    ``prefill_from == 0`` is the cold path (bitwise the original math).
    ``length`` is the slot's TOTAL live length after this step (real
    prefix + real tail inside the padded bucket).  Both operands are
    traced, so ONE compiled executable per bucket serves cold
    prefills, prefix-cache hits, and chunked-prefill chunks alike —
    sharing changes page-table rows, never device programs.

    The sampled token is also written to ``cache.last_tokens[slot]``
    (ISSUE 37): the slot's first decode step takes it from the device."""

    rec = models.KINDS[kind]
    # static facts of the kind: a kind without them traces none of it
    shares = "prefix_sharing" not in rec.refuses
    rings = bool(rec.dims(cfg)["window_layers"])

    def prefill_fn(cache, params, tokens, slot, length, key, step):
        # named_scope = metadata-only xprof regions (no prims added, so
        # the jaxpr/SPMD audits of these exact builders are unchanged)
        with obs.named_scope("apex_prefill_forward"):
            # length threads into the forward so the lm head projects
            # ONLY the last real position, not every bucket-padded row
            logits, ks, vs = models.prefill_forward(
                kind, cfg, params, tokens[None], length, tp=tp)[:3]
        with obs.named_scope("apex_prefill_cache_insert"):
            cache = kv_cache.insert(cache, slot, ks, vs, length)
        with obs.named_scope("apex_prefill_sample"):
            last = logits[0].astype(jnp.float32)            # [vocab]
            tok = sample_token(last, jax.random.fold_in(key, step),
                               sampling)
            # the slot's next decode step reads it from the device
            cache = kv_cache.feed_back(cache, tok, slot)
        return cache, tok, last

    def prefill_paged_fn(cache, params, tokens, slot, length, row,
                         prefill_from, key, step):
        # the pool layers' k/v go to the slot's pages; a kind with window
        # layers (ISSUE 30) also writes their last positions to its
        # rings, and prefills every prompt whole (prefill_from is held
        # to 0 by the engine); a kind that selects (ISSUE 36) writes its
        # index keys with its k/v; a kind with stats returns them as the
        # sampled token's tail, read in the one transfer the scheduler
        # already makes
        suffix = dict(cache=cache, row=row,
                      prefill_from=prefill_from) if shares else {}
        with obs.named_scope("apex_prefill_forward"):
            logits, ks, vs, wks, wvs, iks, stats = models.prefill_forward(
                kind, cfg, params, tokens[None], length, tp=tp, **suffix)
        with obs.named_scope("apex_prefill_cache_insert"):
            # a kind that never resumes starts on page 0: its write is the
            # aligned one, chosen here with no branch; a kind that shares
            # picks aligned or mid-page by the traced start, in-program
            cache = kv_cache.insert_tokens(
                cache, slot, ks, vs, length, row,
                prefill_from if shares else 0, iks)
            if rings:
                cache = kv_cache.insert_window(cache, slot, wks, wvs,
                                               length)
        with obs.named_scope("apex_prefill_sample"):
            last = logits[0].astype(jnp.float32)            # [vocab]
            tok = sample_token(last, jax.random.fold_in(key, step),
                               sampling)
            # the slot's next decode step reads it from the device (a
            # chunk's that is not the prompt's last is overwritten by
            # the next chunk's)
            cache = kv_cache.feed_back(cache, tok, slot)
            if rec.stats:
                tok = host_vector(
                    tok, tail=models.stats_tail(rec.stats, stats, cache))
        return cache, tok, last

    return prefill_paged_fn if paged else prefill_fn


def make_decode_fn(kind: str, cfg, sampling: SamplingConfig,
                   fused: bool = False, tp: int = 1):
    """Pure decode step: ``(cache, params, tokens [slots] | None,
    active [slots], key, step) -> (cache, host, logits, truncated)``.
    Every slot computes (static shape); only active slots advance their
    length, and ``truncated`` flags active slots already at capacity
    whose emitted token could NOT be appended (the caller must retire
    them — nothing is clamped silently).  Serves both cache layouts:
    the paged pool threads its page table through the same signature.

    ``tokens=None`` (ISSUE 37, how the engine serves): the step's input
    tokens are the device's own ``cache.last_tokens`` — what the last
    prefill or decode step sampled for each slot — and the tokens sampled
    here are written back there where ``active``, so step N+1 can be
    launched before the host has read step N.  An array in that place is
    a caller feeding tokens of its choice (a drafter's catch-up, a test);
    they are fed back the same way.

    ``host`` is :func:`host_vector`'s ``[next_tokens [slots] |
    truncated [slots] | stats tail]``, peeled by ``step_vector.peel_step``
    (``peel_step(host, slots)[0]`` are the tokens): the scheduler's pass
    reads that ONE array and nothing else of the step.  ``logits`` and
    ``truncated`` stay outputs of their own, never read by the serving
    loop: they are what the parity tests and the packed-layout test hold
    ``host`` to, and the capacity contract's tests read the flags there.

    ``fused`` (ISSUE 15, paged engines): the ``params`` operand becomes
    the pair ``(tree, fused_layers)`` and every transformer block runs
    as ONE Pallas kernel (``fused_block_decode``) — still ONE donated
    executable with the same outputs, selected statically at engine
    construction by ``APEX_TPU_DECODE_FUSION``; fusion off keeps the
    original per-op lowering bitwise."""

    rec = models.KINDS[kind]

    def decode_fn(cache, params, tokens, active, key, step):
        tree, fused_layers = params if fused else (params, None)
        if tokens is None:
            tokens = cache.last_tokens
        with obs.named_scope("apex_decode_forward"):
            logits, cache, stats = models.decode_forward(
                kind, cfg, tree, cache, tokens, fused=fused_layers, tp=tp,
                active=active)
        with obs.named_scope("apex_decode_sample"):
            logits = logits.astype(jnp.float32)
            toks = sample_token(logits, jax.random.fold_in(key, step),
                                sampling)
        with obs.named_scope("apex_decode_advance"):
            cache, truncated = kv_cache.advance(cache, active)
            # the active slots' next step takes these tokens from here,
            # whether or not the host has read them yet
            cache = kv_cache.feed_back(cache, toks, active)
            # tokens, flags and the counters' tail: one read for all
            host = host_vector(
                toks, truncated,
                tail=models.stats_tail(rec.stats, stats, cache)
                if rec.stats else None)
        return cache, host, logits, truncated

    return decode_fn


def make_verify_fn(kind: str, cfg, sampling: SamplingConfig, k: int,
                   tp: int = 1):
    """Pure speculative-verify step (ISSUE 15): ``(cache, params, slab
    [slots, k+1], active [slots], key, step) -> (cache, host, n_emit
    [slots], truncated)``; ``host`` is :func:`host_vector`'s ``[tokens
    [slots * (k+1)] | n_emit [slots] | truncated [slots]]`` — the ONE
    array the scheduler's pass reads; ``peel_step(host, slots * (k+1))``
    gives the ``tokens`` of the paragraphs below (reshape ``[slots,
    k+1]``) and the two flag vectors (no stats tail: every kind with
    ``stats`` refuses verify).  ``n_emit`` and ``truncated`` stay
    outputs of their own for the tests that hold ``host`` to them.

    ``slab`` column 0 is each slot's last confirmed (pending) token,
    columns ``1..k`` the drafted continuation.  ONE batched forward
    scores every slab position against the cache (the slab's k/v land
    at ``[lengths, lengths + k + 1)`` first — the paged layout makes
    this the same one-scatter-per-layer write decode uses), the
    longest draft prefix matching the target's own greedy tokens is
    accepted, and ``tokens[:, :n_emit]`` is the emitted stream —
    accepted drafts followed by the target's bonus/correction token,
    i.e. ALWAYS the target's greedy stream (a bad draft costs
    speculation upside, never output correctness; ``n_emit`` is in
    ``[1, k+1]``).

    Accept/reject is the length rollback the paged cache was built
    for: lengths advance by ``n_emit`` (``kv_cache.advance_by``), so
    the rejected tail's rows go dead-by-mask — pages were reserved at
    admission, nothing is released device-side, and the page-table
    rows are untouched.  Greedy-only in this round: rejection-sampled
    verification for temperature > 0 needs the draft DISTRIBUTION,
    which the drafter protocol does not carry yet.
    """
    if k < 1:
        raise ValueError(f"speculative verify needs k >= 1, got {k}")
    if not sampling.is_greedy:
        raise ValueError(
            "speculative verify is greedy-only (acceptance compares "
            "drafts against argmax; rejection sampling for "
            "temperature > 0 needs draft probabilities the drafter "
            "protocol does not carry)")

    def verify_fn(cache, params, slab, active, key, step):
        with obs.named_scope("apex_verify_forward"):
            logits, cache = models.verify_forward(kind, cfg, params,
                                                  cache, slab, tp=tp)
        with obs.named_scope("apex_verify_accept"):
            toks = greedy(logits.astype(jnp.float32))    # [slots, k+1]
            match = (toks[:, :-1] == slab[:, 1:]).astype(jnp.int32)
            # leading-match count: cumprod zeroes everything after the
            # first mismatch
            n_acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            n_emit = (n_acc + 1).astype(jnp.int32)
        with obs.named_scope("apex_verify_advance"):
            cache, truncated = kv_cache.advance_by(cache, active,
                                                   n_emit)
        return cache, host_vector(toks, n_emit, truncated), n_emit, \
            truncated

    return verify_fn


def prefill_bucket(n: int, max_seq: int, min_bucket: int = 64) -> int:
    """Smallest power-of-two bucket >= n (clamped to max_seq): prompts
    pad up to it so the prefill executable count stays O(log max_seq)."""
    if n < 1 or n > max_seq:
        raise ValueError(f"prompt length {n} outside [1, {max_seq}]")
    b = min_bucket
    while b < n:
        b *= 2
    return min(b, max_seq)


class PendingSwapOut:
    """In-flight device→host page drain (ISSUE 19): the batched
    gather dispatches have been issued but the blocking
    ``device_get``\\ s have not run yet.  ``resolve()`` fetches (once;
    idempotent) and returns the concatenated ``(k, v)`` numpy slabs.
    Safe to defer across later cache mutations: each batch's output is
    a fresh device buffer, not a view of the (donated) cache."""
    __slots__ = ("_batches", "_resolved")

    def __init__(self, batches):
        self._batches = batches        # [(k_dev, v_dev, valid_rows)]
        self._resolved = None

    @property
    def done(self) -> bool:
        """True once :meth:`resolve` has fetched (the wave-boundary
        drain or a racing hit already paid the ``device_get``)."""
        return self._resolved is not None

    def resolve(self):
        if self._resolved is None:
            ks = [np.asarray(jax.device_get(k_s))[:m]
                  for k_s, _, m in self._batches]
            vs = [np.asarray(jax.device_get(v_s))[:m]
                  for _, v_s, m in self._batches]
            self._resolved = (np.concatenate(ks, axis=0),
                              np.concatenate(vs, axis=0))
            self._batches = None       # free the device buffers
        return self._resolved


class InferenceEngine:
    """Serving engine over a standalone GPT/LLaMA/Laguna/A.X-K1/BERT —
    single-chip by default, tensor-parallel over a ``tp``-wide mesh on
    request (``gpt``/``llama``).

    What a generative kind is, the engine asks its record
    (``models.KINDS``), never its name: what the record ``refuses`` is
    refused at construction with the record's reason — for ``laguna``
    (ISSUE 30: expert FFN, window + full layers, a head count per layer)
    the dense cache, tp > 1, speculative verify, the host KV tier, the
    fused block kernel, and prefix sharing at the first prefill that asks;
    for ``axk1`` (ISSUE 34: latent attention over a pool with no KV-head
    axis, a share of the experts held) the same six — and a kind with
    ``stats`` appends ``stats_tail`` int32 counters to the tokens its
    prefill and decode return.  The pool's shape and a page's bytes come
    from the record's ``dims`` (``models.cache_row_values``).

    Static shape contract: ``slots`` concurrent sequences, each with a
    ``max_seq``-deep cache line, decode always batched over every slot.
    The host-side request plumbing lives in
    :class:`apex_tpu.inference.scheduler.SlotScheduler`; this class owns
    the device programs and the cache geometry.

    Tensor-parallel serving (ISSUE 17): ``tp=N`` (or
    ``APEX_TPU_SERVE_TP``) shards the param mirrors column/row-wise and
    the paged kv pool over kv heads across a private one-axis mesh
    (:func:`~apex_tpu.transformer.parallel_state.serving_mesh`) — a
    model whose dense mirrors + pool exceed one chip's HBM serves from
    ``tp`` chips at ~1/tp the per-chip footprint and compute.  Each
    step stays ONE donated executable (now a mesh program); the page
    table, allocator, prefix cache, and COW barrier are replicated and
    byte-identical to single-chip, so the scheduler never changes.
    Requires the paged cache and a generative model; per-slot outputs
    are replica-uniform and match the single-chip engine."""

    def __init__(self, kind: str, cfg, params, *, slots: int = 4,
                 max_seq: Optional[int] = None, dtype=None,
                 cache_dtype=jnp.bfloat16,
                 sampling: SamplingConfig = SamplingConfig(),
                 seed: int = 0, paged: bool = False,
                 page_size: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 decode_fusion=None, fusion_min_pages=None,
                 spec_k: Optional[int] = None,
                 tp: Optional[int] = None,
                 host_tier_bytes: Optional[int] = None,
                 swap_batch_pages: Optional[int] = None):
        # a generative kind is its record (models.KINDS); "bert", the
        # encode-only path, has none
        rec = None
        if kind != "bert":
            models.check_supported(kind, cfg)   # an unknown kind raises
            rec = models.KINDS[kind]
        self._refuses = rec.refuses if rec else {}
        #: the int32 counters a step appends to the tokens it returns,
        #: by name (the record's ``stats``), and how many they are; none
        #: for kinds without an expert FFN
        self.stats_names = rec.stats if rec else ()
        self.stats_tail = len(self.stats_names)
        #: can a cached prefix's pages be mapped into another slot, or
        #: a prefill resume mid-prompt?  Not over window rings
        self.supports_prefix_sharing = "prefix_sharing" not in self._refuses
        self.kind, self.cfg = kind, cfg
        self.slots = int(slots)
        self.max_seq = min(int(max_seq or cfg.max_seq_length),
                           cfg.max_seq_length)
        self.cache_dtype = cache_dtype
        self.sampling = sampling
        # paged mode (ISSUE 6): HBM bounded by the page POOL, not by
        # slots * max_seq — any paged kwarg opts in
        self.paged = bool(paged or page_size is not None
                          or num_pages is not None)
        if kind == "bert" and self.paged:
            raise ValueError("BERT is the encode-only path (no KV "
                             "cache); paged kwargs do not apply")
        if self.paged:
            self.page_size = int(page_size if page_size is not None
                                 else kv_cache.default_page_size())
            if self.page_size < 1 or (self.page_size &
                                      (self.page_size - 1)):
                raise ValueError(
                    f"page_size must be a positive power of two (so "
                    f"prefill buckets tile into whole pages), got "
                    f"{self.page_size}")
            if self.max_seq % self.page_size:
                raise ValueError(
                    f"max_seq ({self.max_seq}) must be a multiple of "
                    f"page_size ({self.page_size})")
            self.max_pages_per_slot = self.max_seq // self.page_size
            # default pool = dense-equivalent capacity; size it SMALLER
            # (the point of paging) to bound HBM by expected load
            self.num_pages = int(
                num_pages if num_pages is not None
                else self.slots * self.max_pages_per_slot)
            if self.num_pages < 1:
                raise ValueError(
                    f"num_pages must be >= 1, got {self.num_pages}")
            # host-DRAM page tier (ISSUE 18): explicit kwargs win, else
            # the registered env knobs; 0 bytes = tier off (eviction
            # discards, the pre-tier behavior)
            self.host_tier_bytes = int(
                host_tier_bytes if host_tier_bytes is not None
                else host_kv_tier_bytes())
            if self.host_tier_bytes < 0:
                raise ValueError(
                    f"host_tier_bytes must be >= 0, got "
                    f"{self.host_tier_bytes}")
            self.swap_batch_pages = int(
                swap_batch_pages if swap_batch_pages is not None
                else kv_cache.default_swap_batch_pages())
            if self.swap_batch_pages < 1:
                raise ValueError(
                    f"swap_batch_pages must be >= 1, got "
                    f"{self.swap_batch_pages}")
        else:
            if host_tier_bytes:
                raise ValueError(
                    "host_tier_bytes is the paged-mode host page tier; "
                    "this engine runs the dense slot cache")
            self.page_size = self.num_pages = None
            self.max_pages_per_slot = None
            self.host_tier_bytes = 0
            self.swap_batch_pages = None
        # tensor-parallel serving width (ISSUE 17): explicit kwarg wins,
        # else APEX_TPU_SERVE_TP, else single chip
        self.tp = int(tp) if tp is not None else serve_tp()
        if self.tp < 1:
            raise ValueError(f"tp must be >= 1, got {self.tp}")
        if self.tp > 1:
            if kind == "bert":
                raise ValueError(
                    "tensor-parallel serving is a generative-path "
                    "feature; BERT is the encode-only path")
            if not self.paged:
                raise ValueError(
                    "tensor-parallel serving shards the PAGED kv pool "
                    "over kv heads — pass page_size=/num_pages= (the "
                    "dense slot cache does not shard)")
        # what is not built for the kind is refused here, with the
        # record's reason — never run wrong
        fusion_on = (decode_fusion is not None
                     and resolve_fusion_mode(decode_fusion) == "1")
        asked = {"dense": not self.paged, "tp": self.tp > 1,
                 "verify": bool(spec_k),
                 "host_tier": bool(self.host_tier_bytes),
                 "fused": fusion_on}
        for feature, why in self._refuses.items():
            if asked.get(feature):
                raise ValueError(why)
        if dtype is not None:
            from apex_tpu.optimizers.functional import _cast_floating
            params = _cast_floating(params, dtype)
        self.params = params
        self._key = jax.random.PRNGKey(seed)
        self._step = 0
        # (executable, shape) keys whose op -> scope table is kept
        self._captured = set()
        # dispatch counters are GLOBAL-registry families (engine-level,
        # process-wide — per-wave serving metrics live in the
        # scheduler's ServeTelemetry registry); cached so declared()'s
        # lock + schema lookup is not per-token work, re-resolved on
        # registry identity so reset_global_registry() can't orphan them
        self._tel_registry = None
        self._refresh_dispatch_counters()
        if kind == "bert":
            # resolve the spelling so every fusion-off value ("0",
            # "off", "false", and "auto" — which can only resolve
            # unfused on a cache-less engine) passes; only an explicit
            # fusion-ON request is a configuration error here
            if spec_k or fusion_on:
                raise ValueError("speculative decoding / fused-block "
                                 "decode are generative-path features; "
                                 "BERT is the encode-only path")
            self.spec_k = 0
            self.decode_fused = False
            self._encode = jax.jit(self._make_bert_encode())
        else:
            self.dims = models.model_dims(kind, cfg)
            # tensor-parallel serving (ISSUE 17): validate the geometry
            # up front (tp | heads; tp | kvh or kvh | tp), build the
            # private one-axis serving mesh, and expand GQA/MQA kv
            # heads below tp in the SERVED mirrors so the plain column
            # shard hands every rank the kv head its query group reads
            self.tp_dims = models.tp_dims(kind, cfg, self.tp)
            self._param_specs = self._fused_specs = None
            self._cache_specs = None
            if self.tp > 1:
                self.mesh = serving_mesh(self.tp)
                self.params = models.expand_kv_for_tp(
                    kind, cfg, self.params, self.tp)
            else:
                self.mesh = None
            # fused-block decode (ISSUE 15): resolved STATICALLY here —
            # the knob selects which of two lowerings the ONE decode
            # executable compiles, never a per-step branch.  The fused
            # layout is a one-time device-side re-copy of the layer
            # weights (prefill keeps the original tree) — HBM for
            # decode latency, documented beside the knob.
            self.decode_fused = rec.fused is not None \
                and resolve_decode_fusion(
                decode_fusion, paged=self.paged,
                max_pages=self.max_pages_per_slot,
                min_pages=fusion_min_pages,
                dims=self._fused_block_dims() if self.paged else None)
            self._fused_layers = (
                models.fused_layer_params(kind, cfg, self.params)
                if self.decode_fused else None)
            if self.tp > 1:
                self._place_tp_mirrors()
            P, cs, ps = PartitionSpec, self._cache_specs, self._param_specs
            # the _raw fns are the exact (shard_map-wrapped at tp > 1)
            # step bodies the jits below compile — the SPMD audits
            # trace THESE, so the audited program is the served one
            self._prefill_raw = self._tp_wrap(
                make_prefill_fn(kind, cfg, sampling, paged=self.paged,
                                tp=self.tp),
                in_specs=(cs, ps) + (P(),) * (7 if self.paged else 5),
                out_specs=(cs, P(), P()))
            self._prefill = jax.jit(self._prefill_raw,
                                    donate_argnums=(0,))
            dps = ((ps, self._fused_specs) if self.decode_fused else ps)
            self._decode_raw = self._tp_wrap(
                make_decode_fn(kind, cfg, sampling,
                               fused=self.decode_fused, tp=self.tp),
                in_specs=(cs, dps, P(), P(), P(), P()),
                out_specs=(cs, P(), P(), P()))
            self._decode = jax.jit(self._decode_raw, donate_argnums=(0,))
            # speculative decoding (ISSUE 15): ONE verify executable
            # per (k, engine) — the slab width is static
            self.spec_k = int(spec_k if spec_k is not None
                              else 0 if "verify" in self._refuses
                              else default_spec_k())
            if self.spec_k:
                self._verify_raw = self._tp_wrap(
                    make_verify_fn(kind, cfg, sampling, self.spec_k,
                                   tp=self.tp),
                    in_specs=(cs, ps, P(), P(), P(), P()),
                    out_specs=(cs, P(), P(), P()))
                self._verify = jax.jit(self._verify_raw,
                                       donate_argnums=(0,))
            else:
                self._verify_raw = self._verify = None
            # retirement's device half (ISSUE 35): one donated metadata
            # update, the slot a traced int32 — ONE executable for every
            # slot of either layout; the pool's leaves pass through
            # aliased to themselves, the compiled program holds no copy
            self._evict = jax.jit(
                self._tp_wrap(kv_cache.evict, in_specs=(cs, P()),
                              out_specs=cs), donate_argnums=(0,))
            if self.paged:
                # the COW write barrier (ISSUE 12): one donated page
                # copy, compiled once, dispatched only when a slot must
                # privatize a page it still shares
                self._cow_raw = self._tp_wrap(
                    kv_cache.cow_page, in_specs=(cs, P(), P()),
                    out_specs=cs)
                self._cow = jax.jit(self._cow_raw, donate_argnums=(0,))
                # the host-tier swap copy programs (ISSUE 18): one
                # gather out, one scatter in, each compiled ONCE at the
                # static swap batch width (page-ID vectors pad to it).
                # The slab spec mirrors the k/v pool spec — under tp
                # each rank moves its own kv-head shard; device_get of
                # the sharded slab assembles the global page host-side.
                sb = cs.k if self.tp > 1 else None
                self._swap_out_raw = self._tp_wrap(
                    kv_cache.extract_pages, in_specs=(cs, P()),
                    out_specs=(sb, sb, None))
                # NOT donated: extract is a pure read — the pool stays
                # live (eviction is host-side bookkeeping)
                self._swap_out = jax.jit(self._swap_out_raw)
                self._swap_in_raw = self._tp_wrap(
                    kv_cache.restore_pages,
                    in_specs=(cs, P(), sb, sb), out_specs=cs)
                self._swap_in = jax.jit(self._swap_in_raw,
                                        donate_argnums=(0,))

    def _capture(self, key, jitted, *args) -> None:
        """At the first dispatch of each compiled shape, keep the op ->
        scope table of the executable ``jitted`` runs at ``args``
        (``xla_stats.capture``: it shares the call's one compile; ISSUE
        38).  Every later dispatch pays one membership test."""
        if key not in self._captured:
            self._captured.add(key)
            obs.xla_stats.capture(jitted, *args)

    def _fused_block_dims(self) -> dict:
        """The per-rank layer geometry the fused block kernel would run
        at — what :func:`resolve_decode_fusion` prices against VMEM."""
        td = self.tp_dims
        leaves = [x for x in jax.tree_util.tree_leaves(self.params)
                  if jnp.issubdtype(x.dtype, jnp.floating)]
        return dict(
            kind=self.kind, hidden=self.cfg.hidden_size,
            ffn=self.cfg.ffn // self.tp, heads=td["heads_local"],
            kv_heads=td["kv_heads_local"], head_dim=td["head_dim"],
            page_size=self.page_size,
            itemsize=max(x.dtype.itemsize for x in leaves),
            cache_itemsize=jnp.dtype(self.cache_dtype).itemsize,
            # under tp the MLP runs outside the kernel, past the psum
            fuse_mlp=self.tp == 1, partial_out=self.tp > 1)

    def _refresh_dispatch_counters(self) -> None:
        reg = obs.global_registry()
        if reg is not self._tel_registry:
            self._tel_registry = reg
            self._prefill_dispatches = reg.declared(
                "infer_prefill_dispatch_total")
            self._prefill_aligned = reg.declared(
                "serve_prefill_aligned_total")
            self._decode_dispatches = reg.declared(
                "infer_decode_dispatch_total")
            self._cow_dispatches = reg.declared(
                "infer_cow_dispatch_total")
            self._evict_dispatches = reg.declared(
                "infer_evict_dispatch_total")
            self._fused_decode_dispatches = reg.declared(
                "infer_decode_fused_dispatch_total")
            self._verify_dispatches = reg.declared(
                "infer_verify_dispatch_total")
            self._swap_out_dispatches = reg.declared(
                "infer_swap_out_dispatch_total")
            self._swap_in_dispatches = reg.declared(
                "infer_swap_in_dispatch_total")

    # -- tensor-parallel serving (ISSUE 17) ----------------------------------
    def _tp_wrap(self, fn, *, in_specs, out_specs):
        """Per-rank step body -> mesh program: ``shard_map`` over the
        serving mesh's tensor axis.  tp=1 returns ``fn`` untouched, so
        the single-chip lowering stays bitwise the pre-TP engine.  The
        unjitted wrap is what the ``_*_raw`` attributes hold — the SPMD
        audits trace those, auditing the exact program served."""
        if self.tp == 1:
            return fn
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def _place_tp_mirrors(self) -> None:
        """Column/row-partition the served mirrors onto the mesh: spec
        trees from :func:`models.param_partition_specs` /
        :func:`models.fused_partition_specs`, every leaf ``device_put``
        with its ``NamedSharding`` at construction so dispatch never
        reshards (the jitted steps see already-placed operands)."""
        mesh = self.mesh

        def put(tree, specs):
            return jax.tree_util.tree_map(
                lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
                tree, specs)

        self._param_specs = models.param_partition_specs(
            self.kind, self.cfg, self.params, self.tp)
        self.params = put(self.params, self._param_specs)
        if self._fused_layers is not None:
            self._fused_specs = models.fused_partition_specs(
                self._fused_layers, self.tp)
            self._fused_layers = put(self._fused_layers,
                                     self._fused_specs)
        # page table / lengths / capacity replicated, k/v pool sharded
        # over the kv-head dim — the host-side allocator, prefix cache,
        # COW, and eviction logic never see the shard boundary
        self._cache_specs = kv_cache.paged_cache_partition_specs()
        self._key = jax.device_put(
            self._key, NamedSharding(mesh, PartitionSpec()))

    # -- cache ---------------------------------------------------------------
    def init_cache(self):
        if self.kind == "bert":
            raise ValueError("BERT is the encode-only path (no KV "
                             "cache); use encode()")
        d = self.dims
        if self.paged:
            # under tp the GLOBAL pool carries kv_heads_pool heads
            # (kvh * rep — GQA/MQA replicate below tp); the k/v leaves
            # then shard over the kv-head dim, handing each rank
            # kv_heads_pool / tp heads of every page
            def build():
                return kv_cache.init_paged_cache(
                    self.num_pages, d["pool_layers"],
                    self.tp_dims["kv_heads_pool"],
                    self.page_size, d["head_dim"], slots=self.slots,
                    max_pages_per_slot=self.max_pages_per_slot,
                    dtype=self.cache_dtype,
                    window_layers=d["window_layers"], window=d["window"],
                    latent=d["latent"], index=d["index"],
                    index_layers=d.get("index_layers"),
                    window_latent=d.get("window_latent", 0))

            if self.tp == 1:
                return build()
            # built ON the mesh: every rank allocates only its own
            # kv-head shard, so a pool sized for tp chips never has to
            # fit on one (building it whole and THEN resharding did)
            shardings = jax.tree_util.tree_map(
                lambda s: NamedSharding(self.mesh, s), self._cache_specs,
                is_leaf=lambda s: isinstance(s, PartitionSpec))
            return jax.jit(build, out_shardings=shardings)()
        return kv_cache.init_cache(
            self.slots, d["layers"], d["kv_heads"], self.max_seq,
            d["head_dim"], dtype=self.cache_dtype)

    def new_allocator(self) -> kv_cache.PageAllocator:
        """Fresh host-side page allocator matching the engine's pool
        geometry (paged mode only) — one per cache lifetime; the
        scheduler owns it alongside its slot bookkeeping."""
        if not self.paged:
            raise ValueError("new_allocator() is the paged-mode page "
                             "bookkeeping; this engine runs the dense "
                             "slot cache")
        return kv_cache.PageAllocator(self.num_pages, self.page_size,
                                      self.max_pages_per_slot)

    def cache_hbm_bytes(self) -> int:
        """Bytes the KV cache pins in HBM: pool pages (paged, incl. the
        trash page) or slots x max_seq (dense).  Under tensor-parallel
        serving this is PER-RANK bytes — the pool shards over kv heads,
        so each chip pins ``kv_heads_pool / tp`` heads (= 1/tp of the
        tp-divisible pool; an MQA pool replicated below tp pins its one
        kv head per rank)."""
        d = self.dims
        itemsize = jnp.dtype(self.cache_dtype).itemsize
        kvh = self.tp_dims["kv_heads_pool"] // self.tp   # per-rank heads
        # what a position holds a layer is the record's: a key and a
        # value per KV head, or one latent row (and an index key)
        per_layer_tok = models.cache_row_values(d, kvh) * itemsize
        if self.paged:
            # the pool's layers (with the index keys of those the index
            # pool keeps), + the window layers' rings: fixed rows a slot
            # whatever the context (none without such layers)
            ring = kv_cache.ring_rows(d["window"], self.page_size)
            return ((self.num_pages + 1) * self.page_size
                    * models.cache_position_values(d, kvh) * itemsize
                    + self.slots * ring * d["window_layers"]
                    * models.ring_row_values(d, kvh) * itemsize)
        return self.slots * self.max_seq * d["layers"] * per_layer_tok

    # -- generative path -----------------------------------------------------
    def _next_step(self):
        # numpy scalar, not jnp: an eager jnp.asarray of a python int
        # compiles a throwaway convert program per call — a numpy
        # operand binds into the jitted step with no extra executable
        s = self._step
        self._step += 1
        return np.int32(s)

    def bucket_for(self, n: int) -> int:
        """The prefill bucket an ``n``-token prompt pads up to — the
        one place the bucket policy lives (prefill pads with it; the
        scheduler's padding-badput accounting reads it)."""
        min_bucket = max(64, self.page_size) if self.paged else 64
        return prefill_bucket(n, self.max_seq, min_bucket=min_bucket)

    def prefill(self, cache, tokens, slot, pages=None, prefill_from=0):
        """Admit one prompt into ``slot``: returns ``(cache, next_token,
        last_logits)``.  ``tokens`` is the UNPADDED prompt (list/array of
        ints); padding to the executable bucket happens here.

        Paged mode additionally takes ``pages`` — the FULL ordered
        page-ID list backing the prompt + decode headroom (shared
        prefix pages first on a prefix-cache hit, then the privately
        acquired suffix pages) — and ``prefill_from`` (ISSUE 12): how
        many leading prompt tokens are already cached in those pages.
        Only ``tokens[prefill_from:]`` runs the forward (padded to ITS
        bucket, so a short uncached tail rides a small executable),
        attending to the cached prefix through the page window; the
        bucket rounds up freely, positions beyond the reservation spill
        into the pool's trash page by construction.  ``prefill_from``
        is a traced operand — a hit admits with zero new compiles once
        the tail's bucket is warm."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        n = tokens.shape[0]
        start = int(prefill_from)
        if start < 0 or start >= n:
            raise ValueError(
                f"prefill_from ({start}) must be in [0, prompt length "
                f"{n}) — at least the last prompt token is always "
                f"prefilled (its logits seed the first sampled token)")
        if start and not self.paged:
            raise ValueError(
                "prefill_from needs the paged cache (prefix sharing is "
                "a page-table edit); this engine runs the dense slot "
                "cache")
        if start and not self.supports_prefix_sharing:
            raise ValueError(f"prefill_from={start}: "
                             f"{self._refuses['prefix_sharing']}")
        suffix = tokens[start:]
        bucket = self.bucket_for(suffix.shape[0])
        padded = np.zeros((bucket,), np.int32)
        padded[:suffix.shape[0]] = suffix
        if self.paged:
            if pages is None:
                raise ValueError(
                    "paged prefill needs the slot's reserved page IDs "
                    "(engine.new_allocator().acquire(...)); the "
                    "scheduler threads them automatically")
            if len(pages) * self.page_size < n:
                raise ValueError(
                    f"reservation of {len(pages)} page(s) x "
                    f"{self.page_size} covers {len(pages) * self.page_size}"
                    f" tokens < the {n}-token prompt — the prompt tail "
                    f"would silently land in the trash page; reserve "
                    f"ceil((prompt + max_new_tokens) / page_size) pages")
            row = kv_cache.page_row(pages, self.max_pages_per_slot,
                                    self.num_pages)
            args = (cache, self.params, padded, np.int32(slot),
                    np.int32(n), row, np.int32(start))
        else:
            args = (cache, self.params, padded, np.int32(slot),
                    np.int32(n))
        # counted AFTER validation: a rejected reservation raised above
        # and dispatched nothing.  The annotation metadata (slot, the
        # chunk origin) lets an xprof capture line up each dispatch
        # with the request tracer's prefill_chunk spans (ISSUE 13).
        self._refresh_dispatch_counters()
        self._prefill_dispatches.inc()
        if self.paged and start % self.page_size == 0:
            self._prefill_aligned.inc()
        args += (self._key, self._next_step())
        self._capture(("prefill", bucket), self._prefill, *args)
        with obs.trace_annotation("apex_tpu.inference.prefill",
                                  slot=int(slot), prefill_from=start):
            return self._prefill(*args)

    def cow_page(self, cache, src, dst):
        """Copy-on-write page duplication (paged mode): copy physical
        page ``src`` into ``dst`` and return the cache.  The write
        barrier of the sharing contract — the scheduler calls this
        before a slot writes into a page it still shares (the partial
        boundary page of an unaligned prefix-cache hit), pointing the
        slot's row at ``dst`` in the prefill that follows.  ``src`` and
        ``dst`` are traced int32, so every COW rides ONE compiled copy
        program for the engine's lifetime."""
        if not self.paged:
            raise ValueError("cow_page is the paged-mode write barrier; "
                             "this engine runs the dense slot cache")
        self._refresh_dispatch_counters()
        self._cow_dispatches.inc()
        args = (cache, np.int32(src), np.int32(dst))
        self._capture("cow", self._cow, *args)
        with obs.trace_annotation("apex_tpu.inference.cow_page",
                                  src=int(src), dst=int(dst)):
            return self._cow(*args)

    def evict_slot(self, cache, slot: int):
        """Device-side metadata evict of one slot (paged or dense):
        zero its length and re-park its page-table row on the trash
        page so the idle slot's masked decode appends can never land
        in another request's pages.  ONE launch of ONE compiled, donated
        program (``kv_cache.evict`` jitted in the constructor beside the
        COW copy; ``slot`` is a traced int32, so every retirement of
        the engine's lifetime rides it and nothing is applied eagerly
        between two passes — ISSUE 35).  The retire half of the engine's
        device surface — the scheduler releases the slot's page
        REFERENCES host-side only after this returns (the launch is
        queued ahead of any later prefill or decode that could reuse
        the pages), so a stub engine (protocol audit) can mirror the
        whole lifecycle without a device."""
        self._refresh_dispatch_counters()
        self._evict_dispatches.inc()
        self._capture("evict", self._evict, cache, np.int32(slot))
        with obs.trace_annotation("apex_tpu.inference.evict_slot",
                                  slot=int(slot)):
            return self._evict(cache, np.int32(slot))

    def page_host_bytes(self) -> int:
        """Host-DRAM bytes ONE page's k+v slabs occupy in the host
        tier.  GLOBAL geometry even under tensor parallelism: swap-out
        ``device_get``\\ s the sharded slab into the full kv-head dim,
        so the host books (like the page table) are rank-invariant."""
        if not self.paged:
            raise ValueError("page_host_bytes is the paged-mode host "
                             "tier ledger; this engine runs the dense "
                             "slot cache")
        d = self.dims
        itemsize = jnp.dtype(self.cache_dtype).itemsize
        return (self.page_size * itemsize * models.cache_position_values(
            d, self.tp_dims["kv_heads_pool"]))

    def swap_out_pages(self, cache, page_ids, defer: bool = False):
        """Copy physical pages ``page_ids`` device→host (ISSUE 18
        eviction offload): returns ``(k, v)`` numpy slabs
        ``[n, layers, kv_heads, page_size, head_dim]``.  Pure read —
        the cache operand stays valid (the HBM pages return to the
        free list host-side).  Batches of ``swap_batch_pages`` are
        dispatched back-to-back (short batches pad with the trash
        page) and fetched only after the LAST dispatch, so the
        device-side gathers pipeline ahead of the host copies; every
        batch rides the ONE compiled extract program.

        ``defer=True`` (ISSUE 19) skips the fetch entirely and returns
        a :class:`PendingSwapOut` instead: the gathers are dispatched
        NOW (into fresh output buffers, so later cache donations
        cannot disturb them) but the blocking ``device_get``\\ s run
        only at ``resolve()`` — the scheduler drains them at the next
        wave boundary instead of stalling the eviction path."""
        if not self.paged:
            raise ValueError("swap_out_pages is the paged-mode host "
                             "tier; this engine runs the dense slot "
                             "cache")
        ids = np.asarray(page_ids, np.int32).reshape(-1)
        n, B = ids.shape[0], self.swap_batch_pages
        if n == 0:
            raise ValueError("swap_out_pages needs at least one page")
        self._refresh_dispatch_counters()
        pending = []
        with obs.trace_annotation("apex_tpu.inference.swap_out",
                                  pages=int(n)):
            for i in range(0, n, B):
                chunk = ids[i:i + B]
                padded = np.full((B,), self.num_pages, np.int32)
                padded[:chunk.shape[0]] = chunk
                self._swap_out_dispatches.inc()
                # the host tier is refused for a kind with index keys
                k_s, v_s, _ = self._swap_out(cache, padded)
                pending.append((k_s, v_s, chunk.shape[0]))
            if defer:
                return PendingSwapOut(pending)
        return PendingSwapOut(pending).resolve()

    def swap_in_pages(self, cache, page_ids, k_slabs, v_slabs):
        """Upload host-tier page slabs back into freshly acquired
        physical pages ``page_ids`` (ISSUE 18 hit-after-eviction):
        returns the cache.  The inverse of :meth:`swap_out_pages` —
        batches pad short with an OUT-OF-BOUNDS page index (dropped by
        the scatter) and zero slabs, so every batch rides the ONE
        compiled restore program; the cache is donated through each
        dispatch like every other mutation.  The scheduler calls this
        BEFORE the uncached tail's first prefill chunk, so uploads
        overlap the tail's compute in the dispatch queue."""
        if not self.paged:
            raise ValueError("swap_in_pages is the paged-mode host "
                             "tier; this engine runs the dense slot "
                             "cache")
        ids = np.asarray(page_ids, np.int32).reshape(-1)
        n, B = ids.shape[0], self.swap_batch_pages
        k_slabs = np.asarray(k_slabs)
        v_slabs = np.asarray(v_slabs)
        if n == 0:
            raise ValueError("swap_in_pages needs at least one page")
        if k_slabs.shape[0] != n or v_slabs.shape[0] != n:
            raise ValueError(
                f"swap-in slabs must carry one entry per page id "
                f"({n}), got k {k_slabs.shape[0]} v {v_slabs.shape[0]}")
        self._refresh_dispatch_counters()
        oob = np.int32(self.num_pages + 1)   # >= cache.pages -> dropped
        with obs.trace_annotation("apex_tpu.inference.swap_in",
                                  pages=int(n)):
            for i in range(0, n, B):
                chunk = ids[i:i + B]
                m = chunk.shape[0]
                padded = np.full((B,), oob, np.int32)
                padded[:m] = chunk
                pk = np.zeros((B,) + k_slabs.shape[1:], k_slabs.dtype)
                pv = np.zeros((B,) + v_slabs.shape[1:], v_slabs.dtype)
                pk[:m] = k_slabs[i:i + B]
                pv[:m] = v_slabs[i:i + B]
                self._swap_in_dispatches.inc()
                cache = self._swap_in(cache, padded, pk, pv)
        return cache

    def decode(self, cache, last_tokens=None, active=None):
        """One token for every slot: returns ``(cache, host, logits,
        truncated)`` — ``host`` is the step's ONE array for the host
        (:func:`host_vector`: ``[next_tokens [slots] | truncated
        [slots] | stats tail]``); only ``active`` slots advance their
        cache length.

        The step's input tokens are ``cache.last_tokens`` (ISSUE 37):
        each slot's last sampled token, left on the device by the
        prefill or decode step that sampled it.  The serving loop passes
        none, so a launch uploads ``active`` and nothing else of a
        token's size, and needs no earlier step's vector on the host.
        ``last_tokens`` given (a drafter feeding confirmed tokens, a
        test) is uploaded and used in their place.

        Capacity contract: a slot whose length has reached its capacity
        (``max_seq`` dense; its page reservation paged) must be retired
        (deactivated) by the caller before further steps — the
        scheduler tracks this host-side from prompt/output lengths.
        Past capacity the cache clamps (see :func:`kv_cache.advance`)
        rather than corrupting earlier rows, and the returned
        ``truncated`` vector flags every active slot whose token was
        dropped by that clamp so no caller can miss it.
        """
        if active is None:
            active = np.ones((self.slots,), bool)
        self._refresh_dispatch_counters()
        self._decode_dispatches.inc()
        if self.decode_fused:
            self._fused_decode_dispatches.inc()
        params = ((self.params, self._fused_layers) if self.decode_fused
                  else self.params)
        tokens = (None if last_tokens is None
                  else np.asarray(last_tokens, np.int32))
        args = (cache, params, tokens, np.asarray(active, bool),
                self._key, self._next_step())
        self._capture(("decode", tokens is None), self._decode, *args)
        with obs.trace_annotation("apex_tpu.inference.decode"):
            return self._decode(*args)

    def verify(self, cache, slab, active=None):
        """One speculative-verify step (ISSUE 15): ``slab [slots,
        spec_k + 1]`` (column 0 = each slot's last confirmed token,
        the rest drafts) -> ``(cache, host, n_emit [slots],
        truncated)``, ``host`` the step's ONE array for the host
        (:func:`host_vector`: ``[tokens [slots * (spec_k + 1)] | n_emit
        | truncated]``).
        ``tokens[:, :n_emit]`` per slot is the emitted stream — the
        target's own greedy continuation (accepted drafts + bonus
        token); lengths advanced by
        ``n_emit`` in-program (the accept/reject rollback).  The same
        capacity contract as :meth:`decode`: the caller clamps emitted
        tokens to the slot's remaining capacity and retires truncated
        slots."""
        if not self.spec_k:
            raise ValueError(
                "speculative decoding is off for this engine; build it "
                "with spec_k > 0 (or APEX_TPU_SPEC_K)")
        slab = np.asarray(slab, np.int32)
        if slab.shape != (self.slots, self.spec_k + 1):
            raise ValueError(
                f"verify slab must be [{self.slots}, "
                f"{self.spec_k + 1}] (last token + {self.spec_k} "
                f"drafts), got {tuple(slab.shape)}")
        if active is None:
            active = np.ones((self.slots,), bool)
        self._refresh_dispatch_counters()
        self._verify_dispatches.inc()
        args = (cache, self.params, slab, np.asarray(active, bool),
                self._key, self._next_step())
        self._capture("verify", self._verify, *args)
        with obs.trace_annotation("apex_tpu.inference.verify",
                                  k=self.spec_k):
            return self._verify(*args)

    def generate(self, prompts, max_new_tokens: int = 16,
                 eos_id: Optional[int] = None):
        """Convenience wrapper over the continuous-batching scheduler:
        ``prompts`` (list of token lists) -> list of generated token
        lists, in submission order."""
        from apex_tpu.inference import scheduler
        return scheduler.generate(self, prompts,
                                  max_new_tokens=max_new_tokens,
                                  eos_id=eos_id)

    # -- encode-only path (BERT) --------------------------------------------
    def _make_bert_encode(self):
        from apex_tpu.transformer.testing import bert_model_provider
        model = bert_model_provider(self.cfg, add_binary_head=False)

        def encode(params, tokens, token_types):
            return model.apply(params, tokens, token_types)

        return encode

    def encode(self, tokens, token_types=None):
        """BERT path: one bidirectional forward, logits out."""
        if self.kind != "bert":
            raise ValueError("encode() is the BERT path; use "
                             "prefill()/decode() for generative models")
        tokens = jnp.asarray(tokens, jnp.int32)
        if token_types is None:
            token_types = jnp.zeros(tokens.shape, jnp.int32)
        return self._encode(self.params, tokens, token_types)

    # -- checkpoint boundaries ----------------------------------------------
    @classmethod
    def from_train_state(cls, kind: str, cfg, state, *,
                         dtype=jnp.bfloat16, **kwargs):
        """Build from a :class:`~apex_tpu.train_step.TrainState` (or bare
        ``FlatState``): weights export in ``dtype`` (bf16 serving
        default) via ``FlatState.params(dtype=...)`` — a ZeRO-sharded
        state all-gathers its master, so a checkpoint written at any dp
        restores straight into the engine."""
        opt = getattr(state, "opt", state)
        return cls(kind, cfg, opt.params(dtype=dtype), **kwargs)

    @classmethod
    def from_state_dict(cls, kind: str, cfg, sd, params_template, *,
                        dtype=jnp.bfloat16, **kwargs):
        """Build from a contrib ``DistributedFused*`` shard-aware
        ``state_dict`` (the reassembled full flat master) plus the model
        param template that defines the leaf layout."""
        from apex_tpu.optimizers.functional import export_params
        params = export_params(sd["master"], params_template, dtype=dtype)
        return cls(kind, cfg, params, **kwargs)
