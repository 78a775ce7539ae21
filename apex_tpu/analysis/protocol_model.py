"""Action/state harness for the serving control-plane protocol
auditor (ISSUE 20): the REAL host-side components — ``PageAllocator``,
``PrefixCache`` (radix + host-edge states), ``HostPageStore`` (eager
and deferred slabs), ``SlotScheduler``, ``FleetRouter`` — driven under
a DEVICE-FREE stub engine as an explicit transition system, so the
fifth analysis engine (:mod:`~apex_tpu.analysis.protocol_audit`) can
exhaustively explore small scopes of the serving protocol and assert
its conservation laws at every reachable state.

Three layers:

* :class:`StubEngine` / :class:`StubKVCache` — the whole device
  surface the scheduler touches (prefill / decode / cow_page /
  swap_out_pages / swap_in_pages / evict_slot + the geometry attrs),
  in pure numpy on the host.  Pages carry CONTENT TAGS (a stable
  polynomial hash of the tokens they hold) instead of k/v tensors, so
  invariants can detect a clobbered shared page or a corrupted swap
  slab, not just broken books.  Token emission is a pure function of
  (prompt, position): no RNG, no wall clock — the whole model is
  deterministic.
* :class:`ProtocolHarness` — one small-scope serving system (1..N
  replicas, optionally fronted by the real :class:`FleetRouter`) plus
  the ACTION ALPHABET: submit / scheduler pass (admission + chunked
  prefill + decode + retire, the host's atomic execution unit) / wave
  boundary / evict-to-host / drain_pending_swaps / shed / route
  (fleet submits go through the router) / the abstract disaggregation
  handoff pair (``handoff_extract`` on A → ``handoff_restore`` on B,
  modeled on the ISSUE 18 copy programs — model-checked BEFORE the
  real cross-replica handoff is implemented).  ``canonical()``
  projects the state onto its protocol-relevant core (books, tree
  shape with LRU ranks, queue/slot contents, page contents) and away
  from monotonic counters (uids, clocks, telemetry totals, SLO
  histograms) that never influence a decision at the explored scopes.
* :func:`explore` / :func:`replay` / :func:`shrink` — deterministic
  bounded-exhaustive breadth-first exploration with canonical-state
  dedup (breadth-first so a state is always reached by a SHORTEST
  trace — a depth-bounded DFS could dedup a state at depth d and miss
  its shallower continuations), trace replay (branching re-executes
  the action prefix from the initial state: the components hold locks
  and device-shaped buffers, so replay IS the snapshot mechanism and
  doubles as the counterexample repro path), and action-deletion
  counterexample minimization.

Soundness notes for the canonical projection (why deduping on it
cannot hide a violation): telemetry counters and SLO state feed no
control decision here — the explored scopes keep every queue shorter
than the overload detector's trip threshold (asserted at harness
build), and ``shed_on_overload`` stays False (shedding is an explicit
action through the same code path).  Uid VALUES key dicts but order
no decision; template identity, which determines all future behavior,
is in the projection.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from apex_tpu.inference.kv_cache import PageAllocator
from apex_tpu.inference.step_vector import host_vector

__all__ = [
    "StubEngine", "StubKVCache", "StubPendingSwapOut", "Template",
    "Scope", "ProtocolHarness", "Action", "explore", "replay",
    "shrink", "random_walk", "ExploreResult", "Violation",
    "write_repro", "load_repro",
]

#: Queue depth at/above which the overload detector MAY start seeing
#: sustained pressure (its default ``queue_high``).  Exhaustive scopes
#: must stay strictly below it so SLO state never influences routing —
#: that is what licenses projecting SLO state out of ``canonical()``.
_DETECTOR_QUEUE_HIGH = 4

_MASK = (1 << 63) - 1


_TAG_SEED = 0x9E3779B97F4A7C15 & _MASK


def _mix(tag: int, token: int) -> int:
    """Fold one appended token into a page's content tag."""
    return (int(tag) * 1000003 + int(token) * 31 + 7) & _MASK


def _tag(tokens: Sequence[int]) -> int:
    """Stable polynomial hash of a token slice — page content tags.
    Defined as the left fold of :func:`_mix` so a page filled
    token-by-token by decode carries EXACTLY the tag prefill writes
    for the same slice (that identity is what the content-integrity
    invariants check).  Explicit arithmetic (not ``hash()``) so tags
    are identical across processes regardless of
    ``PYTHONHASHSEED``."""
    h = _TAG_SEED
    for t in tokens:
        h = _mix(h, t)
    return h


class StubKVCache:
    """Host-side stand-in for the paged device cache: the page table
    and lengths the metadata ops maintain, plus one content TAG per
    page in place of the k/v slabs.  ``-1`` table entries are the
    trash page."""

    def __init__(self, slots: int, num_pages: int, page_size: int,
                 max_pages_per_slot: int):
        self.page_table = np.full((slots, max_pages_per_slot), -1,
                                  np.int32)
        self.lengths = np.zeros((slots,), np.int32)
        # each slot's next decode input, kept where the steps run
        # (ISSUE 37): the scheduler launches a step before it has read
        # the one before, so it hands the engine no token
        self.last_tokens = np.zeros((slots,), np.int32)
        self.content = np.zeros((num_pages,), np.int64)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)


class StubPendingSwapOut:
    """Deferred device→host drain, stub-side: the content SNAPSHOT is
    taken at dispatch time (exactly like the real batched gather into
    fresh output buffers), so a page reused and overwritten between
    dispatch and resolve cannot corrupt the slab.  A broken twin that
    snapshots lazily (reads the cache at resolve time) reproduces the
    release-before-extract ordering bug the protocol audit exists to
    catch."""

    def __init__(self, k: np.ndarray, v: np.ndarray):
        self._k, self._v = k, v
        self._done = False

    @property
    def done(self) -> bool:
        return self._done

    def resolve(self):
        self._done = True
        return self._k, self._v


class StubEngine:
    """The full device surface :class:`SlotScheduler` touches, in pure
    host numpy — every page-table edit, content write, COW copy and
    swap mirrors the real engine's semantics at tag granularity.
    Token emission is deterministic: the prefill-sampled first token
    and each decode token are pure functions of the visible ints."""

    paged = True
    spec_k = 0
    kind = "stub"

    def __init__(self, *, slots: int, num_pages: int, page_size: int,
                 max_pages_per_slot: int, host_tier_pages: int = 0):
        self.slots = int(slots)
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.max_pages_per_slot = int(max_pages_per_slot)
        self.max_seq = self.max_pages_per_slot * self.page_size
        self.host_tier_bytes = (int(host_tier_pages)
                                * self.page_host_bytes())
        #: every PendingSwapOut this engine ever issued — the APX407
        #: wave-boundary law walks it (a real engine would not need
        #: the log; the model checker does)
        self.pending_log: List[StubPendingSwapOut] = []

    # -- geometry -------------------------------------------------------------
    def page_host_bytes(self) -> int:
        return self.page_size * 16

    def bucket_for(self, n: int) -> int:
        b = max(1, self.page_size)
        while b < int(n):
            b *= 2
        return b

    def new_allocator(self) -> PageAllocator:
        return PageAllocator(self.num_pages, self.page_size,
                             self.max_pages_per_slot)

    def init_cache(self) -> StubKVCache:
        return StubKVCache(self.slots, self.num_pages, self.page_size,
                           self.max_pages_per_slot)

    # -- token emission (pure) ------------------------------------------------
    @staticmethod
    def _first_token(tokens: Sequence[int]) -> int:
        return (sum(int(t) for t in tokens) + len(tokens)) % 7 + 1

    @staticmethod
    def _next_token(last: int, length: int) -> int:
        return (int(last) * 3 + int(length)) % 7 + 1

    # -- device programs ------------------------------------------------------
    def prefill(self, cache: StubKVCache, tokens, slot: int, *,
                pages: Optional[Sequence[int]] = None,
                prefill_from: int = 0):
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        n = len(toks)
        if pages is None:
            raise ValueError("stub engine is paged: pages required")
        ps = self.page_size
        if len(pages) * ps < n:
            raise ValueError(
                f"reservation of {len(pages)} pages cannot cover "
                f"{n} tokens at page size {ps}")
        row = np.full((self.max_pages_per_slot,), -1, np.int32)
        row[:len(pages)] = np.asarray(pages, np.int32)
        cache.page_table[slot] = row
        # rewrite the content tags of every page the [prefill_from, n)
        # suffix touches: a page's tag is the stable hash of the token
        # slice it holds, so identical prefixes produce identical tags
        start = int(prefill_from)
        for j in range(start // ps, -(-n // ps)):
            cache.content[int(pages[j])] = np.int64(
                _tag(toks[j * ps:min(n, (j + 1) * ps)]))
        cache.lengths[slot] = n
        first = np.int32(self._first_token(toks))
        cache.last_tokens[slot] = first
        return cache, first, None

    def decode(self, cache: StubKVCache, last=None, active=None):
        # the step's input tokens are the cache's own, as the compiled
        # step's are; every call runs at once, so a step "launched
        # ahead" has simply run by the time its vector is read
        last = np.asarray(cache.last_tokens if last is None else last)
        active = np.asarray(active, bool)
        toks = np.zeros((self.slots,), np.int32)
        truncated = np.zeros((self.slots,), bool)
        ps = self.page_size
        for s in range(self.slots):
            if not active[s]:
                continue
            length = int(cache.lengths[s])
            row = cache.page_table[s]
            capacity = int((row >= 0).sum()) * ps
            if length >= capacity or length >= self.max_seq:
                truncated[s] = True
                continue
            tok = self._next_token(int(last[s]), length)
            # the INPUT token's k/v lands at position ``length`` (the
            # emitted token is written by the NEXT step) — so the fold
            # extends the page with ``last``, keeping every page's tag
            # equal to _tag() of the token slice it actually holds
            page = int(row[length // ps])
            base = (_TAG_SEED if length % ps == 0
                    else int(cache.content[page]) & _MASK)
            cache.content[page] = np.int64(_mix(base, int(last[s])))
            cache.lengths[s] = length + 1
            toks[s] = tok
            cache.last_tokens[s] = tok
        # the engine's one array for the host, packed as the compiled
        # step packs it (the scheduler reads nothing else)
        return cache, host_vector(toks, truncated, xp=np), None, truncated

    def cow_page(self, cache: StubKVCache, src: int, dst: int):
        cache.content[int(dst)] = cache.content[int(src)]
        return cache

    def evict_slot(self, cache: StubKVCache, slot: int):
        cache.lengths[slot] = 0
        cache.page_table[slot] = -1
        return cache

    def swap_out_pages(self, cache: StubKVCache, page_ids,
                       defer: bool = False):
        ids = [int(p) for p in page_ids]
        k = np.array([[int(cache.content[p])] for p in ids], np.int64)
        v = k.copy()
        pending = StubPendingSwapOut(k, v)
        self.pending_log.append(pending)
        if defer:
            return pending
        return pending.resolve()

    def swap_in_pages(self, cache: StubKVCache, page_ids, k_slabs,
                      v_slabs):
        for i, p in enumerate(page_ids):
            cache.content[int(p)] = np.int64(int(
                np.asarray(k_slabs[i]).reshape(-1)[0]))
        return cache


@dataclasses.dataclass(frozen=True)
class Template:
    """One request shape the scope's submit actions can instantiate."""
    name: str
    prompt: Tuple[int, ...]
    max_new_tokens: int = 1
    tenant: str = "default"
    priority: int = 0
    eos_id: Optional[int] = None
    cap: int = 1                    # submit budget for this template


@dataclasses.dataclass(frozen=True)
class Scope:
    """One small-scope configuration of the serving control plane —
    the bounded universe an exhaustive exploration covers."""
    name: str
    replicas: int = 1
    slots: int = 2
    num_pages: int = 5
    page_size: int = 2
    max_pages_per_slot: int = 3
    host_tier_pages: int = 0
    prefill_chunk: int = 0
    max_chunks_per_pass: int = 1
    policy: str = "prefix_affinity"
    templates: Tuple[Template, ...] = ()
    evict_sizes: Tuple[int, ...] = ()   # evict-to-host action sizes
    evict_cap: int = 0                  # max evict actions per trace
    shed: bool = False                  # expose the shed action
    handoff: bool = False               # expose the handoff pair
    handoff_cap: int = 1
    max_depth: int = 10                 # exploration depth bound
    max_states: int = 50000             # safety valve (cap hit = error)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["templates"] = [dataclasses.asdict(t)
                          for t in self.templates]
        # JSON-normalized (tuples -> lists) so a fresh report compares
        # equal to the committed pin after its disk round-trip
        return json.loads(json.dumps(d))


#: An action is a plain JSON-serializable tuple: (kind, *args).
Action = Tuple


class ProtocolHarness:
    """One live small-scope serving system plus its action alphabet.

    Construction hooks (``engine_factory`` / ``scheduler_factory`` /
    ``abort_transit_on_end_wave``) exist so the seeded-violation tests
    can swap in deliberately BROKEN component twins and watch the
    invariants catch them; the defaults build the real components.
    """

    def __init__(self, scope: Scope, *,
                 engine_factory: Optional[Callable] = None,
                 scheduler_factory: Optional[Callable] = None,
                 abort_transit_on_end_wave: bool = True):
        from apex_tpu.fleet.router import FleetRouter
        from apex_tpu.inference.scheduler import SlotScheduler
        from apex_tpu.observability import (FleetTelemetry,
                                            MetricsRegistry,
                                            ServeTelemetry)
        self.scope = scope
        total_cap = sum(t.cap for t in scope.templates)
        if total_cap >= _DETECTOR_QUEUE_HIGH and scope.replicas > 1:
            raise ValueError(
                f"scope {scope.name!r}: total submit cap {total_cap} "
                f"can reach the overload detector's trip threshold "
                f"({_DETECTOR_QUEUE_HIGH}) — routing would then depend "
                f"on SLO state the canonical projection drops; shrink "
                f"the caps or extend canonical() first")
        if engine_factory is None:
            engine_factory = lambda sc: StubEngine(          # noqa: E731
                slots=sc.slots, num_pages=sc.num_pages,
                page_size=sc.page_size,
                max_pages_per_slot=sc.max_pages_per_slot,
                host_tier_pages=sc.host_tier_pages)
        if scheduler_factory is None:
            scheduler_factory = SlotScheduler
        self.engines = [engine_factory(scope)
                        for _ in range(scope.replicas)]
        self.reps = [
            scheduler_factory(
                eng, ServeTelemetry(MetricsRegistry()),
                prefix_cache=True,
                prefill_chunk=scope.prefill_chunk,
                max_chunks_per_pass=scope.max_chunks_per_pass,
                tenant_priority={}, replica_id=i)
            for i, eng in enumerate(self.engines)]
        self.router = None
        if scope.replicas > 1:
            self.router = FleetRouter(
                self.reps, policy=scope.policy,
                telemetry=FleetTelemetry(MetricsRegistry()))
        self.abort_transit_on_end_wave = bool(abort_transit_on_end_wave)
        self.submitted: Dict[int, int] = {
            i: 0 for i in range(len(scope.templates))}
        self.uid_template: Dict[Tuple[int, int], int] = {}
        self.evicts_done = 0
        self.handoffs_done = 0
        #: in-flight abstract handoffs: extract-on-A done, restore not
        self.transit: List[dict] = []
        self.trace: List[Action] = []

    # -- the action alphabet --------------------------------------------------
    def enabled_actions(self) -> List[Action]:
        """Every action legal in the current state, in a FIXED
        deterministic order (the exploration order)."""
        sc = self.scope
        acts: List[Action] = []
        for ti, t in enumerate(sc.templates):
            if self.submitted[ti] < t.cap:
                acts.append(("submit", ti))
        for r, rep in enumerate(self.reps):
            if rep.queue or (rep.wave_open and rep.run_pending()):
                acts.append(("pass", r))
            if rep.wave_open and not rep.run_pending():
                acts.append(("end_wave", r))
            if rep.pending_swaps:
                acts.append(("drain", r))
            if sc.shed and rep.queue:
                acts.append(("shed", r))
            if sc.evict_cap and self.evicts_done < sc.evict_cap \
                    and rep.wave_open and rep.prefix is not None \
                    and rep.prefix.pinned_pages > 0:
                for n in sc.evict_sizes or (1,):
                    acts.append(("evict", r, n))
            if sc.handoff and self.handoffs_done < sc.handoff_cap \
                    and rep.wave_open \
                    and self._handoff_chain(r) is not None:
                acts.append(("handoff_extract", r))
        if self.transit:
            src = self.transit[0]["src"]
            n = self.transit[0]["n"]
            for r, rep in enumerate(self.reps):
                if r != src and rep.wave_open \
                        and rep.alloc.free_pages >= n:
                    acts.append(("handoff_restore", r))
        return acts

    def apply(self, action: Action) -> None:
        """Execute one action on the live components.  Actions are the
        host's atomic execution units — nothing in the real system
        interleaves inside one (the serving loop is single-threaded
        per replica)."""
        kind = action[0]
        getattr(self, f"_act_{kind}")(*action[1:])
        self.trace.append(tuple(action))

    def _act_submit(self, ti: int) -> None:
        t = self.scope.templates[int(ti)]
        self.submitted[int(ti)] += 1
        if self.router is not None:
            uid = self.router.submit(
                list(t.prompt), max_new_tokens=t.max_new_tokens,
                eos_id=t.eos_id, tenant=t.tenant, priority=t.priority)
            r, local = self.router.placements[uid]
            self.uid_template[(r, local)] = int(ti)
        else:
            uid = self.reps[0].submit(
                list(t.prompt), max_new_tokens=t.max_new_tokens,
                eos_id=t.eos_id, tenant=t.tenant, priority=t.priority)
            self.uid_template[(0, uid)] = int(ti)

    def _act_pass(self, r: int) -> None:
        rep = self.reps[r]
        if not rep.wave_open:
            rep.begin_run()
        if rep.run_pending():
            rep.run_pass()

    def _act_end_wave(self, r: int) -> None:
        if self.abort_transit_on_end_wave:
            # protocol rule under model check: a handoff extract rides
            # its source wave's dispatch queue, so it must complete
            # (restore) or ABORT before that wave closes — exactly the
            # no-unresolved-PendingSwapOut-across-a-wave-boundary law
            # extended to the disaggregation pair.
            kept = []
            for entry in self.transit:
                if entry["src"] == r:
                    entry["pending"].resolve()   # abort: fetch + drop
                else:
                    kept.append(entry)
            self.transit = kept
        self.reps[r].finish_run()

    def _act_drain(self, r: int) -> None:
        self.reps[r].drain_pending_swaps()

    def _act_shed(self, r: int) -> None:
        self.reps[r].shed_worst()

    def _act_evict(self, r: int, n: int) -> None:
        self.evicts_done += 1
        rep = self.reps[r]
        freed = rep.prefix.evict_lru(int(n))
        if freed:
            rep.telemetry.prefix_evicted(rep.prefix.evictions)

    # -- the abstract disaggregation handoff pair -----------------------------
    def _handoff_chain(self, r: int) -> Optional[Tuple[Tuple[int, ...],
                                                       List[int]]]:
        """Longest fully-HBM full-page chain from the root of replica
        ``r``'s radix tree, following the smallest-token edge at each
        level — the prefix a prefill replica would hand to a decode
        replica.  None when the root has no HBM full-page edge."""
        rep = self.reps[r]
        if rep.prefix is None:
            return None
        edges = {}
        for e in rep.prefix.walk_edges():
            if e["kind"] == "full" and e["page"] is not None:
                edges.setdefault(e["path"], []).append(
                    (e["tokens"], e["page"]))
        path: Tuple[int, ...] = ()
        tokens: List[int] = []
        pages: List[int] = []
        while path in edges:
            et, page = min(edges[path])
            tokens.extend(et)
            pages.append(int(page))
            path = path + et
        if not pages:
            return None
        return tuple(tokens), pages

    def _act_handoff_extract(self, r: int) -> None:
        """Extract-on-A: snapshot a cached prefix's page contents via
        the engine's deferred swap-out path (modeled on the ISSUE 18
        ``extract_pages`` program) — a pure read; A's pages stay
        pinned by its prefix cache."""
        self.handoffs_done += 1
        rep = self.reps[r]
        tokens, pages = self._handoff_chain(r)
        pending = rep.engine.swap_out_pages(rep.cache, pages,
                                            defer=True)
        self.transit.append({"src": int(r), "tokens": tuple(tokens),
                             "n": len(pages), "pending": pending})

    def _act_handoff_restore(self, r: int) -> None:
        """Restore-on-B: acquire fresh pages on the destination, land
        the extracted content (``restore_pages``-shaped), index the
        prefix in B's radix tree, then drop the request-level refs —
        the cache pin keeps exactly the pages B now serves from."""
        entry = self.transit.pop(0)
        rep = self.reps[r]
        k, v = entry["pending"].resolve()
        pages = rep.alloc.acquire(entry["n"])
        assert pages is not None, "enabled_actions checked free_pages"
        rep.cache = rep.engine.swap_in_pages(
            rep.cache, pages, k, v)
        rep.telemetry.page_swapped("in", len(pages))
        rep.prefix.insert(list(entry["tokens"]), pages)
        rep.alloc.release(pages)

    # -- canonical state ------------------------------------------------------
    def canonical(self) -> str:
        """Deterministic projection of the protocol state: allocator
        books (free-list ORDER kept — it picks the next acquire),
        radix shape with LRU STAMPS projected to ranks, host-store
        ledger with HANDLES projected to sorted ranks, queue/slot/
        pending/transit contents, page content tags.  Monotonic
        counters (uids, clocks, telemetry totals, SLO windows) are
        projected OUT — see the module docstring for why that is
        sound at these scopes."""
        parts: List = [tuple(sorted(self.submitted.items())),
                       self.evicts_done, self.handoffs_done]
        parts.append(tuple(
            (e["src"], e["n"], _tag(e["tokens"]),
             bool(e["pending"].done))
            for e in self.transit))
        for r, rep in enumerate(self.reps):
            snap = rep.alloc.snapshot()
            store = rep.host_store
            handles = (sorted(store.snapshot()) if store is not None
                       else [])
            hrank = {h: i for i, h in enumerate(handles)}
            edges = (rep.prefix.walk_edges()
                     if rep.prefix is not None else [])
            stamps = sorted({e["stamp"] for e in edges})
            srank = {s: i for i, s in enumerate(stamps)}
            etup = tuple(
                (e["path"], e["tokens"], e["kind"],
                 -1 if e["page"] is None else int(e["page"]),
                 -1 if e["host"] is None else hrank[e["host"]],
                 srank[e["stamp"]])
                for e in edges)
            if store is not None:
                stat = store.snapshot()
                stup = tuple(
                    (hrank[h], stat[h],
                     (int(store.peek_resident(h)[0].reshape(-1)[0])
                      if stat[h] == "resident" else -1))
                    for h in handles)
            else:
                stup = ()
            queue = tuple(
                (self.uid_template.get((r, req.uid), -1),
                 req.tenant, req.priority)
                for req in rep.queue)
            slots = tuple(
                None if st is None else
                (self.uid_template.get((r, st.uid), -1),
                 st.prefilled, tuple(st.generated), st.issued,
                 st.capacity, tuple(int(p) for p in (st.pages or ())))
                for st in rep.slot_states())
            # the decode step launched and not yet read (ISSUE 37): its
            # vector and which slots' states it was launched for
            ahead = (None if rep._ahead is None else
                     (tuple(int(x) for x in rep._ahead[0]),
                      tuple(st is not None for st in rep._ahead[1])))
            # per-tenant admission recency as a RANK order (the
            # fairness tiebreak reads only the order)
            tla = sorted(rep._tenant_last_admit.items(),
                         key=lambda kv: kv[1])
            cache = rep.cache
            ctup = (() if cache is None else
                    (tuple(int(x) for x in cache.content),
                     tuple(int(x) for x in cache.lengths),
                     tuple(int(x) for x in cache.last_tokens),
                     tuple(int(x) for x in cache.page_table.ravel())))
            parts.append((
                snap["free"], tuple(sorted(snap["refs"].items())),
                etup, stup, queue, rep.wave_open, slots, ahead,
                tuple(rep._run_free), rep.pending_swaps,
                tuple(t for t, _ in tla), ctup))
        if self.router is not None:
            parts.append(self.router._rr_next % len(self.reps))
        return repr(tuple(parts))


# -- exploration / replay / shrinking ----------------------------------------

@dataclasses.dataclass
class Violation:
    """One invariant failure: the finding codes that fired, the
    per-code messages, and the (already truncated-at-failure) trace
    that reproduces them from a fresh harness."""
    codes: Tuple[str, ...]
    messages: Tuple[str, ...]
    trace: Tuple[Action, ...]


@dataclasses.dataclass
class ExploreResult:
    states: int                     # distinct canonical states visited
    transitions: int                # explored edges between them
    depth: int                      # depth bound applied
    truncated: bool                 # hit max_states (pin must be clean)
    violation: Optional[Violation]


def replay(build: Callable[[], ProtocolHarness],
           trace: Sequence[Action],
           check: Callable[[ProtocolHarness], List[Tuple[str, str]]],
           ) -> Tuple[ProtocolHarness, Optional[Violation]]:
    """Re-execute ``trace`` on a fresh harness, checking invariants
    after every action.  Actions no longer enabled (a shrink deleted a
    prerequisite) are SKIPPED, so every candidate trace stays legal.
    Returns the harness and the first violation (trace truncated at
    the failing action) or None."""
    h = build()
    vio = _check(h, check, ())
    if vio is not None:
        return h, vio
    applied: List[Action] = []
    for action in trace:
        if tuple(action) not in {tuple(a)
                                 for a in h.enabled_actions()}:
            continue
        h.apply(action)
        applied.append(tuple(action))
        vio = _check(h, check, tuple(applied))
        if vio is not None:
            return h, vio
    return h, None


def _check(h, check, trace) -> Optional[Violation]:
    found = check(h)
    if not found:
        return None
    return Violation(codes=tuple(c for c, _ in found),
                     messages=tuple(m for _, m in found),
                     trace=tuple(trace))


def _exec(build: Callable[[], ProtocolHarness],
          trace: Sequence[Action]) -> ProtocolHarness:
    """Re-execute an already-validated trace (every action was enabled
    when the edge was first explored, and the model is deterministic)
    without per-step invariant checks — the explorer's branch
    mechanism."""
    h = build()
    for action in trace:
        h.apply(action)
    return h


def explore(build: Callable[[], ProtocolHarness],
            check: Callable[[ProtocolHarness], List[Tuple[str, str]]],
            *, max_depth: int, max_states: int = 50000,
            ) -> ExploreResult:
    """Bounded exhaustive breadth-first exploration with canonical
    dedup.  Breadth-first + dedup means every state is reached (and
    invariant-checked) by a shortest trace, and a violation's raw
    counterexample is already depth-minimal.  Deterministic: action
    order is ``enabled_actions()`` order, queue order is FIFO, no wall
    clock, no RNG.  Stops at the FIRST violation (shrink it
    afterwards).  Invariants run once per explored EDGE — the prefix
    states were each checked when their own edge was explored."""
    h0 = build()
    vio = _check(h0, check, ())
    if vio is not None:
        return ExploreResult(1, 0, max_depth, False, vio)
    seen = {h0.canonical()}
    frontier: List[Tuple[Tuple[Action, ...], List[Action]]] = [
        ((), h0.enabled_actions())]
    states, transitions = 1, 0
    for _depth in range(max_depth):
        nxt: List[Tuple[Tuple[Action, ...], List[Action]]] = []
        for trace, actions in frontier:
            for action in actions:
                transitions += 1
                path = trace + (tuple(action),)
                h = _exec(build, path)
                vio = _check(h, check, path)
                if vio is not None:
                    return ExploreResult(states, transitions,
                                         max_depth, False, vio)
                key = h.canonical()
                if key in seen:
                    continue
                seen.add(key)
                states += 1
                if states > max_states:
                    return ExploreResult(
                        states, transitions, max_depth, True, None)
                nxt.append((path, h.enabled_actions()))
        if not nxt:
            break
        frontier = nxt
    return ExploreResult(states, transitions, max_depth, False, None)


def shrink(build: Callable[[], ProtocolHarness],
           violation: Violation,
           check: Callable[[ProtocolHarness], List[Tuple[str, str]]],
           ) -> Violation:
    """Action-deletion minimization: repeatedly try dropping each
    action; keep a deletion when the SAME primary finding code still
    fires.  Converges to a 1-minimal counterexample (no single action
    can be removed)."""
    target = violation.codes[0]
    best = violation
    changed = True
    while changed:
        changed = False
        for i in range(len(best.trace)):
            cand = best.trace[:i] + best.trace[i + 1:]
            _, vio = replay(build, cand, check)
            if vio is not None and vio.codes[0] == target \
                    and len(vio.trace) < len(best.trace):
                best = vio
                changed = True
                break
    return best


def random_walk(build: Callable[[], ProtocolHarness],
                check: Callable[[ProtocolHarness],
                                List[Tuple[str, str]]],
                *, steps: int, seed: int) -> int:
    """Seeded random long walk (the slow-lane smoke): ``steps``
    uniformly-chosen enabled actions, invariants checked after each.
    Deterministic per seed.  Returns the number of actions actually
    applied (the walk ends early only if nothing is enabled, which
    the scopes' submit caps eventually force).  Raises AssertionError
    on any violation, carrying the trace."""
    import random
    rng = random.Random(seed)
    h = build()
    applied = 0
    for _ in range(steps):
        acts = h.enabled_actions()
        if not acts:
            break
        h.apply(acts[rng.randrange(len(acts))])
        applied += 1
        found = check(h)
        if found:
            raise AssertionError(
                f"invariant {found[0][0]} violated at step {applied} "
                f"(seed {seed}): {found[0][1]}\ntrace: {h.trace}")
    return applied


# -- repro files -------------------------------------------------------------

def write_repro(path, scope: Scope, violation: Violation) -> None:
    """Persist a minimized counterexample as a replayable repro file:
    the scope config, the action trace, and the finding codes it must
    reproduce."""
    doc = {"scope": scope.to_json(),
           "codes": list(violation.codes),
           "messages": list(violation.messages),
           "trace": [list(a) for a in violation.trace]}
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")


def load_repro(path) -> Tuple[Scope, Tuple[str, ...],
                              Tuple[Action, ...]]:
    """Load a repro file back: ``(scope, codes, trace)``.  Re-execute
    with :func:`replay` (passing the same twin build used to produce
    it) and assert the primary code fires again."""
    with open(path) as f:
        doc = json.load(f)
    sd = dict(doc["scope"])
    sd["templates"] = tuple(Template(**t) for t in sd["templates"])
    for key in ("evict_sizes",):
        sd[key] = tuple(sd[key])
    scope = Scope(**sd)
    trace = tuple(tuple(a) for a in doc["trace"])
    return scope, tuple(doc["codes"]), trace
