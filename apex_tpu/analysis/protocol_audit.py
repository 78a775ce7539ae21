"""Serving control-plane protocol auditor — the FIFTH analysis engine
(``apex-tpu-analyze --protocol``).

Exhaustive small-scope model checking of the allocator / prefix-cache /
host-tier / scheduler / router state machines: the committed
:data:`SCOPES` are explored breadth-first to a depth bound with
canonical-state dedup (:mod:`~apex_tpu.analysis.protocol_model`), and
the pinned invariants APX401–APX407 are asserted at every explored
state.  The components under check are the REAL serving classes —
``PageAllocator``, ``PrefixCache``, ``HostPageStore``,
``SlotScheduler``, ``FleetRouter`` — only the device is a stub, so a
clean pin is a statement about the code that serves, not about a
parallel model of it.

The laws (each names the L0 churn-sweep law it subsumes):

=======  ==============================================================
APX401   allocator conservation: ``free + distinct live == num_pages``,
         free list duplicate- and overlap-free, every refcount >= 1
APX402   refcount-weighted conservation: ``sum(refcounts) ==`` slot-row
         holdings + cache-pinned edges
APX403   per-page holder books: every page's refcount equals the
         number of slot rows + cache edges holding it (no page
         reachable from two rows without matching share refs); no
         duplicate page inside one row; page CONTENT matches each
         row's token slice (a mismatch means another writer clobbered
         a page this row trusts — the skipped-COW signature)
APX404   no dangling references: no slot row, device page-table entry,
         or cache edge references a freed (refcount-0) page
APX405   radix tier invariant: page XOR host per edge, nothing below a
         host edge is HBM, one cache ref per indexed page/handle,
         ``pinned_pages``/``host_pages`` book consistency, full-HBM
         edge and resident host-slab content match their tokens
APX406   host-store byte budget: ``bytes_used == pages * page_bytes <=
         capacity``, store handles mirror the host edges exactly
APX407   lifecycle + wave-boundary + fleet: per-replica ``submitted ==
         finished + active + rejected``; NO unresolved PendingSwapOut
         (deferred offload or handoff extract) and no decode step
         launched ahead of its read (ISSUE 37) survives a wave
         boundary; the router's three-level conservation holds
=======  ==============================================================

On a violation the engine shrinks the trace by action deletion to a
1-minimal counterexample and writes a REPLAYABLE repro file
(``.protocol_repro_<scope>.json``) that :func:`replay_repro`
re-executes.  Clean results pin to ``.analysis_protocol.json`` (scope
configs + canonical state-space sizes, byte-identical across runs);
any drift — state count, config, a scope added or dropped — is an
APX400 finding until consciously re-pinned with ``--write-protocol``.

The abstract disaggregation handoff pair (``handoff_extract`` /
``handoff_restore`` in the ``fleet`` scope) model-checks ROADMAP
item 1's cross-replica prefix handoff protocol BEFORE its device
implementation exists: the pinned clean scope is the proof obligation
the real implementation must keep discharging.
"""
from __future__ import annotations

import collections
import json
import os
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

from apex_tpu.analysis.finding import Finding
from apex_tpu.analysis.protocol_model import (ExploreResult,
                                              ProtocolHarness, Scope,
                                              Template, _tag, explore,
                                              replay, shrink,
                                              write_repro)

__all__ = ["PIN_NAME", "SCOPES", "INVARIANTS", "check_harness",
           "audit_scope", "run_protocol_audit", "compare_protocol",
           "replay_repro", "protocol_scope_env"]

PIN_NAME = ".analysis_protocol.json"
_SCOPE_ENV = "APEX_TPU_PROTOCOL_SCOPE"

#: The pinned invariant battery.  ``covers`` names the conservation
#: laws the L0 churn sweeps assert wave-by-wave — the L1 guard test
#: checks this registry covers every one of them, so the protocol
#: audit can never silently check LESS than the runtime sweeps do.
INVARIANTS: Dict[str, dict] = {
    "APX401": {
        "name": "allocator-conservation",
        "description": "free + distinct live pages == num_pages; "
                       "free list has no duplicates and no overlap "
                       "with the ref table; every refcount >= 1",
        "covers": ("allocator-conservation",),
    },
    "APX402": {
        "name": "refcount-weighted-conservation",
        "description": "sum of refcounts == slot-row holdings + "
                       "cache-pinned edges",
        "covers": ("refcount-weighted-conservation",),
    },
    "APX403": {
        "name": "per-page-holder-books",
        "description": "each page's refcount equals its holder count "
                       "(slot rows + cache edges); no duplicate page "
                       "in a row; page content matches each row's "
                       "token slice",
        "covers": ("share-ref-matching", "cow-write-isolation"),
    },
    "APX404": {
        "name": "no-dangling-page-refs",
        "description": "no slot row, page-table entry, or cache edge "
                       "references a freed page",
        "covers": ("no-dangling-page-refs",),
    },
    "APX405": {
        "name": "radix-tier-invariant",
        "description": "page XOR host per edge; nothing below a host "
                       "edge is HBM; one cache ref per indexed "
                       "page/handle; pinned_pages/host_pages books; "
                       "full-edge and resident-slab content integrity",
        "covers": ("prefix-pin-books", "host-tier-shape"),
    },
    "APX406": {
        "name": "host-store-budget",
        "description": "bytes_used == pages * page_bytes <= capacity; "
                       "store handles mirror host edges exactly",
        "covers": ("host-byte-budget", "host-mirror"),
    },
    "APX407": {
        "name": "lifecycle-and-wave-boundary",
        "description": "submitted == finished + active + rejected per "
                       "replica; no unresolved PendingSwapOut across "
                       "a wave boundary (deferred offloads AND "
                       "handoff extracts) and no launched decode "
                       "step left unread there; router three-level "
                       "conservation holds",
        "covers": ("lifecycle-conservation", "wave-boundary-swaps",
                   "fleet-three-level"),
    },
}


def protocol_scope_env() -> Optional[List[str]]:
    """``APEX_TPU_PROTOCOL_SCOPE``: comma-separated scope names the
    ``--protocol`` engine restricts to (``0``/empty/unset = all
    committed scopes; a restricted run refuses ``--write-protocol``)."""
    raw = os.environ.get(_SCOPE_ENV, "").strip()
    if not raw or raw == "0":
        return None
    return [s.strip() for s in raw.split(",") if s.strip()]


# -- the committed small scopes ----------------------------------------------
# Kept deliberately tiny: exhaustive exploration must finish in
# seconds, and small-scope coverage is the point (the "small scope
# hypothesis": protocol bugs that exist at all exist at tiny sizes).

SCOPES: Dict[str, Scope] = {
    # single replica, shared-prefix family with a COW boundary page,
    # chunked prefill, shed — the allocator/prefix/scheduler core
    "core": Scope(
        name="core", replicas=1, slots=2, num_pages=7, page_size=2,
        max_pages_per_slot=4, prefill_chunk=2, shed=True,
        evict_sizes=(1,), evict_cap=1,
        templates=(
            # budgets sized so A is still DECODING when A2's admission
            # matches A's inserted prefix: the explored states include
            # one page held by two slot rows plus the cache pin
            # (refcount 3) AND a COW of the shared boundary page —
            # multi-owner protocol states, not just cache pins
            Template("A", (1, 2, 3), max_new_tokens=4),
            Template("A2", (1, 2, 3, 4), max_new_tokens=3),
            Template("B", (5, 6), max_new_tokens=2, tenant="t2"),
        ),
        max_depth=9),
    # single replica over a 2-page host tier: evict-to-host (deferred
    # slabs), drain, swap-in on the repeat template's host hit
    "tiered": Scope(
        name="tiered", replicas=1, slots=1, num_pages=4, page_size=2,
        max_pages_per_slot=2, host_tier_pages=2,
        evict_sizes=(2,), evict_cap=2,
        templates=(
            Template("A", (1, 2, 3), max_new_tokens=1, cap=2),
            Template("B", (5, 6, 7), max_new_tokens=1, tenant="t2"),
        ),
        max_depth=10),
    # two replicas behind the real prefix-affinity router, plus the
    # abstract disaggregation handoff pair (ROADMAP item 1)
    "fleet": Scope(
        name="fleet", replicas=2, slots=1, num_pages=4, page_size=2,
        max_pages_per_slot=2, policy="prefix_affinity", shed=True,
        handoff=True, handoff_cap=1,
        templates=(
            Template("A", (1, 2), max_new_tokens=1),
            Template("B", (7, 8), max_new_tokens=1, tenant="t2"),
        ),
        max_depth=10),
}


# -- the invariant battery ---------------------------------------------------

def _occupied(rep) -> List[tuple]:
    return [(s, st) for s, st in enumerate(rep.slot_states())
            if st is not None]


def _edges(rep) -> List[dict]:
    return rep.prefix.walk_edges() if rep.prefix is not None else []


def _check_allocator(h: ProtocolHarness) -> List[Tuple[str, str]]:
    out = []
    n = h.scope.num_pages
    for r, rep in enumerate(h.reps):
        if rep.alloc is None:
            continue
        snap = rep.alloc.snapshot()
        free, refs = snap["free"], snap["refs"]
        if len(set(free)) != len(free):
            out.append(("APX401",
                        f"replica {r}: duplicate page in the free "
                        f"list {free}"))
        overlap = sorted(set(free) & set(refs))
        if overlap:
            out.append(("APX401",
                        f"replica {r}: pages {overlap} both free and "
                        f"ref-counted"))
        if len(set(free)) + len(refs) != n:
            out.append(("APX401",
                        f"replica {r}: {len(set(free))} free + "
                        f"{len(refs)} live != {n} pool pages"))
        bad = sorted(p for p, c in refs.items() if c < 1)
        if bad:
            out.append(("APX401",
                        f"replica {r}: pages {bad} held at "
                        f"refcount < 1"))
        oob = sorted(p for p in list(free) + list(refs)
                     if not 0 <= p < n)
        if oob:
            out.append(("APX401",
                        f"replica {r}: out-of-range page ids {oob}"))
    return out


def _holders(rep) -> collections.Counter:
    hold: collections.Counter = collections.Counter()
    for _s, st in _occupied(rep):
        for p in st.pages or ():
            hold[int(p)] += 1
    for e in _edges(rep):
        if e["page"] is not None:
            hold[int(e["page"])] += 1
    return hold


def _check_refcounts(h: ProtocolHarness) -> List[Tuple[str, str]]:
    out = []
    for r, rep in enumerate(h.reps):
        if rep.alloc is None:
            continue
        refs = rep.alloc.snapshot()["refs"]
        hold = _holders(rep)
        if sum(refs.values()) != sum(hold.values()):
            out.append(("APX402",
                        f"replica {r}: sum(refcounts) "
                        f"{sum(refs.values())} != slot-row + "
                        f"cache-edge holdings {sum(hold.values())}"))
    return out


def _check_rows(h: ProtocolHarness) -> List[Tuple[str, str]]:
    out = []
    for r, rep in enumerate(h.reps):
        if rep.alloc is None:
            continue
        refs = rep.alloc.snapshot()["refs"]
        hold = _holders(rep)
        for s, st in _occupied(rep):
            pages = [int(p) for p in st.pages or ()]
            if len(set(pages)) != len(pages):
                out.append(("APX403",
                            f"replica {r} slot {s}: page mapped "
                            f"twice in one row {pages}"))
        for p in sorted(set(hold) | set(refs)):
            if hold.get(p, 0) != refs.get(p, 0):
                out.append(("APX403",
                            f"replica {r}: page {p} held by "
                            f"{hold.get(p, 0)} slot-row/cache "
                            f"owner(s) but ref-counted "
                            f"{refs.get(p, 0)}"))
        cache = rep.cache
        if cache is None or not hasattr(cache, "content"):
            continue            # content laws are stub-cache only
        ps = h.scope.page_size
        for s, st in _occupied(rep):
            length = int(cache.lengths[s])
            if length == 0:
                continue        # admitted, first prefill piece pending
            seq = (list(st.prompt) + list(st.generated))[:length]
            if len(seq) < length:
                out.append(("APX403",
                            f"replica {r} slot {s}: cache length "
                            f"{length} exceeds the request's "
                            f"{len(seq)} known tokens"))
                continue
            row = [int(x) for x in cache.page_table[s]]
            pages = [int(p) for p in st.pages or ()]
            if row[:len(pages)] != pages:
                out.append(("APX403",
                            f"replica {r} slot {s}: device row "
                            f"{row[:len(pages)]} diverges from the "
                            f"slot books {pages}"))
                continue
            for j in range(-(-length // ps)):
                piece = seq[j * ps:min(length, (j + 1) * ps)]
                got = int(cache.content[row[j]])
                if got != _tag(piece):
                    out.append((
                        "APX403",
                        f"replica {r} slot {s}: page {row[j]} "
                        f"(ordinal {j}) content does not match the "
                        f"row's tokens {piece} — another writer "
                        f"clobbered a page this row holds"))
    return out


def _check_dangling(h: ProtocolHarness) -> List[Tuple[str, str]]:
    out = []
    for r, rep in enumerate(h.reps):
        if rep.alloc is None:
            continue
        live = set(rep.alloc.snapshot()["refs"])
        for s, st in _occupied(rep):
            dead = sorted({int(p) for p in st.pages or ()} - live)
            if dead:
                out.append(("APX404",
                            f"replica {r} slot {s}: row references "
                            f"freed page(s) {dead}"))
        for e in _edges(rep):
            if e["page"] is not None and int(e["page"]) not in live:
                out.append(("APX404",
                            f"replica {r}: cache edge at "
                            f"{e['path'] + e['tokens']} references "
                            f"freed page {e['page']}"))
        cache = rep.cache
        if cache is not None and hasattr(cache, "page_table"):
            occupied = {s for s, _ in _occupied(rep)}
            for s in range(cache.page_table.shape[0]):
                if s not in occupied:
                    continue    # idle rows are device-side trash
                dead = sorted({int(p) for p in cache.page_table[s]
                               if p >= 0} - live)
                if dead:
                    out.append(("APX404",
                                f"replica {r}: device page-table row "
                                f"{s} references freed page(s) "
                                f"{dead}"))
    return out


def _check_prefix(h: ProtocolHarness) -> List[Tuple[str, str]]:
    out = []
    for r, rep in enumerate(h.reps):
        if rep.prefix is None:
            continue
        edges = _edges(rep)
        pages: collections.Counter = collections.Counter()
        hosts: collections.Counter = collections.Counter()
        for e in edges:
            if (e["page"] is None) == (e["host"] is None):
                out.append(("APX405",
                            f"replica {r}: edge at "
                            f"{e['path'] + e['tokens']} violates "
                            f"page XOR host (page={e['page']}, "
                            f"host={e['host']})"))
            if e["page"] is not None:
                pages[int(e["page"])] += 1
            if e["host"] is not None:
                hosts[int(e["host"])] += 1
        for p, c in sorted(pages.items()):
            if c > 1:
                out.append(("APX405",
                            f"replica {r}: page {p} indexed by {c} "
                            f"cache edges"))
        for hd, c in sorted(hosts.items()):
            if c > 1:
                out.append(("APX405",
                            f"replica {r}: host handle {hd} carried "
                            f"by {c} cache edges"))
        host_roots = [tuple(e["path"]) + tuple(e["tokens"])
                      for e in edges if e["host"] is not None]
        for e in edges:
            if e["page"] is None:
                continue
            path = tuple(e["path"])
            for root in host_roots:
                if len(root) <= len(path) \
                        and path[:len(root)] == root:
                    out.append((
                        "APX405",
                        f"replica {r}: HBM edge at "
                        f"{path + tuple(e['tokens'])} sits below "
                        f"host edge {root} — tier invariant broken"))
        if rep.prefix.pinned_pages != sum(pages.values()):
            out.append(("APX405",
                        f"replica {r}: pinned_pages book "
                        f"{rep.prefix.pinned_pages} != {sum(pages.values())} "
                        f"HBM edges"))
        if rep.prefix.host_pages != sum(hosts.values()):
            out.append(("APX405",
                        f"replica {r}: host_pages book "
                        f"{rep.prefix.host_pages} != {sum(hosts.values())} "
                        f"host edges"))
        cache, store = rep.cache, rep.host_store
        if cache is None or not hasattr(cache, "content"):
            continue
        for e in edges:
            if e["kind"] != "full":
                continue        # partial tails legitimately extended
            want = _tag(e["tokens"])
            if e["page"] is not None:
                got = int(cache.content[int(e["page"])])
                if got != want:
                    out.append((
                        "APX405",
                        f"replica {r}: full edge at "
                        f"{e['path'] + e['tokens']} page {e['page']} "
                        f"content does not match its tokens"))
            elif store is not None:
                slab = store.peek_resident(int(e["host"]))
                if slab is None:
                    continue    # deferred and still in flight
                got = int(slab[0].reshape(-1)[0])
                if got != want:
                    out.append((
                        "APX405",
                        f"replica {r}: host slab {e['host']} for "
                        f"edge {e['path'] + e['tokens']} does not "
                        f"match its tokens — swap-out snapshotted "
                        f"after the page was reused?"))
    return out


def _check_store(h: ProtocolHarness) -> List[Tuple[str, str]]:
    out = []
    for r, rep in enumerate(h.reps):
        store = rep.host_store
        edge_handles = sorted(int(e["host"]) for e in _edges(rep)
                              if e["host"] is not None)
        if store is None:
            if edge_handles:
                out.append(("APX406",
                            f"replica {r}: host edges {edge_handles} "
                            f"with no host store"))
            continue
        if store.bytes_used != store.pages * store.page_bytes:
            out.append(("APX406",
                        f"replica {r}: bytes_used {store.bytes_used} "
                        f"!= {store.pages} pages * "
                        f"{store.page_bytes} B"))
        if store.bytes_used > store.capacity_bytes:
            out.append(("APX406",
                        f"replica {r}: host store over budget "
                        f"({store.bytes_used} > "
                        f"{store.capacity_bytes} B)"))
        handles = sorted(store.snapshot())
        if handles != edge_handles:
            out.append(("APX406",
                        f"replica {r}: store handles {handles} do "
                        f"not mirror the host edges {edge_handles}"))
    return out


def _check_lifecycle(h: ProtocolHarness) -> List[Tuple[str, str]]:
    out = []
    for r, rep in enumerate(h.reps):
        c = rep.telemetry.conservation()
        if c["submitted"] != c["finished"] + c["active"] \
                + c["rejected"]:
            out.append(("APX407",
                        f"replica {r}: lifecycle conservation broken "
                        f"({c})"))
        if rep.wave_open:
            continue
        if rep._ahead is not None:
            out.append(("APX407",
                        f"replica {r}: a decode step launched ahead "
                        f"of its read is still unread with the wave "
                        f"closed"))
        if rep.pending_swaps:
            out.append(("APX407",
                        f"replica {r}: {rep.pending_swaps} deferred "
                        f"swap-out(s) unresolved across a wave "
                        f"boundary"))
        stranded = sum(1 for e in h.transit if e["src"] == r)
        if stranded:
            out.append(("APX407",
                        f"replica {r}: {stranded} handoff extract(s) "
                        f"in transit across the source's wave "
                        f"boundary"))
        log = getattr(rep.engine, "pending_log", None)
        if log is not None:
            open_n = sum(1 for p in log
                         if not getattr(p, "done", True))
            if open_n:
                out.append(("APX407",
                            f"replica {r}: {open_n} engine-issued "
                            f"PendingSwapOut(s) unresolved with the "
                            f"wave closed"))
    if h.router is not None:
        cons = h.router.conservation()
        if not cons["holds"]:
            out.append(("APX407",
                        f"fleet three-level conservation broken: "
                        f"{cons}"))
    return out


_CHECKERS = (_check_allocator, _check_refcounts, _check_rows,
             _check_dangling, _check_prefix, _check_store,
             _check_lifecycle)


def check_harness(h: ProtocolHarness) -> List[Tuple[str, str]]:
    """The full APX401–APX407 battery; returns EVERY violated law as
    ``(code, message)`` (one underlying bug usually breaks several
    books at once — tests assert the expected code is among them)."""
    out: List[Tuple[str, str]] = []
    for checker in _CHECKERS:
        out.extend(checker(h))
    return out


# -- running + pinning -------------------------------------------------------

def audit_scope(scope: Scope, *,
                build: Optional[Callable[[], ProtocolHarness]] = None,
                ) -> ExploreResult:
    """Explore one scope under the invariant battery; a violation
    comes back action-deletion MINIMIZED."""
    if build is None:
        build = lambda: ProtocolHarness(scope)      # noqa: E731
    res = explore(build, check_harness, max_depth=scope.max_depth,
                  max_states=scope.max_states)
    if res.violation is not None:
        res.violation = shrink(build, res.violation, check_harness)
    return res


def replay_repro(path, *,
                 build: Optional[Callable[[], ProtocolHarness]] = None,
                 ):
    """Re-execute a repro file written by the audit; returns the
    :class:`~apex_tpu.analysis.protocol_model.Violation` it reproduces
    (None if it no longer fires — the bug is fixed, delete the file).
    Pass the same twin ``build`` that produced it; default builds the
    clean harness from the embedded scope."""
    from apex_tpu.analysis.protocol_model import load_repro
    scope, _codes, trace = load_repro(path)
    if build is None:
        build = lambda: ProtocolHarness(scope)      # noqa: E731
    _h, vio = replay(build, trace, check_harness)
    return vio


def run_protocol_audit(scope_names: Optional[List[str]] = None, *,
                       repro_dir=None,
                       ) -> Tuple[List[Finding], dict]:
    """Run the protocol audit over ``scope_names`` (default: every
    committed scope) and return ``(findings, report)``.  The report is
    the pin payload: deterministic, timestamp-free, byte-identical
    across runs of the same code."""
    names = sorted(SCOPES) if scope_names is None else scope_names
    unknown = [n for n in names if n not in SCOPES]
    if unknown:
        raise ValueError(
            f"unknown protocol scope(s) {unknown}; "
            f"known: {sorted(SCOPES)}")
    findings: List[Finding] = []
    report: dict = {"version": 1, "scopes": {}}
    for name in names:
        scope = SCOPES[name]
        try:
            res = audit_scope(scope)
        except Exception as e:                      # noqa: BLE001
            findings.append(Finding(
                "APX400", f"<protocol:{name}>", 0, 0,
                f"exploration crashed: {type(e).__name__}: {e}",
                line_text=f"protocol scope {name}"))
            continue
        if res.truncated:
            findings.append(Finding(
                "APX400", f"<protocol:{name}>", 0, 0,
                f"state-space cap hit ({res.states} states > "
                f"max_states {scope.max_states}) — the scope is no "
                f"longer exhaustively explored; shrink it or raise "
                f"the cap", line_text=f"protocol scope {name}"))
            continue
        if res.violation is not None:
            vio = res.violation
            msg = (f"{vio.messages[0]} — minimized counterexample "
                   f"({len(vio.trace)} action(s)): "
                   f"{json.dumps([list(a) for a in vio.trace])}")
            if repro_dir is not None:
                repro = Path(repro_dir) / f".protocol_repro_{name}.json"
                write_repro(repro, scope, vio)
                msg += f" — repro: {repro}"
            findings.append(Finding(
                vio.codes[0], f"<protocol:{name}>", 0, 0, msg,
                line_text=f"protocol scope {name}"))
            continue
        report["scopes"][name] = {
            "states": res.states,
            "transitions": res.transitions,
            "depth": res.depth,
            "violations": 0,
            "config": scope.to_json(),
        }
    return findings, report


def compare_protocol(report: dict, committed: Optional[dict], *,
                     full: bool = True) -> List[Finding]:
    """Ratchet the fresh report against the committed pin: any drift
    — a scope's canonical state-space size, its config, a scope added
    or (on full runs) dropped — is an APX400 finding until consciously
    re-pinned with ``--write-protocol``."""
    out: List[Finding] = []
    if committed is None:
        if report["scopes"]:
            out.append(Finding(
                "APX400", f"<protocol>", 0, 0,
                f"no committed {PIN_NAME}; run --protocol "
                f"--write-protocol to pin the explored scopes",
                line_text="protocol pin missing"))
        return out
    pinned = committed.get("scopes", {})
    for name, fresh in sorted(report["scopes"].items()):
        if name not in pinned:
            out.append(Finding(
                "APX400", f"<protocol:{name}>", 0, 0,
                f"scope {name!r} is not in the committed pin; "
                f"--write-protocol to adopt it",
                line_text=f"protocol scope {name}"))
            continue
        for key in ("states", "transitions", "depth", "config"):
            if fresh[key] != pinned[name].get(key):
                out.append(Finding(
                    "APX400", f"<protocol:{name}>", 0, 0,
                    f"scope {name!r} {key} drifted from the pin "
                    f"({pinned[name].get(key)!r} -> {fresh[key]!r}): "
                    f"the explored protocol changed; review, then "
                    f"--write-protocol to re-pin",
                    line_text=f"protocol scope {name} {key}"))
    if full:
        for name in sorted(set(pinned) - set(report["scopes"])):
            out.append(Finding(
                "APX400", f"<protocol:{name}>", 0, 0,
                f"committed scope {name!r} was not produced by this "
                f"run (dropped or renamed?); --write-protocol to "
                f"re-pin", line_text=f"protocol scope {name}"))
    return out
