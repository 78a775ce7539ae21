"""Device time by the program's own named scopes.

The program names its stages with ``jax.named_scope("apex_...")``
(``apex_prefill_cache_insert``, ``apex_moe_experts``,
``apex_train_forward`` ...).  A v5e profile names each ``XLA Ops`` event by
the operation's HLO text without its metadata, so no scope reaches an
event; the program keeps, for every executable it compiled, the table
``instruction -> (scope, backward)`` read from that executable's optimized
HLO (``apex_tpu.observability.xla_stats.scope_tables()``, called here, in
the run's own process, after the window).  This file joins the two:

* each ``XLA Ops`` event belongs to the executable whose ``XLA Modules``
  event on the same chip contains it in time (``jit_prefill_paged_fn(<id>)``);
* the executable's table is the one of that module name whose result types
  match the events' heads (``%fusion.12 = bf16[...]{...} fusion(...``):
  two executables of one jit name — prefill buckets — share their
  instruction names, not their shapes; the first match of an ``<id>`` is
  kept for its later events;
* each device nanosecond counts once, for the innermost operation event
  that covers it (a ``while`` holds its body's events), under that
  instruction's ``(chain, backward)`` — its ``apex_*`` scopes outer to
  inner, so a kernel's time is also its calling stage's
  (``apex_train_optimizer`` holds ``apex_lamb_stage1``); an instruction
  with no scope, with no entry in its table, or in an executable without
  a table is *unattributed*.

A program without the tables (one older than them), a run without a trace,
or a trace with no module events reads ``None`` everywhere.
"""
from __future__ import annotations

import bisect
import heapq
import re
from collections import defaultdict

from . import spans

_HEAD = re.compile(r"%([^\s=]+) = (.*)", re.S)
UNATTRIBUTED = ((), False)
#: an operation event may end a rounding step after its module's event
SLACK_NS = 10.0

_CACHE: list = []          # [(trace, result)]: the readers share one join


def program_tables() -> tuple:
    """The program's tables, or ``()`` where it keeps none."""
    try:
        from apex_tpu.observability import xla_stats
    except ImportError:
        return ()
    read = getattr(xla_stats, "scope_tables", None)
    return tuple(read()) if read is not None else ()


def _module_of(event_name: str) -> tuple:
    """``jit_step(123)`` -> ``("jit_step", "123")``."""
    name, _, rest = event_name.partition("(")
    return name, rest.rstrip(")")


def _matches(table, heads) -> int:
    """How many of the events' ``(instruction, text after ' = ')`` heads
    the table's result types agree with (a head cut short by the trace's
    name limit agrees with any type it begins)."""
    hit = 0
    for name, rest in heads:
        t = table.types.get(name)
        if t is not None and (rest.startswith(t + " ") or t.startswith(rest)):
            hit += 1
    return hit


def _choose(tables, heads):
    """The table of one module name whose types the heads match best;
    ``None`` where there is none or none matches."""
    if len(tables) == 1:
        return tables[0]
    best = max(tables, key=lambda t: _matches(t, heads), default=None)
    return best if best is not None and _matches(best, heads) else None


def _exclusive(events) -> dict:
    """Nanoseconds by key over ``(start, end, key)`` intervals, each
    nanosecond to the latest-started interval that covers it (the innermost
    where they nest); the values sum to the intervals' union."""
    out = defaultdict(float)
    stack, alive, ends = [], set(), []
    cur = None

    def top():
        while stack and stack[-1] not in alive:
            stack.pop()
        return stack[-1] if stack else None

    def advance(t):
        nonlocal cur
        while ends and ends[0][0] <= t:
            end, i = heapq.heappop(ends)
            j = top()
            if j is not None and end > cur:
                out[events[j][2]] += end - cur
            cur = max(cur, end)
            alive.discard(i)
        j = top()
        if j is not None and t > cur:
            out[events[j][2]] += t - cur
        cur = max(cur, t)

    events = sorted(events, key=lambda x: (x[0], -x[1]))
    for i, (s, e, _) in enumerate(events):
        if cur is None:
            cur = s
        advance(s)
        stack.append(i)
        alive.add(i)
        heapq.heappush(ends, (e, i))
    if ends:
        advance(max(e for e, _ in ends))
    return out


def attribute(trace, tables=None):
    """``{"scopes": {(chain, backward): seconds}, "unattributed": seconds,
    "modules": {module: [seconds, attributed seconds]}, "ops": {(module,
    instruction): [chain, backward, seconds]}, "picked": {module event:
    fingerprint of its table}}`` averaged over the chips, or ``None`` (no
    trace, no module events, or no tables)."""
    if trace is None or not trace.modules:
        return None
    mine = tables is None
    for t, result in _CACHE:
        if t is trace and mine:
            return result
    tables = program_tables() if mine else tuple(tables)
    if not tables:
        return None
    by_module = defaultdict(list)
    for table in tables:
        by_module[table.module].append(table)
    chips = max(1, len(trace.modules))
    scopes, modules, ops_s, picked = (defaultdict(float), defaultdict(
        lambda: [0.0, 0.0]), {}, {})
    for chip, mods in trace.modules.items():
        ops = trace.ops.get(chip, [])
        starts = [e.start for e in ops]
        chosen = {}
        for m in mods:
            module, pid = _module_of(m.name)
            inside = []
            for i in range(bisect.bisect_left(starts, m.start), len(ops)):
                e = ops[i]
                if e.start > m.end:
                    break
                if e.end <= m.end + SLACK_NS:
                    inside.append(e)
            heads = []
            for e in inside:
                h = _HEAD.match(e.name.lstrip())
                heads.append((h.group(1), h.group(2)) if h
                             else (e.name[:80], ""))
            key = (module, pid)
            if key not in chosen:
                chosen[key] = _choose(by_module.get(module, []), heads)
            table = chosen[key]
            picked[m.name] = table.fingerprint if table else None
            keyed = []
            for e, (name, _) in zip(inside, heads):
                sb = (table.scopes.get(name, UNATTRIBUTED) if table
                      else UNATTRIBUTED)
                keyed.append((e.start, e.end, (sb, name)))
            for (sb, name), ns in _exclusive(keyed).items():
                s = ns * 1e-9 / chips
                scopes[sb] += s
                modules[module][0] += s
                modules[module][1] += s if sb[0] else 0.0
                row = ops_s.setdefault((module, name), [sb[0], sb[1], 0.0])
                row[2] += s
    result = {"unattributed": scopes.pop(UNATTRIBUTED, 0.0),
              "scopes": dict(scopes), "modules": dict(modules),
              "ops": ops_s, "picked": picked}
    if mine:
        _CACHE[:] = [(trace, result)]
    return result


def scope_seconds(trace, scope_names, backward=None):
    """Device seconds of the operations inside any of the scopes named
    (``backward`` True / False keeps one direction, ``None`` both), or
    ``None`` where no operation is."""
    got = attribute(trace)
    if got is None:
        return None
    hit = [s for (chain, back), s in got["scopes"].items()
           if any(c in scope_names for c in chain)
           and (backward is None or back == backward)]
    return sum(hit) if hit else None


def ms_per_pass(run, scope_names):
    """Device ms a traced pass of the scopes named (both directions)."""
    seconds = scope_seconds(run.trace, scope_names)
    passes = spans.traced_passes(run.trace)
    if seconds is None or not passes:
        return None
    return seconds * 1e3 / len(passes)


def ms_per_step(run, scope_names, backward=None):
    """Device ms a traced training step of the scopes named."""
    seconds = scope_seconds(run.trace, scope_names, backward)
    steps = run.facts.get("traced_steps", 0)
    if seconds is None or not steps:
        return None
    return seconds * 1e3 / steps


MOE_STAGES = ("apex_moe_route", "apex_moe_sort", "apex_moe_experts",
              "apex_moe_combine", "apex_moe_shared")
MOE_DISPATCH = ("apex_moe_route", "apex_moe_sort", "apex_moe_combine")
