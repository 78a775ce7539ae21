"""Profiler scopes + on-demand trace capture.

The nvtx story, TPU-native (absorbed from the old
``apex_tpu/utils/metrics.py``): :func:`trace_annotation` marks host
regions, :func:`named_scope` names the ops traced inside a region
(both surface in TensorBoard/xprof), and the
``APEX_TPU_PROFILE_DIR`` knob arms :func:`profile_capture` — a no-op
context manager until the knob names a directory, at which point it
brackets the region with ``jax.profiler.start_trace``/``stop_trace``
and drops an xprof capture there.  ``bench.py`` legs and
``examples/generate.py`` run inside it, so grabbing a device trace of
any leg is one environment variable, zero code edits.
"""
from __future__ import annotations

import contextlib
import glob as _glob
import os
import sys
from typing import Optional

import jax

__all__ = ["trace_annotation", "named_scope", "profile_dir",
           "profile_dir_unusable", "start_profile", "stop_profile",
           "profile_capture", "PROFILE_EVENTS"]

_ENV_PROFILE_DIR = "APEX_TPU_PROFILE_DIR"

#: JSONL event kinds this module emits (schema-guard pattern).
PROFILE_EVENTS = ("profile_start", "profile_stop", "profile_skipped")


def trace_annotation(name: str, **metadata):
    """Context manager marking a host-side region in profiler traces
    (analog of ``torch.cuda.nvtx.range``).  ``metadata`` key/values
    ride the TraceMe into xprof (ISSUE 13: the engine stamps
    ``slot``/``prefill_from`` onto prefill dispatches so device traces
    correlate with the request tracer's ``trace_span`` waterfalls)."""
    return jax.profiler.TraceAnnotation(name, **metadata)


def named_scope(name: str):
    """Context manager naming ops traced inside (shows in XLA HLO/xprof).
    Metadata only — it adds no primitives, so instrumented jaxprs audit
    identically."""
    return jax.named_scope(name)


def profile_dir() -> Optional[str]:
    """The capture directory, or None when capture is disarmed
    (``APEX_TPU_PROFILE_DIR`` unset/``0``)."""
    val = os.environ.get(_ENV_PROFILE_DIR, "0")
    return None if val in ("", "0") else val


_ACTIVE: Optional[str] = None


def profile_dir_unusable(log_dir: str) -> Optional[str]:
    """Why a capture into ``log_dir`` must degrade to a no-op, or
    ``None`` when the directory is usable (ISSUE 14 satellite).

    * ``"already-populated"`` — the directory holds a prior trace
      session (``plugins/profile/*`` entries or ``*.trace.json*`` /
      ``*.xplane.pb`` files anywhere under it).  jax session names
      have one-second resolution, so a second capture into the same
      directory can silently SHADOW the old trace — refusing keeps
      every committed capture attributable to exactly one run.
    * ``"unwritable"`` — the directory (or its creation) is not
      writable, so ``start_trace`` would fail at stop time at the
      latest.
    """
    if os.path.isdir(log_dir):
        sessions = os.path.join(log_dir, "plugins", "profile")
        if os.path.isdir(sessions) and os.listdir(sessions):
            return "already-populated"
        for pattern in ("*.trace.json*", "*.xplane.pb"):
            if _glob.glob(os.path.join(log_dir, "**", pattern),
                          recursive=True):
                return "already-populated"
        if not os.access(log_dir, os.W_OK):
            return "unwritable"
        return None
    try:
        os.makedirs(log_dir, exist_ok=True)
    except OSError:
        return "unwritable"
    if not os.access(log_dir, os.W_OK):
        return "unwritable"
    return None


def _start_trace_device_only(log_dir: str) -> None:
    """``jax.profiler.start_trace`` with the Python-call tracer OFF
    (ISSUE 14).  A capture window spans jit TRACING, whose millions of
    python-call events exhaust the trace-viewer export's event cap
    (~1e6) before a single XLA op event lands — the ingested capture
    then reads ``unavailable:no-op-events``.  The XLA op events (the
    ones attribution prices) come from the host/runtime tracer, so
    ``python_tracer_level = 0`` keeps everything measured and drops
    only the python noise."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def start_profile(log_dir: Optional[str] = None) -> bool:
    """Begin a profiler capture into ``log_dir`` (default: the env
    knob's directory).  Returns False (and warns) instead of raising
    when capture can't start — a dead profiler must never kill a
    training run or a bench leg — including when the directory is
    stale or unwritable (:func:`profile_dir_unusable`).  This is the
    bare, print-only surface; :func:`profile_capture` is the EVENTED
    one (``profile_start``/``profile_stop``/``profile_skipped`` on the
    JSONL record)."""
    global _ACTIVE
    log_dir = log_dir or profile_dir()
    if log_dir is None:
        return False
    if _ACTIVE is not None:
        return False                       # one capture at a time
    reason = profile_dir_unusable(log_dir)
    if reason is not None:
        print(f"observability: profiler capture skipped: {log_dir} is "
              f"{reason}", file=sys.stderr)
        return False
    try:
        os.makedirs(log_dir, exist_ok=True)
        _start_trace_device_only(log_dir)
    except Exception as e:  # noqa: BLE001 — capture is best-effort
        print(f"observability: profiler capture failed to start: {e}",
              file=sys.stderr)
        return False
    _ACTIVE = log_dir
    return True


def stop_profile() -> Optional[str]:
    """End the active capture; returns its directory (None if none)."""
    global _ACTIVE
    if _ACTIVE is None:
        return None
    log_dir, _ACTIVE = _ACTIVE, None
    try:
        jax.profiler.stop_trace()
    except Exception as e:  # noqa: BLE001
        print(f"observability: profiler capture failed to stop: {e}",
              file=sys.stderr)
        return None
    return log_dir


def _emit_profile_event(registry, kind: str, **fields) -> None:
    """Emit one profile lifecycle event, best-effort: to the caller's
    registry, else the env-configured global one (so an armed-but-
    skipped capture is on the record even when the call site never
    wired telemetry).  Swallows sink/configure failures — the
    never-raises contract of :func:`profile_capture` must survive an
    unwritable ``APEX_TPU_TELEMETRY`` target too."""
    try:
        if registry is None:
            from apex_tpu.observability import configure_from_env
            registry = configure_from_env()
        registry.emit_event(kind, **fields)
    except Exception as e:  # noqa: BLE001 — telemetry is best-effort
        print(f"observability: profile event {kind!r} dropped: {e}",
              file=sys.stderr)


@contextlib.contextmanager
def profile_capture(tag: str = "capture", registry=None):
    """Capture the enclosed region when ``APEX_TPU_PROFILE_DIR`` is
    armed; a transparent no-op otherwise.  Emits ``profile_start`` /
    ``profile_stop`` events so the JSONL log records which captures
    exist and what they covered.

    Hardened (ISSUE 14 satellite): an armed directory that is
    unwritable or already holds a trace session degrades to a no-op
    with a ``profile_skipped`` event naming the reason — silently
    shadowing an old trace is how a capture gets misattributed to the
    wrong run.  Never raises either way."""
    log_dir = profile_dir()
    started = False
    if log_dir is not None:
        reason = profile_dir_unusable(log_dir)
        if reason is not None:
            print(f"observability: profiler capture skipped: "
                  f"{log_dir} is {reason}", file=sys.stderr)
            _emit_profile_event(registry, "profile_skipped",
                                dir=log_dir, tag=tag, reason=reason)
        else:
            started = start_profile(log_dir)
    if started:
        _emit_profile_event(registry, "profile_start", dir=log_dir,
                            tag=tag)
    try:
        yield started
    finally:
        if started:
            stop_profile()
            _emit_profile_event(registry, "profile_stop", dir=log_dir,
                                tag=tag)
