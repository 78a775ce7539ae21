"""What every cell shares: finding its files by name, the device check, the
peaks table, the profiler window, metric readers found by file name, and the
result line.  Nothing here knows the name of a cell, a configuration, a
traffic mix or a metric: they are data, found through ``BENCHMARK.json``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

from . import trace as trace_mod

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

#: host spans kept from a trace: the benchmark's own and the program's
HOST_SPAN_PREFIXES = ("bench.", "apex_tpu.")


class Refused(Exception):
    """The run cannot measure: no result line, a non-zero exit."""


@dataclasses.dataclass
class Cell:
    root: Path                # the checkout the cell's files came from
    name: str
    chips: int
    config: dict
    mix: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list


def _load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    """Does ``metric`` belong to ``cell``?  By its ``workloads`` list; an
    end-to-end metric without one (``setup_s``) belongs to every cell."""
    return cell in metric.get("workloads", [cell])


def _adopt(root: Path) -> None:
    """Let this process's ``benchmark`` packages find the modules that a
    checkout other than ours has added (a toy checkout rehearsed in
    process; the one command's checkout is its own)."""
    for folder in (root / "benchmark").iterdir():
        if (folder / "__init__.py").is_file():
            pkg = importlib.import_module(f"benchmark.{folder.name}")
            if str(folder) not in pkg.__path__:
                pkg.__path__.append(str(folder))


def load_cell(name: str, root: Path) -> Cell:
    if root != ROOT:
        _adopt(root)
    index = _load(root / "BENCHMARK.json")
    entry = next((w for w in index["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refused(f"no workload {name!r} in BENCHMARK.json; it has "
                      + ", ".join(w["name"] for w in index["workloads"]))
    cfg_entry = next(c for c in index["configs"]
                     if c["name"] == entry["config"])
    e2e = [m for m in index["end_to_end"] if _reports(m, name)]
    layer = [m for m in index["per_layer"]
             if name in m["workloads"]]     # a per-layer metric names its cells
    return Cell(root=root, name=name, chips=int(entry["chips"]),
                config=_load(root / cfg_entry["file"]),
                mix=_load(root / "benchmark" / "traffic"
                          / f"{entry['traffic']}.json"),
                end_to_end=e2e, per_layer=layer)


def peaks_for(device_kind: str) -> dict:
    table = _load(HERE / "peaks.json")["by_device_kind"]
    if device_kind not in table:
        raise Refused(
            f"device kind {device_kind!r} is not in benchmark/peaks.json "
            f"({', '.join(table)}); add its published peaks with their "
            f"source — there is no default")
    return table[device_kind]


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache`` (a
    fixed path: it is part of the cache's key), unless the environment
    already placed it."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(root / ".jax_cache"))
    # keep every program, not only those that took a second to build
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@dataclasses.dataclass
class Devices:
    platform: str
    kind: str
    used: list                 # the jax devices the cell runs on
    peaks: Optional[dict]      # None in a rehearsal off the chip

    def memory_peak_bytes(self) -> Optional[int]:
        stats = [d.memory_stats() for d in self.used]
        if any(s is None or "peak_bytes_in_use" not in s for s in stats):
            return None
        return max(int(s["peak_bytes_in_use"]) for s in stats)


def find_devices(chips: int, rehearse: bool) -> Devices:
    import jax
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if platform != "tpu" and not rehearse:
        raise Refused(f"JAX found platform {platform!r}, not a TPU: a "
                      f"measurement needs the chip (--rehearse drives the "
                      f"path on the CPU and prints no device metric)")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips; JAX found "
                      f"{len(devs)}")
    peaks = peaks_for(kind) if platform == "tpu" else None
    return Devices(platform, kind, devs[:chips], peaks)


class ProfilerWindow:
    """The traced part of a ``--trace 1`` run: ``start()`` once the cell's
    driver says so, ``stop()`` after the window; ``load()`` reduces it."""

    def __init__(self, root: Path):
        self.dir = root / ".bench_trace"
        self.started = self.stopped = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.started = time.perf_counter()

    def stop(self) -> None:
        import jax
        self.stopped = time.perf_counter()
        jax.profiler.stop_trace()

    def load(self) -> trace_mod.Trace:
        try:
            return trace_mod.load_xplane(
                trace_mod.find_xplane(str(self.dir)), HOST_SPAN_PREFIXES)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@contextlib.contextmanager
def span(name: str, **kw):
    """A host span in the profiler's own trace (free when none is on)."""
    import jax
    with jax.profiler.TraceAnnotation("bench." + name, **kw):
        yield


@dataclasses.dataclass
class Run:
    """What a metric reader is handed."""
    cell: Cell
    devices: Devices
    facts: dict                       # the driver's timestamps and counters
    trace: Optional[trace_mod.Trace]  # None with --trace 0
    setup_s: float

    @property
    def peaks(self) -> dict:
        return self.devices.peaks


def read_metric(name: str, run: Run):
    """``benchmark/metrics/<name>.py :: read(run)`` -> number or None."""
    path = run.cell.root / "benchmark" / "metrics" / f"{name}.py"
    if not path.exists():
        raise Refused(f"metric {name!r} has no reader at {path}")
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics." + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def _load_named(package: str, name: str, root: Path):
    """``benchmark/<package>/<name>.py`` of the checkout ``root``, imported
    as ``benchmark.<package>.<name>``.  A name with no file is ``Refused``
    with the names that have one: nothing stands in for it."""
    folder = root / "benchmark" / package
    if not (folder / f"{name}.py").is_file():
        have = sorted(f.stem for f in folder.glob("*.py")
                      if f.stem != "__init__")
        raise Refused(f"no benchmark/{package}/{name}.py in {root}; "
                      f"there is {', '.join(have) or 'none'}")
    return importlib.import_module(f"benchmark.{package}.{name}")


def load_driver(cell: Cell):
    """The driver the cell's mix names: ``drivers/<driver>.py :: run``."""
    return _load_named("drivers", cell.mix["driver"], cell.root)


def load_binding(cell: Cell):
    """The model binding the cell's configuration names under ``binding``
    (``bindings/<name>.py``).  There is no default: a configuration that
    names none is ``Refused``."""
    name = cell.config.get("binding")
    if not name:
        raise Refused(f"the configuration of {cell.name} names no "
                      f"\"binding\" (a file under benchmark/bindings/)")
    return _load_named("bindings", name, cell.root)


def check_line(checks: list) -> str:
    """``name=value<=limit`` for every number compared, for standard error."""
    return " ".join(f"{c['name']}={c['value']:.6g}<={c['limit']:.6g}"
                    + ("" if c["value"] <= c["limit"] else "(FAIL)")
                    for c in checks)


_BOOK = None        # the process's one CompileBook, once run.py made it


class CompileBook:
    """XLA compile requests, persistent-cache hits and misses, and the
    seconds spent compiling or loading, from ``jax.monitoring``."""

    def __init__(self):
        import jax
        self.requests = self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._secs)
        global _BOOK
        _BOOK = self

    def _event(self, name, **_):
        if name.endswith("/compile_requests_use_cache"):
            self.requests += 1
        elif name.endswith("/cache_hits"):
            self.hits += 1
        elif name.endswith("/cache_misses"):
            self.misses += 1

    def _secs(self, name, secs, **_):
        if name.endswith("/backend_compile_duration"):
            self.seconds += secs

    def __str__(self):
        return (f"compile requests {self.requests}, cache hits "
                f"{self.hits}, misses {self.misses}, compile-or-load "
                f"{self.seconds:.1f} s")


def note(t_process: float, what: str) -> None:
    """A set-up milestone on standard error, with the seconds so far."""
    book = f" ({_BOOK})" if _BOOK is not None else ""
    print(f"[bench {time.perf_counter() - t_process:7.1f}s] {what}{book}",
          file=sys.stderr, flush=True)


def emit(result: dict) -> None:
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
