#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and last ``checks``: every number compared beside its limit).
Without a TPU, or with fewer chips than the cell asks for, the run exits
non-zero and prints no result.  ``--rehearse`` (the benchmark's own flag,
for the CPU tests and for trying a path before spending chip time) drives
the same code on whatever platform JAX has and prints only what the
program counts (``program_counter`` metrics): times, rates and shares come
only from the chip.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()      # set-up is counted from here

import argparse                      # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))                     # the checkout's root

from benchmark import harness as H   # noqa: E402
from benchmark import trace as trace_mod  # noqa: E402


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="drive the path off the chip; prints counts only")
    return p.parse_args(argv)


def run_cell(args, t_process: float, root=H.ROOT) -> dict:
    cell = H.load_cell(args.workload, root)
    if not (root / "apex_tpu").is_dir():
        raise H.Refused(f"the program (apex_tpu/) is not in the checkout "
                        f"{root}")
    devices = H.find_devices(cell.chips, args.rehearse)
    if devices.platform == "tpu":
        H.enable_compile_cache(root)
    H.CompileBook()                  # its counts ride on every note
    driver = H.load_driver(cell)
    profiler = H.ProfilerWindow(root) if args.trace else None
    out = driver.run(cell=cell, devices=devices, seed=args.seed,
                     seconds=args.seconds, profiler=profiler,
                     t_process=t_process)
    H.note(t_process, "window and checks done")
    trace = profiler.load() if profiler is not None else None
    run = H.Run(cell=cell, devices=devices, facts=out["facts"],
                trace=trace, setup_s=out["setup_s"])
    wanted = cell.per_layer if args.trace else cell.end_to_end
    if devices.platform != "tpu":
        # off the chip only what the program COUNTS may be printed: a
        # time, a rate or a share comes from the chip alone
        wanted = [m for m in wanted if m["source"] == "program_counter"]
    metrics = {}
    for m in wanted:
        value = H.read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices.platform, "kind": devices.kind,
              "count": len(devices.used),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": bool(out["correct"]),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    if trace is not None and devices.platform == "tpu":
        busy = trace_mod.busy_seconds(trace)
        win = trace_mod.window(trace)
        device["busy_s"] = sum(busy.values()) / max(1, len(busy))
        device["window_s"] = (win[1] - win[0]) * 1e-9 if win else 0.0
        result["breakdown"] = {
            "device_ops": trace_mod.top_ops(trace),
            "idle_gaps": trace_mod.idle_gaps_by_host_span(trace)}
    if args.rehearse:
        result["rehearsal"] = True
    result["checks"] = out["checks"]
    print("checks: " + H.check_line(out["checks"]), file=sys.stderr,
          flush=True)
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        result = run_cell(args, T_PROCESS if argv is None
                          else time.perf_counter())
    except H.Refused as e:
        print(f"benchmark: refused: {e}", file=sys.stderr, flush=True)
        return 2
    H.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
