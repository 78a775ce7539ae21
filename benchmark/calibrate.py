#!/usr/bin/env python3
"""Readings that the limits and the load are set from — run on the chip by
hand, never by the benchmark's own runs.

    python3 benchmark/calibrate.py train  --workload W --seeds 1,2,3 [--control-seeds 1,2,3]
    python3 benchmark/calibrate.py serve  --workload W --seeds 1,2,3 --seconds 8 [--control-seeds ...]
    python3 benchmark/calibrate.py sweep  --workload W --rates 2,4,6,8 --seconds 12
    python3 benchmark/calibrate.py trace  --workload W --seconds 6 --out chiprun_out/x.json

``train``: per seed the program's numbers against the reference (the LOWER
readings), and for the control seeds the reference in the precision below
(fp8 operands) and with half of the batch left out (the UPPER readings).
``serve``: per seed one short window at the cell's own load, the program's
widest served-token gap, and for the control seeds the gap of the token the
fp8 reference puts first.  ``sweep``: one engine, one window per offered
rate and per seed of ``--seeds`` (the order of the traffic; a seed given
twice repeats its window) — the highest rate whose queue does not grow is
the knee.  ``trace``:
one traced window, its reduced trace written out to be read by hand.
Each prints one JSON line per reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from benchmark import harness as H                   # noqa: E402
from benchmark import reduce, traffic                # noqa: E402


def ints(text):
    return [int(x) for x in text.split(",") if x]


def say(**kw):
    print(json.dumps(kw), flush=True)


def cmd_train(args, cell, devices):
    from benchmark.drivers import train as D

    limits = cell.config["correct"]["limits"]
    for seed in ints(args.seeds):
        feed = traffic.TrainBatches(cell.mix, seed,
                                    cell.config["token_ids"])
        t0 = time.perf_counter()
        state, step, shapes = D.build(cell, seed)

        def one_step(state):
            batch = feed.next()
            state, loss = step(state, batch)
            return state, loss, batch

        state, got, batches = D.first_steps(cell, seed, state, shapes,
                                            one_step)
        scale = float(state.scaler.loss_scale)
        del state
        D.free_device(devices.platform)
        want = D.reference_readings(cell, shapes, seed, batches)
        say(kind="program", seed=seed, loss_scale=scale,
            seconds=time.perf_counter() - t0, losses=got["losses"],
            ref_losses=want["losses"],
            **{c["name"]: c["value"]
               for c in D.compare(got, want, limits)})
        if seed in ints(args.control_seeds):
            for kind, kw in (("control_fp8", {"quant": "fp8"}),
                             ("fault_half_batch",
                              {"rows": cell.mix["batch"] // 2})):
                low = D.reference_readings(cell, shapes, seed, batches,
                                           **kw)
                say(kind=kind, seed=seed,
                    **{c["name"]: c["value"]
                       for c in D.compare(low, want, limits)})
            D.free_device(devices.platform)


def cmd_serve(args, cell, devices):
    from benchmark.drivers import serve as D

    for seed in ints(args.seeds):
        t0 = time.perf_counter()
        requests = traffic.serve_requests(cell.mix, seed, args.seconds,
                                          cell.config["token_ids"])
        engine, sched, shapes = D.build(cell, seed)
        D.warm_up(sched, cell, traffic.rng_for(seed, stream=2))
        out = D.measure(cell, sched, requests, args.seconds)
        peak = devices.memory_peak_bytes()
        del engine, sched
        D.free_device(devices.platform)
        seqs = D.sample_sequences(cell, seed, requests, out["by_uid"],
                                  out["served"])
        gap = D.served_token_gap(cell, shapes, seed, seqs)
        say(kind="program", seed=seed, gap=gap["widest"],
            tokens=gap["tokens"], requests=len(out["by_uid"]),
            unfinished=sum(r["reason"] != "length"
                           for r in out["by_uid"].values()),
            peak_gib=(peak or 0) / 2 ** 30,
            seconds=time.perf_counter() - t0)
        if seed in ints(args.control_seeds):
            low = D.served_token_gap(cell, shapes, seed, seqs, quant="fp8")
            say(kind="control_fp8", seed=seed, gap=low["widest"],
                tokens=low["tokens"])
        D.free_device(devices.platform)


def cmd_sweep(args, cell, devices):
    from benchmark.drivers import serve as D

    seed = ints(args.seeds)[0]
    engine, sched, _ = D.build(cell, seed)
    D.warm_up(sched, cell, traffic.rng_for(seed, stream=2))
    for rate in [float(x) for x in args.rates.split(",")]:
        cell.mix["arrivals"] = {"kind": "poisson", "rate_per_s": rate}
        cell.mix["drain_seconds"] = 20.0
        for order in ints(args.seeds):     # the traffic's order; one engine
            requests = traffic.serve_requests(
                cell.mix, order, args.seconds, cell.config["token_ids"])
            facts = D.measure(cell, sched, requests, args.seconds)["facts"]
            lo, hi = facts["window"]
            late = [r for r in facts["requests"] if r["due"] > hi - 3.0]
            ttft = reduce.serve_ttft_ms(facts)
            say(kind="sweep", rate=rate, order=order,
                requests=len(facts["requests"]),
                tokens_per_s=reduce.serve_tokens_per_s(facts),
                ttft_p50_ms=reduce.percentile(ttft, 50),
                ttft_p95_ms=reduce.percentile(ttft, 95),
                ttft_last3s_p50_ms=reduce.percentile(reduce.serve_ttft_ms(
                    dict(facts, requests=late)), 50),
                queue_wait_p95_ms=reduce.percentile(
                    reduce.queue_wait_ms(facts), 95),
                gap_p95_ms=reduce.percentile(reduce.serve_gaps_ms(facts), 95),
                decode_ms_p50=reduce.decode_step_ms_p50(facts),
                drain_s=facts["drained"] - hi,
                occupancy=reduce.slot_occupancy(facts),
                pool_live_peak=reduce.pool_pages(facts, 0, max),
                pool_attended_peak=reduce.pool_pages(facts, 1, max),
                peak_gib=(devices.memory_peak_bytes() or 0) / 2 ** 30)


def cmd_trace(args, cell, devices):
    driver = H.load_driver(cell)
    profiler = H.ProfilerWindow(cell.root)
    out = driver.run(cell=cell, devices=devices, seed=ints(args.seeds)[0],
                     seconds=args.seconds, profiler=profiler,
                     t_process=time.perf_counter())
    trace = profiler.load()
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(summary(trace, out["facts"]), f, indent=1)
    say(kind="trace", out=args.out, checks=out["checks"],
        setup_s=out["setup_s"],
        ops={k: len(v) for k, v in trace.ops.items()},
        host=len(trace.host))


def summary(trace, facts) -> dict:
    """A trace small enough to read by hand: per line the names that took
    most time (with counts), every program, every host span."""
    from benchmark import trace as T

    def table(events, n):
        acc = {}
        for e in events:
            t = acc.setdefault(e.name, [0.0, 0])
            t[0] += e.seconds
            t[1] += 1
        rows = sorted(acc.items(), key=lambda kv: -kv[1][0])[:n]
        return [[k, round(v[0], 6), v[1]] for k, v in rows]

    win = T.window(trace)
    return {
        "window_s": (win[1] - win[0]) * 1e-9 if win else None,
        "busy_s": T.busy_seconds(trace),
        "traced_steps": facts.get("traced_steps"),
        "ops": {k: table(v, 70) for k, v in trace.ops.items()},
        "modules": {k: table(v, 40) for k, v in trace.modules.items()},
        "host": table(trace.host, 40),
        "idle_gaps": T.idle_gaps_by_host_span(trace),
        "first_events": {k: [[e.name, e.start, e.end] for e in v[:400]]
                         for k, v in trace.ops.items()},
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("command", choices=("train", "serve", "sweep", "trace"))
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="1")
    p.add_argument("--control-seeds", default="")
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--rates", default="")
    p.add_argument("--out", default="chiprun_out/trace.json")
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args(argv)
    cell = H.load_cell(args.workload, H.ROOT)
    devices = H.find_devices(cell.chips, args.rehearse)
    if devices.platform == "tpu":
        H.enable_compile_cache(H.ROOT)
    {"train": cmd_train, "serve": cmd_serve, "sweep": cmd_sweep,
     "trace": cmd_trace}[args.command](args, cell, devices)
    return 0


if __name__ == "__main__":
    sys.exit(main())
