"""Fleet front-door demo (ISSUE 19): three replicas, one submit().

Builds N tiny paged GPT engines with the host tier armed, wires them
under one :class:`~apex_tpu.fleet.FleetRouter`, and serves a skewed
tenant mix (each tenant re-sends its own long shared prefix with fresh
tails) through BOTH routing arms at equal aggregate HBM:

* ``round_robin`` stripes blindly, so every replica re-prefills every
  tenant's prefix into its own pool — duplicated pages, cold tails;
* ``prefix_affinity`` probes each replica's ACTUAL prefix tree
  (read-only ``peek_match`` + the swap-aware admission cost) and sends
  each tenant home, spilling off deep queues so affinity never starves
  a replica.

Prints per-arm hit rates, mean TTFT, the per-replica routing split,
and the three-level conservation law, then prices the fleet with the
capacity simulator (measured capture profile when one exists —
``unavailable:`` provenance is printed, never fabricated).

Runs anywhere::

    JAX_PLATFORMS=cpu python examples/fleet_serve.py
"""
from __future__ import annotations

import argparse
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))                # repo root on sys.path

from apex_tpu.fleet import (CAPACITY_DRIFT_TOLERANCE, build_fleet,
                            profile_from_captures, required_replicas)
from apex_tpu.inference import InferenceEngine
from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.testing import GPTConfig, gpt_model_provider


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="apex_tpu fleet demo")
    p.add_argument("--replicas", type=int, default=3)
    p.add_argument("--waves", type=int, default=6)
    p.add_argument("--max-new-tokens", type=int, default=4)
    p.add_argument("--slo-ttft-us", type=float, default=20000.0,
                   help="TTFT p99 target the capacity sim prices")
    return p.parse_args(argv)


def build_engines(n):
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(1)
    cfg = GPTConfig(vocab_size=64, hidden_size=64, num_layers=1,
                    num_attention_heads=2, max_seq_length=64,
                    hidden_dropout=0.0, attention_dropout=0.0)
    model = gpt_model_provider(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))
    return [InferenceEngine("gpt", cfg, params, slots=2, max_seq=64,
                            page_size=8, num_pages=16,
                            host_tier_bytes=1 << 20)
            for _ in range(n)]


def serve_arm(policy, engines, prefixes, args):
    """One routing arm over FRESH schedulers (shared warm engines)."""
    fleet = build_fleet(engines, policy=policy)
    n_tenants = len(prefixes)
    for w in range(args.waves):
        for j in range(n_tenants):
            t = (w + j) % n_tenants           # rotate submission order
            prompt = prefixes[t] + [(w * 7 + t) % 64,
                                    (w * 11 + t + 1) % 64]
            fleet.submit(prompt, max_new_tokens=args.max_new_tokens,
                         tenant=f"tenant{t}")
        fleet.run()
    law = fleet.conservation()
    hits = sum(int(r.telemetry.prefix_hits.total())
               for r in fleet.replicas)
    served = sum(c["finished"] for c in law["replicas"])
    ttft_sum = sum(float(r.telemetry.ttft.sum())
                   for r in fleet.replicas) * 1e6
    ttft_n = sum(int(r.telemetry.ttft.count())
                 for r in fleet.replicas)
    split = [int(fleet.telemetry.routed.value(replica=str(i)) or 0)
             for i in range(len(engines))]
    return {"policy": policy, "hit_rate": hits / max(1, served),
            "ttft_us": ttft_sum / max(1, ttft_n), "split": split,
            "spills": int(fleet.telemetry.affinity_spills.total()),
            "holds": law["holds"]}


def main(argv=None):
    args = parse_args(argv)
    engines = build_engines(args.replicas)
    # one shared prefix per tenant, one more tenant than replicas so
    # the mix never tiles evenly (the skew affinity has to chase)
    prefixes = [
        [int(t) for t in (np.arange(16, dtype=np.int64) * (j + 3) + j)
         % 64]
        for j in range(args.replicas + 1)]

    # warm every program both arms dispatch (cold bucket, decode,
    # suffix chunk) so the first arm is not billed for the compiles
    from apex_tpu.inference import SlotScheduler
    for eng in engines:
        warm = SlotScheduler(eng)
        for tail in ((63, 62), (61, 60)):
            warm.submit(prefixes[0] + list(tail),
                        max_new_tokens=args.max_new_tokens)
            warm.run()

    print(f"{args.replicas} replicas x 2 slots, "
          f"{len(prefixes)} tenants, {args.waves} waves")
    for policy in ("round_robin", "prefix_affinity"):
        arm = serve_arm(policy, engines, prefixes, args)
        print(f"  {arm['policy']:16s} hit_rate={arm['hit_rate']:.3f} "
              f"ttft={arm['ttft_us']:8.0f}us "
              f"split={arm['split']} spills={arm['spills']} "
              f"conservation={'ok' if arm['holds'] else 'BROKEN'}")

    prof = profile_from_captures()
    req = required_replicas(
        prof, slots=2, slo_ttft_us=args.slo_ttft_us, n_requests=128,
        interarrival_us=1000.0, prompt_tokens=64, decode_tokens=4,
        seed=19)
    print(f"capacity sim ({req['provenance']}, drift tolerance "
          f"{CAPACITY_DRIFT_TOLERANCE}x): "
          f"replicas for TTFT p99 <= {args.slo_ttft_us:.0f}us -> "
          f"{req['replicas']}")


if __name__ == "__main__":
    from apex_tpu.utils.compile_cache import \
        enable_persistent_compile_cache
    enable_persistent_compile_cache()
    main()
