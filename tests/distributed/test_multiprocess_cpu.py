"""Two-process ``jax.distributed`` smoke test (slow lane).

Executable evidence for the multi-process story MIGRATION.md documents
: the recipe is one SPMD process per host plus
``jax.distributed.initialize(coordinator_address, num_processes,
process_id)`` — this test actually runs it, as two OS processes on the
CPU backend, and asserts the coordination service forms, the global
device view is consistent (``device_count == 2 x local``,
``process_index``/``process_count`` correct), and a payload round-trips
through the coordination-service KV store in both directions.

Cross-process collectives are not implemented by this image's CPU
backend (the worker pins the exact error so a jax upgrade that adds
them flips the marker to MULTIPROC-COLLECTIVES-OK); on TPU pods the
identical init path serves real collectives over ICI/DCN.
"""
import os
import socket
import subprocess
import sys

WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "_multiprocess_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def test_two_process_distributed_init_and_kv_exchange():
    nproc = 2
    port = _free_port()
    env = dict(os.environ)
    # each worker gets ONE cpu device: the 2x-local global view is then
    # unambiguous (2 devices total, one per process)
    env["XLA_FLAGS"] = " ".join(
        [f for f in env.get("XLA_FLAGS", "").split()
         if not f.startswith("--xla_force_host_platform_device_count")]
        + ["--xla_force_host_platform_device_count=1"])
    procs = [subprocess.Popen(
        [sys.executable, WORKER, str(rank), str(nproc), str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for rank in range(nproc)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, (
            f"worker {rank} rc={p.returncode}:\n{out[-2000:]}")
        assert f"MULTIPROC-OK {rank}" in out, out[-2000:]
        assert (f"MULTIPROC-COLLECTIVES-OK {rank}" in out
                or f"MULTIPROC-COLLECTIVES-UNSUPPORTED {rank}" in out), \
            out[-2000:]
