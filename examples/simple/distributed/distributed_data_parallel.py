"""Minimal data-parallel training over a device mesh (reference:
``examples/simple/distributed/distributed_data_parallel.py`` — the
smallest end-to-end DDP example: wrap the model, train, verify ranks
agree).

Mesh-native translation of the reference's ``torch.distributed.launch``
two-process recipe: ONE process, a 1-D ``data`` mesh over all local
devices, the per-device batch sharded by ``shard_map``, gradients averaged
by ``DistributedDataParallel.reduce_gradients`` (bucketed psum), and a
SyncBatchNorm layer whose batch statistics are computed over the GLOBAL
batch via the same mesh axis.

Run (any machine — 8 virtual devices on CPU):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python distributed_data_parallel.py
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel import DistributedDataParallel, SyncBatchNorm

STEPS, LR, BATCH_PER_RANK, DIM, CLASSES = 20, 0.05, 8, 16, 4


def main():
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("data",))
    ndev = len(devices)
    print(f"mesh: {ndev} x {devices[0].device_kind}")

    bn = SyncBatchNorm(num_features=DIM)   # psum-Welford stats over "data"
    rng = np.random.RandomState(0)
    w = jnp.asarray(rng.randn(DIM, CLASSES) * 0.1, jnp.float32)
    bn_vars = bn.init(jax.random.key(0),
                      jnp.zeros((BATCH_PER_RANK, DIM)))
    params = {"w": w, "bn": bn_vars["params"]}
    ddp = DistributedDataParallel()

    # learnable synthetic task: label is recoverable from the features
    y = rng.randint(0, CLASSES, size=BATCH_PER_RANK * ndev)
    x = rng.randn(BATCH_PER_RANK * ndev, DIM).astype(np.float32) * 0.5
    x[np.arange(x.shape[0]), y % DIM] += 2.0
    x, y = jnp.asarray(x), jnp.asarray(y)

    def loss_fn(params, batch_stats, x, y):
        h, _ = bn.apply({"params": params["bn"],
                         "batch_stats": batch_stats},
                        x, mutable=["batch_stats"])
        logits = h @ params["w"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1))

    @functools.partial(jax.jit, donate_argnums=(0,))
    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(), P(), P("data"), P("data")),
        out_specs=(P(), P()), check_vma=False)
    def train_step(params, batch_stats, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch_stats,
                                                  x, y)
        grads = ddp.reduce_gradients(grads)   # psum-mean over "data"
        params = jax.tree.map(lambda p, g: p - LR * g, params, grads)
        return jax.lax.pmean(loss, "data"), params

    losses = []
    batch_stats = bn_vars["batch_stats"]
    for step in range(STEPS):
        loss, params = train_step(params, batch_stats, x, y)
        losses.append(float(loss))
        if step % 5 == 0:
            print(f"step {step:3d} loss {losses[-1]:.4f}")
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    from apex_tpu.utils.compile_cache import \
        enable_persistent_compile_cache
    enable_persistent_compile_cache()
    main()
