"""Minimal expert-parallel MoE training over a device mesh.

Beyond reference parity (the reference has no MoE — SURVEY.md §2.4);
this is the EP sibling of
``examples/simple/distributed/distributed_data_parallel.py``: the
smallest end-to-end recipe showing the pieces a Megatron MoE user needs —

* ``initialize_model_parallel(expert_model_parallel_size_=...)`` carving
  the ``expert`` axis out of data parallelism,
* :class:`~apex_tpu.transformer.moe.MoELayer` dispatching tokens through
  an ``all_to_all`` over that axis,
* the SPLIT gradient reduction: dense params (router + head) average
  over ``("data", "expert")`` while each expert shard averages over
  ``data`` only — ``reduce_moe_grads`` does both,
* the router's load-balancing aux loss keeping experts alive.

Run (any machine — 8 virtual devices on CPU):

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python expert_parallel_moe.py
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from apex_tpu.transformer import parallel_state
from apex_tpu.transformer.moe import MoELayer, reduce_moe_grads

STEPS, LR = 80, 0.1
TOKENS_PER_RANK, HIDDEN, FFN, EXPERTS, TOP_K = 16, 16, 32, 4, 2
AUX_COEFF = 0.01


def main(expert_parallel_size: int = 2):
    parallel_state.destroy_model_parallel()
    parallel_state.initialize_model_parallel(
        expert_model_parallel_size_=expert_parallel_size)
    # the ep>1 parallel_state is this example's, not the process's:
    # leaving it initialized (even on a failure partway through) makes
    # every later axis_name=None reduction resolve to ('data', 'expert')
    # and fail in callers running their own mesh
    try:
        return _train(expert_parallel_size)
    finally:
        parallel_state.destroy_model_parallel()


def _train(expert_parallel_size):
    mesh = parallel_state.get_mesh()
    ep = expert_parallel_size
    dp = mesh.shape["data"]
    print(f"mesh: data={dp} x expert={ep} "
          f"({mesh.devices.size} x {mesh.devices.flat[0].device_kind})")

    moe = MoELayer(num_experts=EXPERTS, hidden_size=HIDDEN,
                   ffn_hidden_size=FFN, top_k=TOP_K,
                   expert_parallel_size=ep)

    # learnable synthetic task: the target is a fixed rotation of the
    # input, recoverable only if tokens actually reach working experts
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(dp * ep * TOKENS_PER_RANK, HIDDEN),
                    jnp.float32)
    rot = jnp.asarray(np.linalg.qr(rng.randn(HIDDEN, HIDDEN))[0],
                      jnp.float32)
    y = x @ rot

    def loss_fn(params, x, y):
        out, aux = moe.apply(params, x)
        mse = jnp.mean((out - y) ** 2)
        return mse + AUX_COEFF * aux["load_balancing_loss"], mse

    # Param placement: expert shards live distributed along the 'expert'
    # axis (dim 0 of each [E_local, ...] leaf stacks to the global E);
    # the router is replicated.  The spec tree expresses exactly that.
    import jax.tree_util as jtu

    struct = jax.eval_shape(
        # same layer config with ep=1: identical tree STRUCTURE, and an
        # ep>1 init would need axis_index (shard_map-only)
        lambda: moe.clone(expert_parallel_size=1).init(
            jax.random.key(0), jnp.zeros((4, HIDDEN), jnp.float32)))
    param_specs = jtu.tree_map_with_path(
        lambda path, _: P("expert") if any(
            isinstance(p, jtu.DictKey) and p.key == "experts"
            for p in path) else P(),
        struct)

    @functools.partial(jax.jit, donate_argnums=(0,))
    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(param_specs, P(("data", "expert")),
                  P(("data", "expert"))),
        out_specs=(P(), param_specs), check_vma=False)
    def train_step(params, x, y):
        (_, mse), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, x, y)
        # router averages over (data, expert); expert shards over data
        grads = reduce_moe_grads(grads)
        params = jax.tree.map(lambda p, g: p - LR * g, params, grads)
        return jax.lax.pmean(mse, ("data", "expert")), params

    @jax.jit
    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(("data", "expert")),),
        out_specs=param_specs, check_vma=False)
    def init_params(x):
        return moe.init(jax.random.key(0), x)

    params = init_params(x)
    losses = []
    for step in range(STEPS):
        loss, params = train_step(params, x, y)
        losses.append(float(loss))
        if step % 10 == 0:
            print(f"step {step:3d} mse {losses[-1]:.4f}")
    print(f"final mse {losses[-1]:.4f} (first {losses[0]:.4f})")
    return losses


if __name__ == "__main__":
    from apex_tpu.utils.compile_cache import \
        enable_persistent_compile_cache
    enable_persistent_compile_cache()
    main()
