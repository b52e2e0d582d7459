"""GPipe-style pipeline parallelism via shard_map + collective_permute.

For clusters whose ICI topology favours a ring over wide TP, the layer
stack is split into `n_stages` contiguous groups laid out along a mesh
axis; microbatches stream through with collective_permute between stages.
This is an optional alternative to the default DP x TP layout (DESIGN §6)
— exercised by tests and the `examples/pipeline_train.py` scenario, not by
the dry-run baselines.

Bubble fraction = (n_stages - 1) / (n_micro + n_stages - 1); the schedule
below is the standard fill-drain loop (1F1B left as future work).
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(mesh: Mesh, stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, x_micro: jax.Array,
                   axis: str = "model") -> jax.Array:
    """Run microbatched inputs through pipeline stages on mesh axis `axis`.

    stage_params: pytree whose leaves have leading dim n_stages (sharded
    over `axis`); x_micro: (n_micro, mb, ...) microbatched activations.
    Each device holds its stage's params; activations rotate by
    collective_permute. Returns outputs in microbatch layout.
    """
    n_stages = mesh.shape[axis]

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(axis), P(None)), out_specs=P(None),
             check_vma=False)
    def run(params_stage, xs):
        params = jax.tree.map(lambda t: t[0], params_stage)  # my stage
        stage = jax.lax.axis_index(axis)
        n_micro = xs.shape[0]
        total = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            buf, outs = carry
            # feed stage 0 with microbatch t (when in range)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            feed = xs[mb_idx]
            cur = jnp.where(stage == 0, feed, buf)
            y = stage_fn(params, cur)
            # last stage emits microbatch t - (n_stages - 1)
            out_idx = t - (n_stages - 1)
            emit = jnp.logical_and(stage == n_stages - 1, out_idx >= 0)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(emit, y, outs[jnp.clip(out_idx, 0, n_micro - 1)]),
                jnp.clip(out_idx, 0, n_micro - 1), 0)
            nxt = jax.lax.ppermute(y, axis, perm)
            return (nxt, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(tick, (buf0, outs0),
                                    jnp.arange(total))
        # only the last stage's outs are real; broadcast via masked psum
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    return run(stage_params, x_micro)
