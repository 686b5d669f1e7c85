"""Pipeline parallelism: GPipe-style microbatch streaming over a 'pipe' axis.

Each device (or device group) holds one *stage* -- a contiguous slice of the
layer stack -- and activations stream stage-to-stage with
``lax.ppermute`` (a neighbour collective, the cheapest in the ICI mesh).
The schedule is the classic GPipe fill-drain: with S stages and M
microbatches the bubble fraction is (S-1)/(M+S-1).

This composes with the paper's two-tier idea: stages are the *fast* tier
(neighbour permutes every step), the optimizer's cross-pod sync stays on the
slow tier. It is exposed as an optional wrapper (the 40-cell dry-run uses
DP/TP/EP/SP; PP has its own tests and can be enabled per config).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["pipeline_apply"]


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,        # pytree, leaves [S, ...] (stage-stacked)
    microbatches: jax.Array,  # [M, mb, ...] inputs (logically on stage 0)
    mesh: Mesh,
    axis: str = "pipe",
) -> jax.Array:
    """Run ``y_m = stages_{S-1} ∘ ... ∘ stages_0 (x_m)`` for every microbatch.

    Returns [M, mb, ...] outputs (logically on the last stage). Correctness
    contract: identical to applying the stages sequentially (tested in an
    8-device subprocess against the unsharded reference).
    """
    n_stages = mesh.shape[axis]
    m = microbatches.shape[0]
    steps = m + n_stages - 1

    def run(params_local, mb_local):
        # params_local: leaves [1, ...] (this stage); mb_local: [M, mb, ...]
        # on every device (replicated input; stage 0 is the consumer).
        params_me = jax.tree.map(lambda x: x[0], params_local)
        idx = jax.lax.axis_index(axis)
        buf = jnp.zeros_like(mb_local[0])
        out = jnp.zeros_like(mb_local)

        def step(carry, t):
            buf, out = carry
            # stage 0 ingests microbatch t (if any) -- others keep their buf
            feed = jax.lax.dynamic_index_in_dim(
                mb_local, jnp.clip(t, 0, m - 1), keepdims=False)
            x = jnp.where((idx == 0) & (t < m), feed, buf)
            y = stage_fn(params_me, x)
            # last stage stores its result at position t - (S-1)
            slot = jnp.clip(t - (n_stages - 1), 0, m - 1)
            store = (idx == n_stages - 1) & (t >= n_stages - 1)
            out = jax.lax.cond(
                store,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, slot, axis=0),
                lambda o: o,
                out,
            )
            # shift activations to the next stage (neighbour permute)
            buf = jax.lax.ppermute(
                y, axis, [(i, i + 1) for i in range(n_stages - 1)])
            return (buf, out), None

        (buf, out), _ = jax.lax.scan(step, (buf, out),
                                     jnp.arange(steps, dtype=jnp.int32))
        # replicate the collected outputs from the last stage to all devices
        # (ppermute is a strict permutation; broadcast = psum of a mask).
        out = jax.lax.psum(
            jnp.where(idx == n_stages - 1, out, jnp.zeros_like(out)), axis
        )
        return out

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        run, mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, microbatches)
