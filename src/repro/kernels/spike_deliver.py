"""Pallas TPU kernel: tiled gather-matvec spike delivery.

The paper's *deliver* phase dominates state propagation (§3, Discussion) and
its irregular memory access is the subject of the §2.3 cache model. NEST walks
per-synapse pointer chains; the TPU-native rethink is dense and delay-resolved:

* connectivity is rectangular ``src/w/delay [N, K]`` (fixed in-degree),
* the source gather ``w * spk[src]`` runs in XLA (Mosaic lowers only 2-D
  gathers, and a ``[TILE_N, K]`` index into a 1-D spike vector is not one),
  so the kernel receives the per-synapse values ``vals [N, K]``,
* a grid over target tiles keeps each ``[TILE_N, K]`` block of values and
  delays in VMEM,
* for each delay slot ``j`` in the compile-time window ``[steps_lo,
  steps_lo + r_span)`` the kernel reduces ``vals * [delay == j]`` over K in
  one VPU pass, emitting ``contrib[TILE_N, r_span]``.

The engine then rolls ``contrib`` into the ring buffer at
``slot = (t + steps_lo + j) % R``. The separation of *intra* and *inter*
tables (paper §4.1.2) shows up here as two kernel invocations with different
``(src, w, delay)`` sets and different spike sources (the subgroup-gathered
area vector vs. the globally gathered [D, N] block), each with its own narrow
delay window -- which is what keeps ``r_span`` (and the wasted compare work)
small per pathway.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["spike_deliver_pallas", "delay_resolved_contrib", "TILE_N"]

TILE_N = 128  # target-neuron rows per grid step; [TILE_N, K] stays in VMEM


def delay_resolved_contrib(vals, j, r_span: int):
    """Reduce synapse values over K once per slot of the delay window.

    ``vals [N, K]`` are the per-synapse contributions (w * spike), ``j [N, K]``
    the slot offsets in ``[0, r_span)``. One reduction over K per slot;
    ``r_span`` is a small compile-time constant (per-pathway delay width), so
    this unrolls into r_span masked row-sums -- no MXU, pure VPU. The row
    sums stay ``[N, 1]`` and are concatenated along lanes: a stack of 1-D
    sums needs a relayout Mosaic does not do. Shared by this kernel and the
    fused superstep kernel (:mod:`repro.kernels.cycle`).
    """
    return jnp.concatenate(
        [jnp.sum(jnp.where(j == r, vals, 0.0), axis=1, keepdims=True)
         for r in range(r_span)], axis=1)


def _kernel(vals_ref, d_ref, out_ref, *, steps_lo: int, r_span: int):
    j = d_ref[...] - steps_lo     # slot offsets in [0, r_span)
    out_ref[...] = delay_resolved_contrib(vals_ref[...], j, r_span)


@functools.partial(
    jax.jit, static_argnames=("steps_lo", "r_span", "tile_n", "interpret")
)
def spike_deliver_pallas(
    vals: jax.Array,    # [N, K] f32 per-synapse values w * spk[src]
    delay: jax.Array,   # [N, K] int32
    *,
    steps_lo: int,
    r_span: int,
    tile_n: int = TILE_N,
    interpret: bool = True,
) -> jax.Array:
    """Delay-resolved delivery contributions ``[N, r_span]``.

    N must be a multiple of ``tile_n`` (use ops.spike_deliver, which also
    does the source gather). Semantics match
    :func:`repro.kernels.ref.spike_deliver_ref`.
    """
    n, k = vals.shape
    if n % tile_n != 0:
        raise ValueError(f"N={n} must be a multiple of tile_n={tile_n}")
    kernel = functools.partial(_kernel, steps_lo=steps_lo, r_span=r_span)
    syn = pl.BlockSpec((tile_n, k), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(n // tile_n,),
        in_specs=[syn, syn],
        out_specs=pl.BlockSpec((tile_n, r_span), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, r_span), vals.dtype),
        interpret=interpret,
    )(vals, delay)
