"""Pallas TPU kernel: fused LIF (iaf_psc_exp) state update.

The paper's *update* phase is one of the three per-cycle compute phases
(Fig. 3). A naive jnp chain (decay -> integrate -> threshold -> reset ->
refractory bookkeeping) makes ~6 HBM round trips over the state arrays; this
kernel fuses them into one pass: each [TILE] block of neuron state is loaded
into VMEM once, updated, and written once. The state is laid out as
``[N / 128, 128]`` rows (the engines flatten [A, n_pad] and pad to the tile
size), so every block is lane-aligned; ``alive`` and the spike output travel
as int32 because Mosaic cannot retile 1-D or sub-(32, 128) int8 blocks.

VPU-bound, so the tile is sized in (8 x 128) register-file multiples.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["lif_update_pallas", "lif_step_math", "LANES", "TILE"]

LANES = 128
# 8 sublanes x 128 lanes x 8 = one comfortably VMEM-resident f32 block per
# state array (9 arrays live at once: v, i_syn, refrac, i_in, alive + outs).
TILE = 8 * LANES * 8


def lif_step_math(
    v, i_syn, refrac, i_in, alive,
    *, p11: float, p21: float, p22: float,
    v_th: float, v_reset: float, t_ref_steps: int,
):
    """One exact-propagator LIF step on in-register values.

    The shared cycle body of this kernel and the fused superstep kernel
    (:mod:`repro.kernels.cycle`); bit-identical to the jnp chain in
    ``repro.core.neuron.lif_update``. ``alive`` is bool; returns
    ``(v', i_syn', refrac', spikes bool)``.
    """
    refractory = refrac > 0
    i_new = i_syn * p11 + i_in
    v_prop = v * p22 + i_syn * p21
    v_new = jnp.where(refractory, v_reset, v_prop)
    spikes = (v_new >= v_th) & alive & ~refractory
    v_out = jnp.where(spikes, v_reset, v_new)
    refrac_out = jnp.where(
        spikes, jnp.int32(t_ref_steps), jnp.maximum(refrac - 1, 0)
    )
    return v_out, i_new, refrac_out, spikes


def _kernel(
    v_ref, i_syn_ref, refrac_ref, i_in_ref, alive_ref,
    v_out_ref, i_out_ref, refrac_out_ref, spike_out_ref,
    *, p11: float, p21: float, p22: float,
    v_th: float, v_reset: float, t_ref_steps: int,
):
    v_out, i_out, refrac_out, spikes = lif_step_math(
        v_ref[...], i_syn_ref[...], refrac_ref[...], i_in_ref[...],
        alive_ref[...] != 0,
        p11=p11, p21=p21, p22=p22, v_th=v_th, v_reset=v_reset,
        t_ref_steps=t_ref_steps,
    )
    v_out_ref[...] = v_out
    i_out_ref[...] = i_out
    refrac_out_ref[...] = refrac_out
    spike_out_ref[...] = spikes.astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=(
        "p11", "p21", "p22", "v_th", "v_reset", "t_ref_steps",
        "tile", "interpret",
    ),
)
def lif_update_pallas(
    v: jax.Array,
    i_syn: jax.Array,
    refrac: jax.Array,
    i_in: jax.Array,
    alive: jax.Array,  # int32 (0/1)
    *,
    p11: float,
    p21: float,
    p22: float,
    v_th: float,
    v_reset: float,
    t_ref_steps: int,
    tile: int = TILE,
    interpret: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused LIF step over ``[N / 128, 128]`` state; returns int32 spikes.

    ``tile`` (neurons per grid step) must be a multiple of 8 x 128 and
    divide N (use :func:`repro.kernels.ops.lif_update` for automatic
    flattening and padding)."""
    rows = v.shape[0]
    tile_rows = tile // LANES
    if v.shape[1:] != (LANES,) or tile % (8 * LANES) or rows % tile_rows:
        raise ValueError(
            f"state {v.shape} must be [N / {LANES}, {LANES}] with N a "
            f"multiple of tile={tile} (a multiple of {8 * LANES})")
    bs = pl.BlockSpec((tile_rows, LANES), lambda i: (i, 0))
    kernel = functools.partial(
        _kernel, p11=p11, p21=p21, p22=p22,
        v_th=v_th, v_reset=v_reset, t_ref_steps=t_ref_steps,
    )
    shape = (rows, LANES)
    return pl.pallas_call(
        kernel,
        grid=(rows // tile_rows,),
        in_specs=[bs] * 5,
        out_specs=(bs, bs, bs, bs),
        out_shape=(
            jax.ShapeDtypeStruct(shape, v.dtype),
            jax.ShapeDtypeStruct(shape, i_syn.dtype),
            jax.ShapeDtypeStruct(shape, jnp.int32),
            jax.ShapeDtypeStruct(shape, jnp.int32),
        ),
        interpret=interpret,
    )(v, i_syn, refrac, i_in, alive)
