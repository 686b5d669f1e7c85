"""Pallas TPU kernel: the fused D-cycle superstep (update + intra delivery).

The engines' structure-aware window runs D local cycles between two global
exchanges. The split kernels (``lif_update``, ``spike_deliver``) still pay one
HBM round trip over the state and the live ring slots *per cycle*; this kernel
fuses the whole window: each grid step loads one area's membrane state and its
live window buffer ``fut [n, W]`` into VMEM ONCE and keeps them there across
the D unrolled cycles -- per-window instead of per-cycle traffic, the
von-Neumann-bottleneck refactoring of Pronold et al. (arXiv:2109.11358)
applied to the innermost loop.

The cycle body reuses the exact math of the split kernels:
:func:`repro.kernels.lif_update.lif_step_math` for the update and
:func:`repro.kernels.spike_deliver.delay_resolved_contrib` for the
delay-resolved intra deposit, plus the counter-based Poisson drive
(:func:`repro.core.neuron.counter_uniform`) recomputed in-kernel -- so
trajectories are bit-identical to the unfused engines (weights on the 1/256
grid; same FMA contraction under jit).

Window-static slot indexing: the live buffer covers relative slots
``[0, W)`` with ``W = D + max_intra_delay``; cycle ``s`` consumes column
``s`` and deposits at columns ``s + delay < W`` -- every index is a static
offset, no ring phase arithmetic in the hot loop. The engine supplies
``fut`` from the blocked ring read and merges columns ``[D, W)`` back
afterwards; the lumped inter exchange stays outside the kernel (it is the
communication step the paper's schedule isolates).

Grid: one program per area -- intra connectivity is area-local, so each
program is self-contained. Sized for areas whose state + tables fit VMEM
(the reference/benchmark scales); production-size areas would add an inner
neuron tiling with a cross-tile spike exchange per cycle.

Interpret mode only: the TPU compiler refuses both kernels (``TPU_REFUSAL``),
and ``EngineConfig.validate`` rejects ``superstep_kernel`` on a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.neuron import counter_uniform
from repro.kernels.lif_update import lif_step_math
from repro.kernels.spike_deliver import delay_resolved_contrib

__all__ = ["superstep_lif_pallas", "superstep_iaf_pallas", "TPU_REFUSAL"]

# Why the TPU kernel compiler (Mosaic) refuses both kernels; pinned against
# the compiler's own errors by tests/test_tpu_compile.py, and what
# EngineConfig.validate reports for superstep_kernel on a TPU.
TPU_REFUSAL = (
    "Mosaic refuses the fused superstep kernels: the in-kernel intra "
    "gather spk[src] is a 1-D gather ('Only 2D gather is supported'), the "
    "LIF variant's in-kernel counter-based drive casts uint32 to float32 "
    "('Unsupported cast: uint32 -> float32'), and a whole area's [n, K] "
    "src/w/delay tables (12 B/synapse, 147 MB at n=4096, K=3000) cannot "
    "stay VMEM-resident at published in-degrees")


def _deposit_window(fut, spk, src, w, j, s: int, steps_lo: int, r_span: int):
    """Intra deposit of cycle ``s``'s spikes at static window offsets."""
    if r_span == 0 or src.shape[-1] == 0:
        return fut
    vals = w * spk.astype(jnp.float32)[src]          # [n, K] gather + scale
    contrib = delay_resolved_contrib(vals, j, r_span)
    return fut.at[:, s + steps_lo: s + steps_lo + r_span].add(contrib)


def _lif_kernel(
    t0_ref, v_ref, i_ref, refrac_ref, fut_ref, p_ref, gid_ref, alive_ref,
    src_ref, w_ref, d_ref,
    v_out, i_out, refrac_out, fut_out, spk_out,
    *, d_win: int, steps_lo: int, r_span: int,
    p11: float, p21: float, p22: float,
    v_th: float, v_reset: float, t_ref_steps: int,
    seed: int, w_ext: float,
):
    t0 = t0_ref[0]                       # SMEM scalar (prefetched)
    v = v_ref[0]
    i_syn = i_ref[0]
    refrac = refrac_ref[0]
    fut = fut_ref[...]                   # [n, W] live window slots, VMEM
    p = p_ref[0]                         # per-cycle drive probability
    gids = gid_ref[0]
    alive = alive_ref[0] != 0
    src = src_ref[...]
    w = w_ref[...]
    j = d_ref[...] - steps_lo
    for s in range(d_win):               # unrolled; every slot index static
        u = counter_uniform(seed, t0 + s, gids)
        drive = (u < p).astype(jnp.float32) * w_ext
        v, i_syn, refrac, spk = lif_step_math(
            v, i_syn, refrac, fut[:, s] + drive, alive,
            p11=p11, p21=p21, p22=p22, v_th=v_th, v_reset=v_reset,
            t_ref_steps=t_ref_steps,
        )
        spk_out[s] = spk.astype(jnp.int32)
        fut = _deposit_window(fut, spk, src, w, j, s, steps_lo, r_span)
    v_out[0] = v
    i_out[0] = i_syn
    refrac_out[0] = refrac
    fut_out[...] = fut


def _iaf_kernel(
    cd_ref, fut_ref, interval_ref, alive_ref, src_ref, w_ref, d_ref,
    cd_out, fut_out, spk_out,
    *, d_win: int, steps_lo: int, r_span: int,
):
    cd = cd_ref[0]
    fut = fut_ref[...]
    interval = interval_ref[0]
    alive = alive_ref[0] != 0
    src = src_ref[...]
    w = w_ref[...]
    j = d_ref[...] - steps_lo
    for s in range(d_win):
        spk = (cd == 0) & alive
        cd = jnp.where(spk, interval - 1, cd - 1)
        spk_out[s] = spk.astype(jnp.int32)
        fut = _deposit_window(fut, spk, src, w, j, s, steps_lo, r_span)
    cd_out[0] = cd
    fut_out[...] = fut


def _specs(n: int, k: int, w_width: int, d_win: int):
    """BlockSpecs shared by both variants: one area per grid step, the area
    axis squeezed (``None``) so every block's last two dims are whole array
    dims. Per-neuron rows travel as ``[A, 1, n]``: a squeezed second-minor
    dim of a 2-D ``[A, n]`` array is not a legal TPU block. Index maps take
    ``*_`` for the scalar-prefetch refs."""
    row = pl.BlockSpec((None, 1, n), lambda i, *_: (i, 0, 0))
    fut = pl.BlockSpec((None, n, w_width), lambda i, *_: (i, 0, 0))
    syn = pl.BlockSpec((None, n, k), lambda i, *_: (i, 0, 0))
    spk = pl.BlockSpec((None, d_win, n), lambda i, *_: (i, 0, 0))
    return row, fut, syn, spk


@functools.partial(
    jax.jit,
    static_argnames=(
        "d_win", "steps_lo", "r_span", "p11", "p21", "p22", "v_th",
        "v_reset", "t_ref_steps", "seed", "w_ext", "interpret",
    ),
)
def superstep_lif_pallas(
    v: jax.Array,        # [A, n] f32
    i_syn: jax.Array,    # [A, n] f32
    refrac: jax.Array,   # [A, n] int32
    fut: jax.Array,      # [A, n, W] f32 live window slots (rel [0, W))
    drive_p: jax.Array,  # [A, n] f32 per-cycle Bernoulli drive probability
    gids: jax.Array,     # [A, n] int32 global neuron ids (drive counter)
    alive: jax.Array,    # [A, n] int32
    src: jax.Array,      # [A, n, K] int32 intra sources (within-area index)
    w: jax.Array,        # [A, n, K] f32
    delay: jax.Array,    # [A, n, K] int32
    t0: jax.Array,       # [1] int32 window-start cycle
    *,
    d_win: int,
    steps_lo: int,
    r_span: int,
    p11: float, p21: float, p22: float,
    v_th: float, v_reset: float, t_ref_steps: int,
    seed: int, w_ext: float,
    interpret: bool = True,
):
    """Fused LIF window: returns ``(v, i_syn, refrac, fut, spikes[A, D, n])``
    with int32 spikes. ``t0`` rides in SMEM as a scalar-prefetch operand."""
    a, n = v.shape
    w_width = fut.shape[-1]
    k = src.shape[-1]
    row, futs, syn, spks = _specs(n, k, w_width, d_win)
    kernel = functools.partial(
        _lif_kernel, d_win=d_win, steps_lo=steps_lo, r_span=r_span,
        p11=p11, p21=p21, p22=p22, v_th=v_th, v_reset=v_reset,
        t_ref_steps=t_ref_steps, seed=seed, w_ext=w_ext,
    )
    rows = lambda x: x.reshape(a, 1, n)
    v, i_syn, refrac, fut, spk = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(a,),
            in_specs=[row, row, row, futs, row, row, row, syn, syn, syn],
            out_specs=(row, row, row, futs, spks),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((a, 1, n), v.dtype),
            jax.ShapeDtypeStruct((a, 1, n), i_syn.dtype),
            jax.ShapeDtypeStruct((a, 1, n), jnp.int32),
            jax.ShapeDtypeStruct((a, n, w_width), fut.dtype),
            jax.ShapeDtypeStruct((a, d_win, n), jnp.int32),
        ),
        interpret=interpret,
    )(t0, rows(v), rows(i_syn), rows(refrac), fut, rows(drive_p),
      rows(gids), rows(alive), src, w, delay)
    return (v.reshape(a, n), i_syn.reshape(a, n), refrac.reshape(a, n),
            fut, spk)


@functools.partial(
    jax.jit,
    static_argnames=("d_win", "steps_lo", "r_span", "interpret"),
)
def superstep_iaf_pallas(
    countdown: jax.Array,  # [A, n] int32
    fut: jax.Array,        # [A, n, W] f32
    interval: jax.Array,   # [A, n] int32 firing interval (steps)
    alive: jax.Array,      # [A, n] int32
    src: jax.Array,        # [A, n, K] int32
    w: jax.Array,          # [A, n, K] f32
    delay: jax.Array,      # [A, n, K] int32
    *,
    d_win: int,
    steps_lo: int,
    r_span: int,
    interpret: bool = True,
):
    """Fused ignore-and-fire window: ``(countdown, fut, spikes[A, D, n])``
    with int32 spikes."""
    a, n = countdown.shape
    w_width = fut.shape[-1]
    k = src.shape[-1]
    row, futs, syn, spks = _specs(n, k, w_width, d_win)
    kernel = functools.partial(
        _iaf_kernel, d_win=d_win, steps_lo=steps_lo, r_span=r_span)
    rows = lambda x: x.reshape(a, 1, n)
    cd, fut, spk = pl.pallas_call(
        kernel,
        grid=(a,),
        in_specs=[row, futs, row, row, syn, syn, syn],
        out_specs=(row, futs, spks),
        out_shape=(
            jax.ShapeDtypeStruct((a, 1, n), jnp.int32),
            jax.ShapeDtypeStruct((a, n, w_width), fut.dtype),
            jax.ShapeDtypeStruct((a, d_win, n), jnp.int32),
        ),
        interpret=interpret,
    )(rows(countdown), fut, rows(interval), rows(alive), src, w, delay)
    return cd.reshape(a, n), fut, spk
