"""Delayed-current ring buffer.

Each neuron owns ``ring_len`` future-input slots. A spike emitted at step
``t`` through a synapse with delay ``d`` (in steps, ``1 <= d < ring_len``)
deposits its weight into slot ``(t + d) % ring_len``; at the start of step
``t`` the engine reads -- and clears -- slot ``t % ring_len``.

This is NEST's per-neuron ring buffer, vectorised: the whole network's buffers
form one dense array ``[..., n, ring_len]`` and delivery is a scatter-add.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "read_and_clear",
    "read_and_clear_block",
    "open_window",
    "merge_window_tail",
    "deposit",
    "deposit_scatter",
]


def read_and_clear(ring: jax.Array, t: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Return (input slot for step t, ring with that slot zeroed).

    ``ring``: [..., R]; ``t``: scalar int32 step counter.
    """
    r = ring.shape[-1]
    slot = jnp.mod(t, r)
    i_in = jax.lax.dynamic_index_in_dim(ring, slot, axis=-1, keepdims=False)
    cleared = jax.lax.dynamic_update_index_in_dim(
        ring, jnp.zeros_like(i_in), slot, axis=-1
    )
    return i_in, cleared


def read_and_clear_block(
    ring: jax.Array, t0: jax.Array, d: int
) -> tuple[jax.Array, jax.Array]:
    """Blocked window read: return (slots [t0, t0+d) as ``[..., d]``, cleared ring).

    The fused D-cycle superstep replaces ``d`` per-cycle ``read_and_clear``
    calls (each a dynamic index + a full-ring dynamic update) with ONE
    contiguous ``[..., d]`` slice + ONE update per window. Requires the ring
    to be *phase-aligned*: ``ring.shape[-1] % d == 0`` (guaranteed by
    ``MultiAreaSpec.ring_len``) and ``t0 % d == 0`` (window starts), so the
    window's slots ``(t0 + s) % R`` for ``s in [0, d)`` are contiguous.
    """
    r = ring.shape[-1]
    if r % d != 0:
        raise ValueError(f"ring_len={r} must be a multiple of the block d={d}")
    start = jnp.mod(t0, r)  # a multiple of d by the phase-alignment contract
    blk = jax.lax.dynamic_slice_in_dim(ring, start, d, axis=-1)
    cleared = jax.lax.dynamic_update_slice_in_dim(
        ring, jnp.zeros_like(blk), start, axis=-1
    )
    return blk, cleared


def open_window(
    ring: jax.Array, t0: jax.Array, d: int, w: int
) -> tuple[jax.Array, jax.Array]:
    """Open a superstep window: blocked read/clear + zero-extended live buffer.

    Returns ``(fut [..., w], cleared ring)``: columns ``[0, d)`` of ``fut``
    are the window's input slots (from :func:`read_and_clear_block`),
    ``[d, w)`` start at zero and accumulate the window's own intra deposits
    that overhang the window end (merged back via
    :func:`merge_window_tail`). ``w`` is ``Network.live_window``.
    """
    blk, cleared = read_and_clear_block(ring, t0, d)
    if w > d:
        blk = jnp.concatenate(
            [blk, jnp.zeros(blk.shape[:-1] + (w - d,), blk.dtype)], axis=-1)
    return blk, cleared


def merge_window_tail(
    ring: jax.Array, tail: jax.Array, t: jax.Array
) -> jax.Array:
    """Add window-overhang slots back into the ring.

    ``tail[..., j]`` holds contributions destined for absolute step ``t + j``
    (the part of a superstep's live window buffer that reaches beyond the
    window end). The target slots are one circular range, so instead of a
    generic scatter (serial on the CPU backend; measured ~equal here but
    pathological on wide tails) the tail is zero-padded to the ring length,
    rotated into phase, and added -- one vectorised full-ring pass per
    *window*. A branch-per-phase ``lax.switch`` touching only the tail
    columns was measured 2.4x slower than this: XLA copies the carry into
    every branch. Exact because delivery weights live on the 1/256 grid.
    """
    r = ring.shape[-1]
    w = tail.shape[-1]
    if w == 0:
        return ring
    if w > r:
        raise ValueError(f"tail width {w} exceeds ring length {r}")
    pad = [(0, 0)] * (tail.ndim - 1) + [(0, r - w)]
    return ring + jnp.roll(jnp.pad(tail, pad), jnp.mod(t, r), axis=-1)


def deposit(
    ring: jax.Array,
    vals: jax.Array,
    delays: jax.Array,
    t: jax.Array,
) -> jax.Array:
    """Scatter-add synaptic contributions into future slots.

    Args:
      ring:   [N, R] per-neuron future-input slots.
      vals:   [N, K] contribution of each synapse (w * spike).
      delays: [N, K] integer delays in steps, ``1 <= d < R``.
      t:      scalar step at which the spikes were emitted.

    Returns the updated ring. Implemented as a one-hot matmul over the slot
    axis rather than ``.at[].add`` -- on TPU this lowers to a dense
    [K x R] contraction per neuron tile (MXU/VPU friendly) instead of a serial
    scatter; the Pallas kernel in ``repro.kernels.spike_deliver`` implements
    the tiled version of exactly this contraction.

    ``precision=HIGHEST``: at default precision a TPU contracts f32 in bf16
    passes, which rounds 1/256-grid weights such as ~88 pA (15 significant
    bits) and breaks the bitwise contract between backends.
    """
    r = ring.shape[-1]
    slots = jnp.mod(t + delays.astype(jnp.int32), r)  # [N, K]
    onehot = jax.nn.one_hot(slots, r, dtype=vals.dtype)  # [N, K, R]
    return ring + jnp.einsum("nk,nkr->nr", vals, onehot,
                             precision=jax.lax.Precision.HIGHEST)


def deposit_scatter(
    ring: jax.Array,
    vals: jax.Array,
    delays: jax.Array,
    t: jax.Array,
) -> jax.Array:
    """Scatter-add variant of :func:`deposit` (same semantics).

    Avoids materialising the ``[N, K, R]`` one-hot. Because weights live on
    an exact 1/256 grid, scatter order does not affect the result
    bit-for-bit.

    Cost model (measured, see core/delivery.py module docstring): XLA lowers
    the scatter-add to a *serial* per-update ``while`` loop on the CPU
    backend (~50 ns/synapse), while the one-hot deposit does R x more
    multiply work but fully vectorised -- so one-hot wins when K is large
    relative to the serial/SIMD throughput gap and scatter wins at small K.
    The ring is flattened so the scatter uses a single fused index column
    (``row * R + slot``) instead of a [.., 2] coordinate table; measured
    ~1.3x faster than the 2-D index form on CPU.
    """
    r = ring.shape[-1]
    n, k = vals.shape
    slots = jnp.mod(t + delays.astype(jnp.int32), r)
    flat_idx = (jnp.arange(n, dtype=jnp.int32)[:, None] * r + slots).reshape(-1)
    flat = ring.reshape(-1).at[flat_idx].add(vals.reshape(-1))
    return flat.reshape(n, r)
