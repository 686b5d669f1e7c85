"""jit'd public wrappers around the Pallas kernels (+ the event-driven path).

These functions handle padding to kernel tile sizes, select interpret mode
automatically (interpret=True unless running on real TPU), and provide the
*event-driven* delivery variant -- the beyond-paper optimization that exploits
spatiotemporal sparsity (at 2.5 spikes/s and 0.1 ms steps only ~0.025 % of
neurons fire per cycle, so dense delivery does ~4000x more multiply work than
the events require). Measured backend-by-backend numbers live in
EXPERIMENTS.md ("Perf: delivery backends"), regenerated by
``benchmarks/bench_delivery.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import cycle as _cyc
from repro.kernels import lif_update as _lif
from repro.kernels import spike_deliver as _dlv

__all__ = [
    "default_interpret",
    "sized_nonzero",
    "bucket_index",
    "ladder_rung",
    "ladder_switch",
    "compact_ids_block",
    "lif_update",
    "spike_deliver",
    "apply_contrib",
    "event_deliver",
    "event_deliver_ids",
    "event_deliver_block",
    "event_deliver_per_area",
    "deposit_chunk",
    "deposit_trips",
    "DEPOSIT_CHUNK_SCOPE",
    "superstep_lif",
    "superstep_iaf",
]


# Entries (packet slots x outgoing width) one chunk of the event deposit
# gathers and scatters: 16 slots of a K_out ~ 3,300 table. A chunk pays one
# loop iteration; a live prefix shorter than a chunk pays for the rest of it.
DEPOSIT_CHUNK_ENTRIES = 1 << 16
# Named scope of one chunk of the event deposit in a trace (not one of the
# window's layer scopes: its ops stay charged to the caller's layer).
DEPOSIT_CHUNK_SCOPE = "event_deposit_chunk"


def default_interpret() -> bool:
    """interpret=True everywhere except on real TPU devices."""
    return jax.default_backend() != "tpu"


def sized_nonzero(mask: jax.Array, *, size: int, fill: int) -> jax.Array:
    """Indices of the first ``size`` true elements of a 1D mask (``fill``-padded).

    Drop-in for ``jnp.nonzero(mask, size=size, fill_value=fill)[0]`` -- same
    first-``size``-by-index selection (so under overflow the *same* entries
    survive) -- but built from a cumsum + a vectorised binary search instead
    of the full O(N log N) sort that sized ``nonzero`` lowers to. The
    compaction is the per-cycle cost of every event path, so this is hot:
    on the CPU backend the searchsorted form measures ~13x faster than the
    sort at N ~ 6k (see EXPERIMENTS.md).
    """
    m = mask.reshape(-1)
    csum = jnp.cumsum(m.astype(jnp.int32))
    idx = jnp.searchsorted(
        csum, jnp.arange(1, size + 1, dtype=jnp.int32), side="left")
    valid = jnp.arange(size) < csum[-1]
    return jnp.where(valid, idx, fill).astype(jnp.int32)


def bucket_index(ladder, need: jax.Array) -> jax.Array:
    """Index of the smallest ladder rung ``>= need`` (clamped to the top).

    The bucket-selection rule of the adaptive two-phase exchange: ``ladder``
    is a static ascending tuple of packet sizes (see
    :func:`repro.core.delivery.bucket_ladder`), ``need`` the phase-1 counted
    maximum. A count landing *exactly on* a rung selects that rung (``side=
    'left'``), one past it selects the next -- the boundary semantics the
    forced-overflow tests pin. Clamping matters only for ladders whose top
    rung is below the true population cap; the exchanges build theirs with
    the hard cap on top, so the chosen rung always covers the count.
    """
    arr = jnp.asarray(ladder, dtype=jnp.int32)
    idx = jnp.searchsorted(arr, jnp.asarray(need, jnp.int32), side="left")
    return jnp.minimum(idx, arr.shape[0] - 1)


def ladder_rung(ladder, need: jax.Array) -> jax.Array:
    """The rung *value* :func:`bucket_index` selects, as a traced int32.

    The byte-accounting companion of :func:`ladder_switch`: both resolve
    the same ``bucket_index``, so the bytes a caller prices always belong
    to the branch that actually executed.
    """
    return jnp.asarray(ladder, dtype=jnp.int32)[bucket_index(ladder, need)]


def ladder_switch(ladder, need: jax.Array, fn, *operands):
    """Dispatch ``fn(rung, *operands)`` over a pre-compiled bucket ladder.

    One branch per rung is traced with that rung as a *static* packet size
    (so jit never retraces on data-dependent shapes) and ``lax.switch``
    selects the branch whose rung the phase-1 count picked via
    :func:`bucket_index`. All branches must return identically-shaped
    outputs (they thread the ring, whose shape never depends on the rung).
    Collectives are legal inside the branches because every participating
    device computes ``need`` from the same count collective and therefore
    selects the same branch.
    """
    idx = bucket_index(ladder, need)
    return jax.lax.switch(
        idx, [functools.partial(fn, int(b)) for b in ladder], *operands)


def compact_ids_block(
    mask: jax.Array,   # [D, L] bool -- which entries to keep, per row
    ids: jax.Array,    # [D, L] or [L] int32 payload per entry
    *,
    size: int,
    fill_id: int,
) -> tuple[jax.Array, jax.Array]:
    """Blocked masked compaction: the wire-packet primitive of every sparse
    exchange path.

    Row ``d`` of the result holds the ``ids`` of the first ``size`` kept
    entries of ``mask[d]`` (by index, so under overflow the *same* entries
    survive as any per-row compaction), padded with ``fill_id``; ``counts[d]``
    is the true number of kept entries (callers surface
    ``max(counts - size, 0)`` as overflow). Built on :func:`sized_nonzero`
    (cumsum + vectorised binary search -- the compaction is the per-cycle
    cost of the event paths, see EXPERIMENTS.md). Shared by
    ``delivery.compact_fired_block`` and the routed exchange's per-edge
    re-compaction (``core/exchange.RoutedExchange``).
    """
    if ids.ndim == mask.ndim - 1:
        ids = jnp.broadcast_to(ids, mask.shape)

    def one(m, i):
        n = m.shape[0]
        pos = sized_nonzero(m, size=size, fill=n)
        ok = pos < n
        pkt = jnp.where(ok, i[jnp.where(ok, pos, 0)], jnp.int32(fill_id))
        return pkt.astype(jnp.int32), m.sum(dtype=jnp.int32)

    return jax.vmap(one)(mask, ids)


def _pad_to(x: jax.Array, multiple: int, axis: int = 0, value=0):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(
    jax.jit,
    static_argnames=("p11", "p21", "p22", "v_th", "v_reset", "t_ref_steps", "tile"),
)
def lif_update(
    v, i_syn, refrac, i_in, alive,
    *, p11, p21, p22, v_th, v_reset, t_ref_steps, tile: int | None = None,
):
    """Fused LIF step over arbitrary-shape state (flattens, pads, and lays
    the neurons out as lane-aligned ``[N / 128, 128]`` rows)."""
    shape = v.shape
    block = 8 * _lif.LANES
    tile = tile or min(_lif.TILE, -(-v.size // block) * block)
    flat = lambda x: _pad_to(x.reshape(-1), tile).reshape(-1, _lif.LANES)
    v_o, i_o, r_o, s_o = _lif.lif_update_pallas(
        flat(v), flat(i_syn), flat(refrac), flat(i_in),
        flat(alive.astype(jnp.int32)),
        p11=p11, p21=p21, p22=p22, v_th=v_th, v_reset=v_reset,
        t_ref_steps=t_ref_steps, tile=tile, interpret=default_interpret(),
    )
    n = v.size
    unflat = lambda x: x.reshape(-1)[:n].reshape(shape)
    return unflat(v_o), unflat(i_o), unflat(r_o), unflat(s_o) != 0


@functools.partial(jax.jit, static_argnames=("steps_lo", "r_span", "tile_n"))
def spike_deliver(
    spikes, src, w, delay, *, steps_lo: int, r_span: int, tile_n: int | None = None
):
    """Delay-resolved contributions [N, r_span] for arbitrary N (pads rows)."""
    n = src.shape[0]
    tile_n = tile_n or min(_dlv.TILE_N, -(-n // 8) * 8)
    # The source gather stays in XLA (Mosaic has no 1-D gather); padded rows
    # carry value 0 and so deposit nothing.
    vals_p = _pad_to(w * spikes[src], tile_n)
    # Delays may be stored int8 (connectivity's narrow dtype); the kernel
    # compares them as int32, so widen at the boundary.
    d_p = _pad_to(delay.astype(jnp.int32), tile_n, value=steps_lo)
    out = _dlv.spike_deliver_pallas(
        vals_p, d_p,
        steps_lo=steps_lo, r_span=r_span, tile_n=tile_n,
        interpret=default_interpret(),
    )
    return out[:n]


def apply_contrib(
    ring: jax.Array,     # [N, R]
    contrib: jax.Array,  # [N, r_span]
    t: jax.Array,
    steps_lo: int,
) -> jax.Array:
    """Roll delay-resolved contributions into ring slots (t+steps_lo+j) % R."""
    r = ring.shape[-1]
    r_span = contrib.shape[-1]
    slots = jnp.mod(t + steps_lo + jnp.arange(r_span), r)  # [r_span]
    return ring.at[:, slots].add(contrib)


def event_deliver_ids(
    ring: jax.Array,      # [N_tgt, R]
    ids: jax.Array,       # [S] int32 fired-source ids; >= N_src marks padding
    tgt_out: jax.Array,   # [N_src, K_out] int32 target ids (N_tgt = no target)
    w_out: jax.Array,     # [N_src, K_out] f32
    d_out: jax.Array,     # [N_src, K_out] int8/int32 delays (steps)
    t: jax.Array,
    *,
    tgt_map=None,
) -> jax.Array:
    """Scatter the outgoing synapses of an already-compacted fired-id list.

    This is the receive side of the sparse-id wire format (NEST sends spike
    *id packets*, not dense vectors): ``ids`` may come straight off an
    ``all_gather`` of per-device compactions. Work is O(K_out) per packet
    slot up to the last live id, in chunks (:func:`event_deliver_block`):
    the scatter goes *directly* into ``ring`` -- no intermediate full-ring
    buffer, so the cost is bounded by the packet, not by N_tgt. Entries with
    ``ids[s] >= N_src`` (the compaction fill value) and table padding rows
    (``tgt = -1``, ``w = 0``) are neutralised by clamping their target into
    row 0 with value 0.0 -- adding +0.0 is bit-exact (ring slots are never
    -0.0: they start at +0.0 and grid-weight sums cannot produce a negative
    zero).

    ``tgt_map`` (optional) remaps gathered target ids elementwise before the
    scatter -- the sharded engines pass the global->device-local row mapping
    here (returning -1 for rows another device owns), so each device
    scatters straight into its ``[N_local, R]`` ring shard instead of
    materialising a full-network buffer.

    Exactness: weights live on the 1/256 grid, so scatter order is irrelevant.
    """
    return event_deliver_block(
        ring, ids.reshape(1, -1), tgt_out, w_out, d_out, t, tgt_map=tgt_map)


def event_deliver_per_area(
    ring: jax.Array,      # [A, N_tgt, R]
    ids: jax.Array,       # [A, S] int32 fired ids per area; >= N_src pads
    tgt_out: jax.Array,   # [A, N_src, K_out] int32
    w_out: jax.Array,     # [A, N_src, K_out] f32
    d_out: jax.Array,     # [A, N_src, K_out] int8/int32
    t: jax.Array,
    *,
    tgt_map=None,
) -> jax.Array:
    """:func:`event_deliver_ids` of each area's packet into its own ring
    with its own tables.

    One chunk loop serves every area: it runs to the last live id of any
    area, and each trip deposits that chunk of every area's packet. The
    trip count is one scalar, so the loop is not batched (a ``vmap`` of
    :func:`event_deliver_ids` would select each area's ring in every trip).
    """
    n_src, k_out = tgt_out.shape[1:]

    def deposit(ring, part):
        return jax.vmap(
            lambda rg, p, tg, w, d: _deposit_packets(
                rg, p[None], tg, w, d, t, tgt_map)
        )(ring, part, tgt_out, w_out, d_out)

    return _deposit_chunks(ring, ids, n_src, k_out, deposit)


def deposit_chunk(k_out: int, s: int) -> int:
    """Packet slots per chunk of the event deposit for packets of ``s``.

    The largest power of two ``C`` with ``C * k_out <=
    DEPOSIT_CHUNK_ENTRIES``, at least 8 and at most ``s``: the chunk width
    follows the shapes alone, so every caller of one table set agrees.
    """
    per_slot = DEPOSIT_CHUNK_ENTRIES // max(k_out, 1)
    return min(1 << max(3, per_slot.bit_length() - 1), max(s, 1))


def deposit_trips(ids: jax.Array, n_src: int, k_out: int) -> jax.Array:
    """Chunks the event deposit runs for the packets ``ids [..., S]``.

    ``ceil(last / C)``, where ``last`` is the position after the last live
    id (``< n_src``) of any row and ``C = deposit_chunk(k_out, S)``. The
    last position, not the count: packets concatenated from several
    devices carry fill between their live runs, which then costs only
    padding.
    """
    s = ids.shape[-1]
    pos = jnp.where(ids < n_src, jnp.arange(1, s + 1, dtype=jnp.int32), 0)
    c = deposit_chunk(k_out, s)
    return (pos.max() + (c - 1)) // c


def _deposit_chunks(ring, ids, n_src, k_out, deposit):
    """``deposit(ring, ids[..., chunk])`` over the column chunks of ``ids``
    up to the chunk holding its last live id, under the chunk scope."""
    c = deposit_chunk(k_out, ids.shape[-1])
    trips = deposit_trips(ids, n_src, k_out)
    ids = _pad_to(ids, c, axis=ids.ndim - 1, value=n_src)

    def chunk(i, ring):
        with jax.named_scope(DEPOSIT_CHUNK_SCOPE):
            part = jax.lax.dynamic_slice_in_dim(ids, i * c, c, axis=-1)
            return deposit(ring, part)

    return jax.lax.fori_loop(0, trips, chunk, ring)


def _deposit_packets(ring, ids, tgt_out, w_out, d_out, t0, tgt_map):
    """Scatter every entry of the id packets ``ids [D, S]``; entry ``(s, i)``
    deposits at slots ``(t0 + s + d) % R``."""
    n_tgt, r = ring.shape
    n_src, _ = tgt_out.shape
    d_win, s_max = ids.shape
    steps = jnp.repeat(jnp.arange(d_win, dtype=jnp.int32), s_max)  # [D*S]
    ids = ids.reshape(-1)
    valid = ids < n_src
    safe = jnp.where(valid, ids, 0)
    tgt = tgt_out[safe]                                       # [D*S, K_out]
    if tgt_map is not None:
        tgt = tgt_map(tgt)
    ok = valid[:, None] & (tgt >= 0) & (tgt < n_tgt)
    tgts = jnp.where(ok, tgt, 0)
    vals = jnp.where(ok, w_out[safe], 0.0)
    # The one place delays are read on the event path: widen the (possibly
    # int8-stored) table entries to int32 at the gather.
    slots = jnp.mod(t0 + steps[:, None] + d_out[safe].astype(jnp.int32), r)
    return ring.at[tgts.reshape(-1), slots.reshape(-1)].add(vals.reshape(-1))


def event_deliver_block(
    ring: jax.Array,      # [N_tgt, R]
    ids: jax.Array,       # [D, S] int32 fired ids per window cycle; >= N_src pads
    tgt_out: jax.Array,   # [N_src, K_out] int32 target ids (N_tgt = no target)
    w_out: jax.Array,     # [N_src, K_out] f32
    d_out: jax.Array,     # [N_src, K_out] int8/int32 delays (steps)
    t0: jax.Array,
    *,
    tgt_map=None,
) -> jax.Array:
    """Single-pass blocked receive: scatter a whole window's id packets.

    ``ids[s]`` is the fired-id packet of window cycle ``s`` (an ``(id, step)``
    packet of bound ``D * S`` with the step implicit in the row); entry
    ``(s, i)`` deposits its outgoing synapses at slots ``(t0 + s + d) % R``.
    This fuses the window-end loop of D sequential per-cycle scatters into
    one deposit with no per-cycle loop bookkeeping. Semantics
    (padding absorption, ``tgt_map`` remapping, exactness) are those of
    :func:`event_deliver_ids`, which is the ``D == 1`` special case.

    The packets are compacted (live ids first), so the work follows the
    spikes, not the bound: the deposit runs over column chunks of
    ``C = deposit_chunk(K_out, S)`` slots, :func:`deposit_trips` of them,
    and skips the rest, whose entries would only add +0.0.
    """
    n_src, k_out = tgt_out.shape

    def deposit(ring, part):
        return _deposit_packets(ring, part, tgt_out, w_out, d_out, t0, tgt_map)

    return _deposit_chunks(ring, ids, n_src, k_out, deposit)


def _pad_rows(xs: dict, tile: int, fills: dict):
    """Pad the neuron axis (axis=1) of every [A, n, ...] array to ``tile``."""
    return {
        name: _pad_to(x, tile, axis=1, value=fills.get(name, 0))
        for name, x in xs.items()
    }


def superstep_lif(
    v, i_syn, refrac, fut, drive_p, gids, alive, src, w, delay, t0,
    *, d_win: int, steps_lo: int, r_span: int,
    p11, p21, p22, v_th, v_reset, t_ref_steps, seed, w_ext,
):
    """Fused LIF D-cycle superstep over ``[A, n]`` state (pads the neuron axis).

    Wraps :func:`repro.kernels.cycle.superstep_lif_pallas`: padded rows carry
    ``alive=0`` / ``w=0`` so they never spike and never deposit. Returns
    ``(v, i_syn, refrac, fut, spikes [A, d_win, n] int32)``.
    """
    n = v.shape[1]
    tile = 128 if n >= 128 else 8
    xs = _pad_rows(
        dict(v=v, i_syn=i_syn, refrac=refrac, fut=fut, drive_p=drive_p,
             gids=gids, alive=alive.astype(jnp.int32), src=src, w=w,
             delay=delay.astype(jnp.int32)),
        tile, fills=dict(delay=steps_lo),
    )
    v_o, i_o, r_o, fut_o, spk = _cyc.superstep_lif_pallas(
        xs["v"], xs["i_syn"], xs["refrac"], xs["fut"], xs["drive_p"],
        xs["gids"], xs["alive"], xs["src"], xs["w"], xs["delay"],
        jnp.reshape(jnp.asarray(t0, jnp.int32), (1,)),
        d_win=d_win, steps_lo=steps_lo, r_span=r_span,
        p11=p11, p21=p21, p22=p22, v_th=v_th, v_reset=v_reset,
        t_ref_steps=t_ref_steps, seed=seed, w_ext=w_ext,
        interpret=default_interpret(),
    )
    return (v_o[:, :n], i_o[:, :n], r_o[:, :n], fut_o[:, :n],
            spk[:, :, :n])


def superstep_iaf(
    countdown, fut, interval, alive, src, w, delay,
    *, d_win: int, steps_lo: int, r_span: int,
):
    """Fused ignore-and-fire superstep; see :func:`superstep_lif`."""
    n = countdown.shape[1]
    tile = 128 if n >= 128 else 8
    xs = _pad_rows(
        dict(countdown=countdown, fut=fut, interval=interval,
             alive=alive.astype(jnp.int32), src=src, w=w,
             delay=delay.astype(jnp.int32)),
        tile, fills=dict(delay=steps_lo, interval=1),
    )
    cd_o, fut_o, spk = _cyc.superstep_iaf_pallas(
        xs["countdown"], xs["fut"], xs["interval"], xs["alive"],
        xs["src"], xs["w"], xs["delay"],
        d_win=d_win, steps_lo=steps_lo, r_span=r_span,
        interpret=default_interpret(),
    )
    return cd_o[:, :n], fut_o[:, :n], spk[:, :, :n]


@functools.partial(jax.jit, static_argnames=("s_max",))
def event_deliver(
    ring: jax.Array,      # [N_tgt, R]
    spikes: jax.Array,    # [N_src] bool
    tgt_out: jax.Array,   # [N_src, K_out] int32 target ids (N_tgt = no target)
    w_out: jax.Array,     # [N_src, K_out] f32
    d_out: jax.Array,     # [N_src, K_out] int8/int32 delays (steps)
    t: jax.Array,
    *,
    s_max: int,
) -> jax.Array:
    """Event-driven delivery: compact fired sources, scatter their targets.

    Work is O(fired * K_out), at most O(s_max * K_out), instead of
    O(N * K); with brain-scale rates this is a >1000x multiply-reduction.
    ``s_max`` is the static event-buffer bound (cf. NEST's spike-register
    resizing -- here sizing is static; the engine surfaces an overflow
    counter in ``SimState`` when the bound is exceeded).
    """
    n_src = tgt_out.shape[0]
    fired = sized_nonzero(spikes.reshape(-1), size=s_max, fill=n_src)
    return event_deliver_ids(ring, fired, tgt_out, w_out, d_out, t)
