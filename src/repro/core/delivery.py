"""Backend-selectable spike delivery: the shared per-cycle hot path.

The paper identifies the *deliver* phase as the dominant per-cycle compute
cost and its irregular memory access as the thing a cache-aware rewrite must
fix (§2.3, §3). Both engines (``engine.py`` single-host, ``dist_engine.py``
sharded) route their intra-/inter-area delivery through this module, selected
by ``EngineConfig.delivery_backend``:

* ``"onehot"``  -- gather + one-hot-einsum deposit. Reference semantics; the
  per-cycle ``[N, K, R]`` one-hot is a dense MXU contraction but materialises
  the full ring axis for every synapse.
* ``"scatter"`` -- gather + ``.at[].add`` deposit. No ``[N, K, R]`` tensor.
  NOTE the measured CPU crossover vs ``onehot`` (BENCH_delivery.json):
  XLA lowers the scatter-add to a *serial* while-loop over all N*K updates
  (~50 ns/synapse on the reference container -- confirmed in compiled HLO),
  whereas the one-hot einsum does R x more multiplies fully vectorised. At
  the quickstart shape (K=64, R=110) the dense einsum therefore wins
  (~1.5x); at MAM-like small K (K=6) the scatter wins (~1.3x). The deposit
  uses flattened single-column indices (see
  :func:`repro.core.ring_buffer.deposit_scatter`), the fastest scatter
  layout measured; on TPU the same op maps to the native scatter unit and
  the crossover moves -- re-measure there before switching defaults.
* ``"pallas"``  -- the tiled, *delay-resolved* kernel
  (:func:`repro.kernels.ops.spike_deliver`): contributions are reduced over K
  once per slot of the per-pathway delay window ``[steps_lo, steps_lo +
  r_span)`` carried on :class:`~repro.core.connectivity.Network`, then rolled
  into the ring with :func:`~repro.kernels.ops.apply_contrib`. The narrow
  windows are exactly what the paper's short/long pathway split (§4.1.2)
  buys.
* ``"event"``   -- compact the fired neurons and scatter their *outgoing*
  synapses (:func:`~repro.kernels.ops.event_deliver`). At brain-scale rates
  (~0.025 % of neurons fire per 0.1 ms cycle) this replaces the dense
  O(N * K) sweep with an O(s_max * K_out) scatter. Requires
  ``build_network(outgoing=True)``.

All four are bit-identical on the reference network: delivery weights live on
the exact 1/256 grid, so f32 ring accumulation is associative-exact and
neither scatter order nor slot-reduction order can change a ULP.

:func:`compact_fired` implements the wire format of the distributed event
path: fired neurons are compacted into fixed-size id packets *before* the
exchange (NEST's spike-id wire format, the one the paper contrasts with
dense vectors). The receive side scatters the ids through each device's
inter receive tables straight into its ring shard
(``ops.event_deliver_ids`` with a global->local ``tgt_map``); since the
sharded-table refactor those are the per-shard *inbound* slices of
``connectivity.shard_inter_tables`` -- each device scatters only the edges
it owns. ``s_max`` caps the packet; the engines surface the spill in
``SimState.overflow``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ring_buffer
from repro.core.connectivity import Network
from repro.kernels import ops as kops

__all__ = [
    "BACKENDS",
    "expected_area_spikes",
    "event_bounds",
    "bucket_ladder",
    "expected_bucket",
    "deliver_intra",
    "deliver_inter",
    "deliver_inter_block",
    "compact_fired",
    "compact_fired_block",
]

BACKENDS = ("onehot", "scatter", "pallas", "event")

# deliver_inter_block folds the window's cycle axis into the synapse axis;
# for the one-hot backend that materialises an [N, D*K, R] tensor. Above
# this element count (1 GiB f32) the blocked call deposits per cycle
# instead -- production-scale MAM shards would otherwise need ~190 GiB of
# temp per device (measured by launch/dryrun, see EXPERIMENTS.md).
ONEHOT_FOLD_LIMIT = 2**28


def expected_area_spikes(net: Network) -> float:
    """Expected spikes per (padded) area per cycle -- the packet-sizing rule.

    Uses the per-area target rate, which for ignore-and-fire is the exact
    emission rate; ShapeDtypeStruct stand-ins (dry-run lowering) carry no
    rate data and fall back to the 2.5 Hz MAM ground state. Single source of
    truth for :func:`event_bounds` and the routed exchange's per-edge bounds
    (``repro.core.exchange``), so the wire accounting always prices the
    bounds the engines actually ship.
    """
    mean_rate = (
        float(jnp.asarray(net.rate_hz).mean())
        if hasattr(net.rate_hz, "mean") else 2.5
    )
    return net.alive.shape[1] * mean_rate * net.dt_ms * 1e-3


def event_bounds(
    net: Network, *, headroom: float, floor: int, burst_factor: int = 1
) -> tuple[int, int]:
    """Static event-buffer bounds ``(s_max_area, s_max_all)``.

    ``s_max = headroom x expected spikes/cycle + floor`` (cf. NEST's dynamic
    spike-register resizing; sizing is static here, the engines surface
    overruns via ``SimState.overflow``). The expectation is
    :func:`expected_area_spikes`. The event path's cost is s_max-bound, so
    ``floor`` is the knob that trades burst tolerance against wasted
    scatter width.

    ``burst_factor`` multiplies only the whole-network bound's constant
    burst slack (the ``4 x floor`` term). The proportional part of
    ``s_max_all`` scales with the area count, but the slack does not -- so
    a network holding ``B`` independent copies (``launch.serve``'s folded
    trial batch) would run strictly tighter per-copy headroom than its
    ``B`` sequential references. Passing ``burst_factor=B`` restores
    parity without touching the per-area bound (widening that instead
    costs ~``B x`` scatter width in *every* area).
    """
    a = net.alive.shape[0]
    exp_area = expected_area_spikes(net)
    s_max_area = int(headroom * exp_area) + max(floor, 1)
    s_max_all = (int(headroom * exp_area * a)
                 + 4 * max(floor, 1) * max(int(burst_factor), 1))
    return s_max_area, s_max_all


def bucket_ladder(floor: int, cap: int) -> tuple[int, ...]:
    """The adaptive exchange's pre-compiled packet-size ladder.

    Powers-of-two rungs ``floor, 2*floor, 4*floor, ...`` topped by ``cap``
    exactly -- ``cap`` is the *hard* population bound (every neuron in scope
    fires once per cycle; refractoriness forbids more), so a packet sized by
    the top rung can never drop a spike. The adaptive two-phase exchange
    compiles one branch per rung (:func:`repro.kernels.ops.ladder_switch`)
    and phase-1 counts choose the smallest rung that covers the window
    (:func:`repro.kernels.ops.bucket_index`) -- quiet windows ship
    ``floor``-sized packets, the worst case ships ``cap``, and
    ``SimState.overflow`` is provably zero in between. Contrast
    :func:`event_bounds`, the *static* sizing rule the adaptive mode
    replaces: its headroom-scaled expectation can sit below a burst, which
    is exactly the overflow failure mode (cf. NEST's dynamic spike-register
    resizing, arXiv:2109.11358).
    """
    floor = max(int(floor), 1)
    cap = max(int(cap), floor)
    rungs = []
    b = floor
    while b < cap:
        rungs.append(b)
        b *= 2
    rungs.append(cap)
    return tuple(rungs)


def expected_bucket(ladder: tuple[int, ...], expected_count: float) -> int:
    """The rung a typical window lands on: smallest rung >= the expectation.

    The *modelled* counterpart of the runtime bucket choice, used by the
    static wire accounting (``exchange.adaptive_wire_bytes``) to price the
    payload bytes of an expectation-sized window without running devices --
    actual runs report measured bytes in ``SimState.shipped_bytes``.
    """
    need = int(-(-expected_count // 1)) if expected_count > 0 else 1
    for b in ladder:
        if b >= need:
            return b
    return ladder[-1]


def _deposit(ring, vals, delays, t, *, onehot: bool):
    a, n, r = ring.shape
    k = vals.shape[-1]
    fn = ring_buffer.deposit if onehot else ring_buffer.deposit_scatter
    out = fn(ring.reshape(a * n, r), vals.reshape(a * n, k),
             delays.reshape(a * n, k), t)
    return out.reshape(a, n, r)


def deliver_intra(
    ring: jax.Array,         # [A, n, R] target rows (may be a device-local view)
    area_spikes: jax.Array,  # [A, n_src] f32 complete per-area spike vectors
    net: Network,            # tables with matching row view: src_intra [A, n, K]
    t: jax.Array,
    *,
    backend: str,
    s_max: int | None = None,
) -> jax.Array:
    """One cycle of intra-area (short-range pathway) delivery."""
    a, n, r = ring.shape
    if net.src_intra.shape[-1] == 0:
        return ring
    if backend == "event":
        # Single-host layout only (ring covers the full area); the sharded
        # event path compacts before the exchange -- see the engines.
        n_src = net.tgt_intra.shape[1]
        fired = jax.vmap(
            lambda sp: kops.sized_nonzero(sp > 0, size=s_max, fill=n_src)
        )(area_spikes)
        return kops.event_deliver_per_area(
            ring, fired, net.tgt_intra, net.wout_intra, net.dout_intra, t)
    if backend == "pallas":
        k = net.src_intra.shape[-1]
        n_src = area_spikes.shape[-1]
        # Lift per-area source indices into one flat id space so the whole
        # network is a single kernel launch (grid over [A * n] row tiles).
        offs = jnp.arange(a, dtype=jnp.int32) * n_src
        src_g = (net.src_intra + offs[:, None, None]).reshape(a * n, k)
        contrib = kops.spike_deliver(
            area_spikes.reshape(-1), src_g,
            net.w_intra.reshape(a * n, k), net.delay_intra.reshape(a * n, k),
            steps_lo=net.steps_lo_intra, r_span=net.r_span_intra,
        )
        flat = kops.apply_contrib(
            ring.reshape(a * n, r), contrib, t, net.steps_lo_intra)
        return flat.reshape(a, n, r)
    vals = net.w_intra * jax.vmap(lambda s, i: s[i])(area_spikes, net.src_intra)
    return _deposit(ring, vals, net.delay_intra, t,
                    onehot=(backend == "onehot"))


def deliver_inter(
    ring: jax.Array,         # [A, n, R] target rows (may be a device-local view)
    flat_spikes: jax.Array,  # [N_global] f32 global spike vector for one cycle
    net: Network,            # src_inter [A, n, K] holding *global* source ids
    t: jax.Array,
    *,
    backend: str,
    s_max: int | None = None,
) -> jax.Array:
    """One cycle of inter-area (long-range pathway) delivery."""
    a, n, r = ring.shape
    k = net.src_inter.shape[-1]
    if k == 0:
        return ring
    if backend == "event":
        k_out = net.tgt_inter.shape[-1]
        flat = kops.event_deliver(
            ring.reshape(a * n, r),
            flat_spikes > 0,
            net.tgt_inter.reshape(a * n, k_out),
            net.wout_inter.reshape(a * n, k_out),
            net.dout_inter.reshape(a * n, k_out),
            t, s_max=s_max,
        )
        return flat.reshape(a, n, r)
    if backend == "pallas":
        contrib = kops.spike_deliver(
            flat_spikes, net.src_inter.reshape(a * n, k),
            net.w_inter.reshape(a * n, k), net.delay_inter.reshape(a * n, k),
            steps_lo=net.steps_lo_inter, r_span=net.r_span_inter,
        )
        flat = kops.apply_contrib(
            ring.reshape(a * n, r), contrib, t, net.steps_lo_inter)
        return flat.reshape(a, n, r)
    vals = net.w_inter * flat_spikes[net.src_inter]
    return _deposit(ring, vals, net.delay_inter, t,
                    onehot=(backend == "onehot"))


def deliver_inter_block(
    ring: jax.Array,     # [A, n, R] target rows (may be a device-local view)
    block: jax.Array,    # [D, N_global] f32 global spike vectors, one per cycle
    net: Network,        # src_inter [A, n, K] holding *global* source ids
    t0: jax.Array,       # window start (cycle s of the block was emitted at t0+s)
    *,
    backend: str,
    s_max: int | None = None,
) -> jax.Array:
    """One lumped window of inter-area delivery in a **single pass**.

    The structure-aware schedule's window-end exchange used to replay
    ``deliver_inter`` D times in a sequential ``fori_loop``; this entry point
    delivers the whole ``[D, N]`` spike block at once. Per backend:

    * ``event``  -- compact each cycle of the block into an id packet
      (``compact_fired_block``: an ``(id, step)`` packet of bound
      ``D * s_max``) and scatter all of them through the outgoing tables in
      one :func:`repro.kernels.ops.event_deliver_block` pass.
    * ``pallas`` -- D delay-resolved kernel launches whose ``[N, r_span]``
      contributions are shift-summed into one ``[N, D-1+r_span]`` window,
      rolled into the ring with a single ``apply_contrib``.
    * ``onehot``/``scatter`` -- fold the window's cycle axis into the synapse
      axis (``[N, D*K]`` values with delays offset by the cycle index) and
      deposit once.

    Cycle ``s`` of the block behaves exactly like ``deliver_inter(..., t0+s)``;
    a window of per-cycle calls and one blocked call are bit-identical
    (1/256-grid weights make deposit order irrelevant).
    """
    a, n, r = ring.shape
    k = net.src_inter.shape[-1]
    d_win = block.shape[0]
    if k == 0:
        return ring
    if backend == "event":
        k_out = net.tgt_inter.shape[-1]
        n_src = a * n
        # Positions ARE global ids on the complete network view, so the
        # compaction reduces to a sized nonzero per cycle.
        fired = jax.vmap(
            lambda sp: kops.sized_nonzero(sp > 0, size=s_max, fill=n_src)
        )(block)                                           # [D, s_max]
        flat = kops.event_deliver_block(
            ring.reshape(a * n, r), fired,
            net.tgt_inter.reshape(a * n, k_out),
            net.wout_inter.reshape(a * n, k_out),
            net.dout_inter.reshape(a * n, k_out),
            t0,
        )
        return flat.reshape(a, n, r)
    if backend == "pallas":
        span = net.r_span_inter
        wide = None
        for s in range(d_win):
            contrib = kops.spike_deliver(
                block[s], net.src_inter.reshape(a * n, k),
                net.w_inter.reshape(a * n, k),
                net.delay_inter.reshape(a * n, k),
                steps_lo=net.steps_lo_inter, r_span=span,
            )
            shifted = jnp.pad(contrib, ((0, 0), (s, d_win - 1 - s)))
            wide = shifted if wide is None else wide + shifted
        flat = kops.apply_contrib(
            ring.reshape(a * n, r), wide, t0, net.steps_lo_inter)
        return flat.reshape(a, n, r)
    # Dense deposits: cycle s with delay d targets slot (t0 + s + d) % R, so
    # folding s into the delay turns the window into one [N, D*K] deposit.
    # The one-hot deposit materialises [N, D*K, R]; beyond ~2^28 elements
    # (1 GiB f32 -- production-scale MAM shards hit ~50G) that folding
    # trades a catastrophic temp blow-up for a op-count win, so fall back
    # to per-cycle deposits inside the block. Static shapes, static choice,
    # bit-identical either way (1/256-grid exactness).
    if backend == "onehot" and a * n * d_win * k * r > ONEHOT_FOLD_LIMIT:
        for s in range(d_win):
            vals = net.w_inter * block[s][net.src_inter]
            ring = _deposit(ring, vals, net.delay_inter, t0 + s, onehot=True)
        return ring
    vals = net.w_inter[None] * block[:, net.src_inter]     # [D, A, n, K]
    delays = net.delay_inter[None] + jnp.arange(
        d_win, dtype=jnp.int32)[:, None, None, None]       # [D, A, n, K]
    vals = jnp.moveaxis(vals, 0, 2).reshape(a, n, d_win * k)
    delays = jnp.moveaxis(delays, 0, 2).reshape(a, n, d_win * k)
    return _deposit(ring, vals, delays, t0, onehot=(backend == "onehot"))


# ---------------------------------------------------------------------------
# Sparse id packets: the distributed event path's wire format.
# ---------------------------------------------------------------------------


def compact_fired(
    fired: jax.Array,   # [...] bool
    ids: jax.Array,     # [...] int32 payload per neuron (e.g. global ids)
    *,
    s_max: int,
    invalid: int,
) -> tuple[jax.Array, jax.Array]:
    """Compact fired neurons into a fixed-size id packet.

    Returns ``(packet [s_max] int32, count scalar int32)``. The packet holds
    ``ids`` of the first ``s_max`` fired neurons, padded with ``invalid``
    (choose it >= the receiving table's row count so
    :func:`repro.kernels.ops.event_deliver_ids` absorbs it). ``count`` is the
    *true* number of fired neurons; ``count > s_max`` means the packet
    dropped spikes -- the engines accumulate that spill into
    ``SimState.overflow`` instead of failing silently.

    The ``D == 1`` special case of :func:`repro.kernels.ops
    .compact_ids_block` -- one compaction primitive serves every packet
    (local pathway, lumped window, routed edges).
    """
    packet, count = kops.compact_ids_block(
        fired.reshape(1, -1), ids.reshape(1, -1),
        size=s_max, fill_id=invalid)
    return packet[0], count[0]


def compact_fired_block(
    fired: jax.Array,   # [D, ...] bool -- one window of spike rasters
    ids: jax.Array,     # [...] int32 payload per neuron (e.g. global ids)
    *,
    s_max: int,
    invalid: int,
) -> tuple[jax.Array, jax.Array]:
    """Compact a whole window into one ``(id, step)`` packet.

    Returns ``(packets [D, s_max] int32, counts [D] int32)`` -- the blocked
    wire format of the lumped exchange: the step of each id is implicit in
    its row, and the bound is ``D * s_max``. Packing is per cycle (each row is
    :func:`compact_fired` of that cycle), so the spill accounting -- and,
    under overflow, the *dropped spikes themselves* -- are identical to D
    per-cycle packings; the engines accumulate ``max(counts - s_max, 0)``
    into ``SimState.overflow`` either way.
    """
    d_win = fired.shape[0]
    return kops.compact_ids_block(
        fired.reshape(d_win, -1), ids.reshape(-1),
        size=s_max, fill_id=invalid)
