"""Pluggable spike-exchange layer: how spikes travel between engine shards.

The shared window core (:mod:`repro.core.schedule`) is parameterized by an
``Exchange`` object with two hooks:

* ``cycle(ring, spikes, t, net, gids, inter_now=...)`` -- the per-cycle
  short-range (intra-area) pathway; under the conventional schedule the same
  hook also runs the per-cycle long-range exchange (``inter_now=True``).
* ``window_end(ring, block, t0, net, gids, blocked=...)`` -- the
  structure-aware schedule's lumped window-end long-range pathway.

Both return ``(ring', overflow_delta, shipped_bytes_delta)``; overflow is
the count of spikes a fixed-size packet dropped (0 on dense pathways, and
*provably* 0 under the adaptive two-phase exchange below); shipped bytes is
the mesh-total wire volume the hook actually moved (f32 scalar), accumulated
into ``SimState.shipped_bytes`` so runs report measured -- not just
worst-case -- bytes per window.

Three implementations:

* :class:`LocalExchange` -- single-host identity: no collectives, delivery
  goes straight through :mod:`repro.core.delivery`. The single-host engine
  (``repro.core.make_simulation`` without a mesh) is a thin assembly over
  the shared core with this exchange.
* :class:`DenseMeshExchange` -- the mesh collectives of the original
  distributed engine: bit-packed spike vectors (``comm.gather_*``) for the
  dense backends, compacted id packets over ``all_gather`` for the event
  backend. Every device receives every fired id, whether or not any of its
  neurons has a synapse from the sender -- but since the sharded-table
  refactor each device *scatters* an arriving id only through the inbound
  edges it owns (``connectivity.shard_inter_tables``; see
  ``_inter_tables`` and :func:`inter_table_report`), not the full
  replicated outgoing table.
* :class:`RoutedExchange` -- the connectivity-routed global pathway: at
  build time the area->area adjacency (:func:`repro.core.connectivity
  .area_adjacency`) is folded to the device-group graph, and the window-end
  exchange ships fixed-size id packets only along group->group edges that
  exist, via ``ppermute`` rotation rounds over the group graph instead of a
  mesh-wide ``all_gather`` (cf. Du et al., "A Low-latency Communication
  Design for Brain Simulations"). Rounds whose offset crosses no edge are
  skipped entirely; within a round the permutation contains only existing
  edges, and each packet is compacted *per destination group* under a
  per-edge ``s_max`` bound -- spills feed the same ``SimState.overflow``
  accounting as every other packet bound.

All exchanges are bit-identical: delivery weights live on the exact 1/256
grid, so neither packet order nor scatter order can change a ULP, and the
routed edge filter is exactly the set of edges with at least one synapse.

**Adaptive two-phase exchange** (``EngineConfig.adaptive_exchange``): every
fixed-size id packet above is statically sized from a rate expectation
(``delivery.event_bounds`` / per-edge ``RouteRound.s_max``), so quiet
windows waste wire bytes and loud windows silently drop spikes into
``SimState.overflow`` -- the failure mode NEST's spike register resizes
itself to avoid (Pronold et al. 2021). Adaptive mode replaces the static
bound with two phases:

1. **counts** -- a tiny int32 collective (``comm.count_max`` /
   ``comm.gather_counts``) tells every device the window's true maximum
   packet need *before* any payload ships;
2. **payload** -- the packet is sized by the smallest rung of a
   pre-compiled power-of-two bucket ladder (``delivery.bucket_ladder``,
   dispatched via ``ops.ladder_switch`` so jit never retraces on
   data-dependent shapes) that covers the counted need. The top rung is the
   hard population cap (every neuron in scope fires once per cycle), so no
   reachable count can exceed it: ``SimState.overflow`` is provably zero.

Trajectories are bit-identical to the static path whenever the static path
itself drops nothing (same compaction order, padding scatters +0.0).

Wire-byte accounting: every exchange reports ``wire_bytes(net)`` -- static
mesh-total bytes received per window, split by pathway -- feeding the
wire table ``launch/simulate.py --profile`` prints beside its trace,
``benchmarks/bench_delivery.py`` and the :mod:`repro.core.cost_model`
communication term. :func:`wire_report`
computes the dense-vs-routed comparison for a hypothetical mesh shape
without constructing devices; each entry now carries **both** the static
worst case and the adaptive two-phase model (phase-1 count bytes +
expectation-sized payload, :func:`adaptive_wire_bytes`), and live runs
accumulate the *measured* bytes in ``SimState.shipped_bytes``.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import comm
from repro.core import delivery as delivery_lib
from repro.core.connectivity import Network
from repro.core.schedule import CONVENTIONAL, INTER_EXCHANGE, STRUCTURE_AWARE
from repro.kernels import ops as kops

__all__ = [
    "EXCHANGES",
    "Exchange",
    "InflightWindow",
    "LocalExchange",
    "DenseMeshExchange",
    "RoutedExchange",
    "Routing",
    "build_routing",
    "adaptive_wire_bytes",
    "inter_table_report",
    "priced_inter_table_report",
    "wire_report",
]

EXCHANGES = ("local", "dense", "routed")

_I32_BYTES = 4
# Receive-table bytes per synapse entry at the production delay dtypes:
# tgt int32 + w f32 + delay int8 (matches Network.bytes_per_synapse for
# every spec whose step cutoffs fit int8; reports use the network's own
# accounting so exotic int32-delay specs stay honest).
_SYN_BYTES = 9


# ---------------------------------------------------------------------------
# Group routing tables (the connectivity-derived structure of RoutedExchange)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RouteRound:
    """One ppermute rotation round of the routed global pathway."""

    offset: int                           # destination group = (g + offset) % G
    pairs: tuple[tuple[int, int], ...]    # existing edges at this offset
    s_max: int                            # per-edge packet bound (ids/cycle)


@dataclasses.dataclass(frozen=True)
class Routing:
    """Per-destination-group routing tables over the area adjacency.

    ``proj[a, h]`` -- does source area ``a`` project into any area of device
    group ``h`` (groups own ``A / n_groups`` consecutive areas, row-major
    over the mesh's area axes, matching the engines' placement).
    ``group_adj[g, h]`` -- the folded group graph. ``rounds`` holds only the
    rotation offsets that cross at least one edge; a dense graph needs all
    ``G`` offsets, a sparse one skips most.
    """

    n_groups: int
    proj: np.ndarray        # [A, G] bool
    group_adj: np.ndarray   # [G, G] bool
    rounds: tuple[RouteRound, ...]

    @property
    def n_edges(self) -> int:
        return int(self.group_adj.sum())

    @property
    def n_wire_rounds(self) -> int:
        """Rounds that actually move bytes (offset 0 is group-local)."""
        return sum(1 for r in self.rounds if r.offset != 0)


def build_routing(
    adj: np.ndarray,
    n_groups: int,
    *,
    exp_area_spikes: float,
    headroom: float,
    floor: int,
    intra_tier: int | None = None,
) -> Routing:
    """Fold the [A, A] area adjacency onto ``n_groups`` device groups.

    ``exp_area_spikes`` is the expected spikes per area per cycle; the
    per-edge packet bound scales with the number of source areas actually
    projecting along the edge (``headroom x expectation + slack``, the same
    sizing rule as :func:`repro.core.delivery.event_bounds`), so sparse
    edges get small packets and absent edges get none.

    ``intra_tier`` is the number of consecutive groups sharing the fast
    interconnect tier (groups per pod on the (pod, data) group grid; group
    index is row-major, so one pod's groups are contiguous). When set, the
    rotation rounds are *hierarchically ordered*: the group-local offset 0
    first, then every offset whose existing edges all stay inside a tier,
    then the pod-crossing ones -- so on a multi-pod mesh most rounds
    complete on the fast tier before the first slow-tier crossing, instead
    of interleaving the two. Ordering only (each round ships the same
    packets either way; delivery is scatter-order-exact on the 1/256
    grid), so trajectories are bit-identical to the flat ring order.
    """
    adj = np.asarray(adj, dtype=bool)
    a = adj.shape[0]
    if a % n_groups != 0:
        raise ValueError(f"n_areas={a} not divisible by n_groups={n_groups}")
    a_loc = a // n_groups
    proj = adj.reshape(a, n_groups, a_loc).any(axis=2)          # [A, G]
    group_adj = proj.reshape(n_groups, a_loc, n_groups).any(axis=1)
    # Source areas contributing to each edge, for the per-edge bound.
    n_src = proj.reshape(n_groups, a_loc, n_groups).sum(axis=1)  # [G, G]
    slack = 4 * max(floor, 1)
    rounds = []
    for k in range(n_groups):
        pairs = tuple(
            (g, (g + k) % n_groups)
            for g in range(n_groups)
            if group_adj[g, (g + k) % n_groups]
        )
        if not pairs:
            continue
        s_max = max(
            int(headroom * exp_area_spikes * n_src[g, h]) + slack
            for g, h in pairs
        )
        rounds.append(RouteRound(offset=k, pairs=pairs, s_max=s_max))
    if intra_tier is not None and 0 < intra_tier < n_groups:
        def tier(rnd: RouteRound) -> int:
            if rnd.offset == 0:
                return 0   # group-local, no wire at all
            if all(g // intra_tier == h // intra_tier for g, h in rnd.pairs):
                return 1   # every edge stays on the fast tier
            return 2       # at least one pod-crossing edge
        rounds.sort(key=lambda r: (tier(r), r.offset))
    return Routing(
        n_groups=n_groups, proj=proj, group_adj=group_adj,
        rounds=tuple(rounds),
    )


# ---------------------------------------------------------------------------
# Exchange implementations
# ---------------------------------------------------------------------------


class InflightWindow(NamedTuple):
    """The two-window in-flight state of the overlapped exchange pipeline.

    ``wire`` is the *received* window-end payload of window ``w`` -- every
    collective has already run by the time an InflightWindow exists, so
    finishing it (the receive scatter into the ring) is collective-free and
    can happen at the top of window ``w+1``'s program, overlapping the
    payload transfer with ``w+1``'s compute on hardware with async
    collectives. ``t0`` is the window's start step (the scatter's time
    base). An *empty* inflight (``Exchange.init_inflight``) scatters
    nothing bitwise: id wires carry only the fill id (dropped by the
    receive maps), dense wires carry zeros (+0.0 adds are bit-exact on the
    1/256 grid, and rings never hold -0.0).
    """

    wire: jax.Array
    t0: jax.Array


class Exchange:
    """Interface + shared bookkeeping; see the module docstring.

    ``cycle`` and ``window_end`` return ``(ring', overflow_delta,
    shipped_bytes_delta)``: overflow counts spikes a fixed-size packet
    dropped (always 0 under the adaptive two-phase exchange), shipped bytes
    is the mesh-total wire volume the hook moved this call (f32 scalar; 0
    on the single-host identity), accumulated by the shared window core
    into ``SimState.shipped_bytes``.

    **Overlapped pipeline split** (``EngineConfig.overlap_exchange``):
    ``window_end`` = ``start_window_end`` then ``finish_window_end``. The
    causality of the structure-aware schedule pins where the cut can go:
    window ``w``'s deposits land at slots ``[t0 + D, ...)`` and the
    earliest of them is exactly the first slot window ``w+1`` reads -- so
    the receive *scatter* cannot be deferred past ``w+1``'s ring reads, but
    everything before it can be issued early. ``start`` therefore does the
    assembly and ALL collectives (the adaptive phase-1 counts -- final at
    the end of ``w``'s block -- plus the payload gathers/ppermutes) and all
    overflow/shipped accounting, returning an :class:`InflightWindow`;
    ``finish`` is the collective-free receive scatter, run at the top of
    the next window's program (or by ``Engine.drain`` at a pipeline
    boundary). Split == sequential bitwise: same packets, same scatter
    values, scatter order is exact on the 1/256 grid.
    """

    name = "abstract"
    adaptive = False

    def cycle(self, ring, spikes, t, net, gids, *, inter_now: bool):
        raise NotImplementedError

    def window_end(self, ring, block, t0, net, gids, *, blocked: bool):
        raise NotImplementedError

    def start_window_end(self, block, t0, net, gids, *, blocked: bool):
        """Assemble + ship window ``[t0, t0+D)``'s global pathway; returns
        ``(InflightWindow, overflow_delta, shipped_bytes_delta)``."""
        raise NotImplementedError

    def finish_window_end(self, ring, inflight, net, gids, *, blocked: bool):
        """Collective-free receive scatter of an in-flight window's payload
        into the ring; returns the updated ring."""
        raise NotImplementedError

    def init_inflight(self, net: Network) -> InflightWindow:
        """An empty (scatters-nothing) in-flight window, globally shaped
        (what a pipeline starts from and resets to after a drain)."""
        raise NotImplementedError

    def inflight_pspecs(self) -> InflightWindow:
        """PartitionSpecs of the in-flight state for ``shard_map`` threading
        (distributed exchanges only)."""
        raise NotImplementedError

    def wire_bytes(self, net: Network) -> dict:
        raise NotImplementedError


class LocalExchange(Exchange):
    """Single-host identity exchange: delivery without any wire.

    Reproduces the original ``make_engine`` semantics exactly, including the
    event backend's per-area / whole-network packet bounds and their
    overflow accounting.
    """

    name = "local"

    def __init__(self, net: Network, cfg):
        self.backend = cfg.backend
        self.adaptive = cfg.adaptive_exchange
        self.s_max_area, self.s_max_all = delivery_lib.event_bounds(
            net, headroom=cfg.s_max_headroom, floor=cfg.s_max_floor,
            burst_factor=cfg.s_max_burst)
        # Adaptive bucket ladders: no wire on a single host, but the event
        # path's packet bound still caps the scatter -- the ladder sizes it
        # to the cycle's true count instead, with the hard population cap
        # (every neuron fires) on top, so overflow is impossible.
        a, n_pad = net.alive.shape
        self.ladder_area = delivery_lib.bucket_ladder(cfg.s_max_floor, n_pad)
        self.ladder_all = delivery_lib.bucket_ladder(
            cfg.s_max_floor, a * n_pad)

    def _overflow(self, spikes, net, inter_now: bool):
        """Spikes dropped by the event path's static packet bounds."""
        if self.backend != "event" or self.adaptive:
            return jnp.int32(0)
        per_area = spikes.sum(axis=-1, dtype=jnp.int32)   # [A]
        over = jnp.int32(0)
        if net.k_intra > 0:
            over = jnp.maximum(per_area - self.s_max_area, 0).sum()
        if inter_now and net.k_inter > 0:
            over = over + jnp.maximum(per_area.sum() - self.s_max_all, 0)
        return over

    def cycle(self, ring, spikes, t, net, gids, *, inter_now: bool):
        del gids
        sf = spikes.astype(jnp.float32)
        if self.backend == "event" and self.adaptive:
            per_area = spikes.sum(axis=-1, dtype=jnp.int32)
            ring = kops.ladder_switch(
                self.ladder_area, per_area.max(),
                lambda b, r: delivery_lib.deliver_intra(
                    r, sf, net, t, backend=self.backend, s_max=b),
                ring)
            if inter_now:
                with jax.named_scope(INTER_EXCHANGE):
                    ring = kops.ladder_switch(
                        self.ladder_all, per_area.sum(),
                        lambda b, r: delivery_lib.deliver_inter(
                            r, sf.reshape(-1), net, t,
                            backend=self.backend, s_max=b),
                        ring)
            return ring, jnp.int32(0), jnp.float32(0)
        ring = delivery_lib.deliver_intra(
            ring, sf, net, t, backend=self.backend, s_max=self.s_max_area)
        if inter_now:
            with jax.named_scope(INTER_EXCHANGE):
                ring = delivery_lib.deliver_inter(
                    ring, sf.reshape(-1), net, t,
                    backend=self.backend, s_max=self.s_max_all)
        return ring, self._overflow(spikes, net, inter_now), jnp.float32(0)

    def window_end(self, ring, block, t0, net, gids, *, blocked: bool):
        del gids
        zero = jnp.float32(0)
        if net.k_inter == 0:
            return ring, jnp.int32(0), zero
        d_win = block.shape[0]
        flat = block.reshape(d_win, -1).astype(jnp.float32)
        adaptive = self.backend == "event" and self.adaptive
        if blocked:
            if adaptive:
                counts = block.reshape(d_win, -1).sum(
                    axis=-1, dtype=jnp.int32)
                ring = kops.ladder_switch(
                    self.ladder_all, counts.max(),
                    lambda b, r: delivery_lib.deliver_inter_block(
                        r, flat, net, t0, backend=self.backend, s_max=b),
                    ring)
                return ring, jnp.int32(0), zero
            ring = delivery_lib.deliver_inter_block(
                ring, flat, net, t0, backend=self.backend,
                s_max=self.s_max_all)
            over = jnp.int32(0)
            if self.backend == "event":
                counts = block.reshape(d_win, -1).sum(
                    axis=-1, dtype=jnp.int32)
                over = jnp.maximum(counts - self.s_max_all, 0).sum()
            return ring, over, zero

        def window_loop(s_max, ring):
            def deliver_s(s, carry):
                ring, over = carry
                ring = delivery_lib.deliver_inter(
                    ring, flat[s], net, t0 + s,
                    backend=self.backend, s_max=s_max)
                if self.backend == "event" and not adaptive:
                    over = over + jnp.maximum(
                        block[s].sum(dtype=jnp.int32) - s_max, 0)
                return ring, over

            return jax.lax.fori_loop(
                0, d_win, deliver_s, (ring, jnp.int32(0)))

        if adaptive:
            counts = block.reshape(d_win, -1).sum(axis=-1, dtype=jnp.int32)
            ring, over = kops.ladder_switch(
                self.ladder_all, counts.max(), window_loop, ring)
        else:
            ring, over = window_loop(self.s_max_all, ring)
        return ring, over, zero

    # -- overlapped pipeline split ------------------------------------------

    def start_window_end(self, block, t0, net, gids, *, blocked: bool):
        del gids, blocked
        t0 = jnp.asarray(t0, jnp.int32)
        d_win = block.shape[0]
        flat = block.reshape(d_win, -1).astype(jnp.float32)
        if net.k_inter == 0:
            return (InflightWindow(wire=flat[:, :0], t0=t0),
                    jnp.int32(0), jnp.float32(0))
        over = jnp.int32(0)
        if self.backend == "event" and not self.adaptive:
            # Same per-cycle spill count the sequential hook accumulates
            # (blocked and legacy paths agree on it).
            counts = block.reshape(d_win, -1).sum(axis=-1, dtype=jnp.int32)
            over = jnp.maximum(counts - self.s_max_all, 0).sum()
        return InflightWindow(wire=flat, t0=t0), over, jnp.float32(0)

    def finish_window_end(self, ring, inflight, net, gids, *, blocked: bool):
        del gids
        if net.k_inter == 0 or inflight.wire.shape[-1] == 0:
            return ring
        flat, t0 = inflight.wire, inflight.t0
        d_win = flat.shape[0]
        adaptive = self.backend == "event" and self.adaptive
        counts = flat.sum(axis=-1).astype(jnp.int32)
        if blocked:
            if adaptive:
                return kops.ladder_switch(
                    self.ladder_all, counts.max(),
                    lambda b, r: delivery_lib.deliver_inter_block(
                        r, flat, net, t0, backend=self.backend, s_max=b),
                    ring)
            return delivery_lib.deliver_inter_block(
                ring, flat, net, t0, backend=self.backend,
                s_max=self.s_max_all)

        def window_loop(s_max, ring):
            def deliver_s(s, ring):
                return delivery_lib.deliver_inter(
                    ring, flat[s], net, t0 + s,
                    backend=self.backend, s_max=s_max)

            return jax.lax.fori_loop(0, d_win, deliver_s, ring)

        if adaptive:
            return kops.ladder_switch(
                self.ladder_all, counts.max(), window_loop, ring)
        return window_loop(self.s_max_all, ring)

    def init_inflight(self, net: Network) -> InflightWindow:
        d_win = max(net.delay_ratio, 1)
        a, n_pad = net.alive.shape
        width = a * n_pad if net.k_inter > 0 else 0
        return InflightWindow(
            wire=jnp.zeros((d_win, width), jnp.float32), t0=jnp.int32(0))

    def wire_bytes(self, net: Network) -> dict:
        return dict(exchange=self.name, local_bytes=0, global_bytes=0,
                    total_bytes=0, adaptive=self.adaptive)


class DenseMeshExchange(Exchange):
    """The mesh-wide collectives (the pre-routing distributed design).

    Structure-aware placement: the per-cycle local pathway completes each
    area over the intra-area subgroup (``model`` axis); the window-end global
    pathway all-gathers the lumped ``[D, ...]`` block (bit-packed vectors for
    the dense backends, compacted id packets for the event backend) over the
    *whole* mesh -- every device receives every fired id. Conventional
    placement: one mesh-wide exchange per cycle feeds both pathways.
    """

    name = "dense"

    def __init__(self, net: Network, cfg, mesh):
        self.backend = cfg.backend
        self.schedule = cfg.schedule
        self.mesh = mesh
        self.area_axes = tuple(mesh.axis_names[:-1])
        self.subgroup = mesh.axis_names[-1]
        self.all_axes = tuple(mesh.axis_names)
        self.n_dev = mesh.size
        self.gsz = mesh.shape[self.subgroup]
        self.n_groups = self.n_dev // self.gsz
        self.headroom = cfg.s_max_headroom
        self.floor = cfg.s_max_floor
        self.adaptive = cfg.adaptive_exchange
        # Static event-packet bounds: per-device shares of the single-host
        # bounds, floored so tiny shards keep headroom. _mesh_bounds is the
        # single source of truth, shared with the static wire accounting so
        # the byte counts always price the bounds the window bodies ship.
        if self.backend == "event":
            self.s_max_loc, self.s_max_dev = _mesh_bounds(
                net, n_groups=self.n_groups, gsz=self.gsz,
                headroom=cfg.s_max_headroom, floor=cfg.s_max_floor)
        else:
            self.s_max_loc = self.s_max_dev = 0
        # Adaptive bucket ladders: capped by the hard population bound of
        # each packet's scope (a neuron fires at most once per cycle), so
        # the top rung can never drop a spike. The per-(area, lane) local
        # packet holds at most this device's n_loc neurons of one area; the
        # per-device window packet at most its whole shard.
        A, n_pad = net.alive.shape
        if self.schedule == CONVENTIONAL:
            n_loc = n_pad // self.n_dev if n_pad % self.n_dev == 0 else n_pad
            self.ladder_loc = None
            self.ladder_dev = delivery_lib.bucket_ladder(
                cfg.s_max_floor, A * n_loc)
        else:
            a_loc, n_loc = A // self.n_groups, n_pad // self.gsz
            self.ladder_loc = delivery_lib.bucket_ladder(
                cfg.s_max_floor, n_loc)
            self.ladder_dev = delivery_lib.bucket_ladder(
                cfg.s_max_floor, a_loc * n_loc)
        # Static per-hook shipped-byte constants, derived from the same
        # accounting the Engine reports (dense_wire_bytes), so measured
        # bytes == modelled bytes wherever packets are statically sized.
        wb = dense_wire_bytes(
            net, backend=self.backend, schedule=self.schedule,
            n_groups=self.n_groups, gsz=self.gsz,
            headroom=self.headroom, floor=self.floor)
        d_win = max(net.delay_ratio, 1)
        if self.schedule == CONVENTIONAL:
            self._cycle_wire = wb["global_bytes"] / d_win
            self._window_wire = 0.0
        else:
            self._cycle_wire = wb["local_bytes"] / d_win
            self._window_wire = float(wb["global_bytes"])

    # -- shard-index helpers (valid only inside shard_map) ------------------

    def _axis_offset(self, axes: Sequence[str], block: int):
        """This device's row offset for a dim sharded over ``axes``."""
        idx = jnp.int32(0)
        for ax in axes:
            idx = idx * self.mesh.shape[ax] + jax.lax.axis_index(ax)
        return idx * block

    def _group_index(self):
        """Flattened (row-major) index of this device's area group."""
        g = jnp.int32(0)
        for ax in self.area_axes:
            g = g * self.mesh.shape[ax] + jax.lax.axis_index(ax)
        return g

    def _global_to_local(self, a_loc: int, n_loc: int, net: Network):
        """Global target id -> local ring row (-1 if another device owns it)."""
        n_pad = net.n_pad
        aoff = self._axis_offset(self.area_axes, a_loc)
        noff = self._axis_offset((self.subgroup,), n_loc)

        def to_local(g):
            al = g // n_pad - aoff
            il = g % n_pad - noff
            keep = (al >= 0) & (al < a_loc) & (il >= 0) & (il < n_loc)
            return jnp.where(keep, al * n_loc + il, -1)

        return to_local

    def _inter_tables(self, net: Network):
        """This device's inter receive tables ``(tgt, w, d) [n_rows, K]``.

        With sharded inbound tables (``connectivity.shard_inter_tables``,
        the default distributed assembly) the shard_map view's leading
        shard axis is local size 1 -- ``[0]`` selects this device's own
        inbound slice, so the receive scatter touches only the ~1/S of
        edges this device owns. Subgroup-sliced tables carry a second
        sharded lane axis (``[S, gsz, rows, K]``, local view ``[1, 1,
        rows, K]``) -- ``[0, 0]`` selects this device's own ~1/(S * gsz)
        slice. The legacy replicated reshape is kept for
        ``EngineConfig.shard_inter_tables=False`` (the equivalence suite's
        bit-identity reference).
        """
        if net.tgt_inter_in is not None:
            if net.tgt_inter_in.ndim == 4:
                return (net.tgt_inter_in[0, 0], net.wout_inter_in[0, 0],
                        net.dout_inter_in[0, 0])
            return (net.tgt_inter_in[0], net.wout_inter_in[0],
                    net.dout_inter_in[0])
        n_rows = net.n_areas * net.n_pad
        k_out = net.tgt_inter.shape[-1]
        return (net.tgt_inter.reshape(n_rows, k_out),
                net.wout_inter.reshape(n_rows, k_out),
                net.dout_inter.reshape(n_rows, k_out))

    def _intra_tables(self, net: Network):
        """This device's outgoing intra tables ``(tgt, w, d) [A, n, K]``.

        Subgroup-sliced tables (``connectivity.slice_intra_tables``) carry
        a leading lane axis sharded over the subgroup (``[gsz, A, n_pad,
        K_lane]``, local view ``[1, A_loc, n_pad, K_lane]``) -- ``[0]``
        selects this lane's own target-window slice, so the local-pathway
        scatter touches only the ~1/gsz of intra edges landing in its own
        neuron window instead of a lane-replicated full table. The 3-D
        passthrough keeps the legacy replicated layout (single-host
        engines, the conventional cut, ``subgroup_inter_tables=False``).
        """
        if net.tgt_intra.ndim == 4:
            return net.tgt_intra[0], net.wout_intra[0], net.dout_intra[0]
        return net.tgt_intra, net.wout_intra, net.dout_intra

    # -- hooks --------------------------------------------------------------

    def cycle(self, ring, spikes, t, net, gids, *, inter_now: bool):
        if self.schedule == CONVENTIONAL:
            return self._cycle_conventional(ring, spikes, t, net, gids)
        assert not inter_now, "structure-aware lumps the global pathway"
        n_loc = spikes.shape[-1]
        a_loc = spikes.shape[0]
        s8 = spikes.astype(jnp.int8)
        over = jnp.int32(0)
        shipped = jnp.float32(self._cycle_wire)
        if self.backend == "event" and net.k_intra > 0:
            # Local pathway, sparse wire: compact fired neurons into
            # per-area id packets *before* the subgroup exchange.
            noff = jax.lax.axis_index(self.subgroup) * n_loc
            ids = noff + jnp.arange(n_loc, dtype=jnp.int32)

            # Scatter straight into this device's neuron window of each
            # area: within-area target -> local row, -1 if not ours.
            def to_local(i):
                il = i - noff
                keep = (il >= 0) & (il < n_loc)
                return jnp.where(keep, il, -1)

            def local_pathway(s_max, ring):
                packets, counts = jax.vmap(
                    lambda f: delivery_lib.compact_fired(
                        f, ids, s_max=s_max, invalid=net.n_pad)
                )(spikes)
                wire = jax.lax.all_gather(
                    packets, self.subgroup, axis=1, tiled=True)
                ring = kops.event_deliver_per_area(
                    ring, wire, *self._intra_tables(net), t,
                    tgt_map=to_local)
                return ring, counts

            if self.adaptive:
                # Phase 1: the mesh-max per-(area, lane) count selects one
                # bucket for every device (branch uniformity); phase 2
                # ships rung-sized packets. The top rung is n_loc (this
                # lane's whole neuron window), so nothing can drop.
                need = comm.count_max(
                    spikes.sum(axis=-1, dtype=jnp.int32).max(),
                    self.all_axes)
                ring, _ = kops.ladder_switch(
                    self.ladder_loc, need, local_pathway, ring)
                rung = kops.ladder_rung(self.ladder_loc, need)
                shipped = (
                    jnp.float32(self.n_dev * a_loc * (self.gsz - 1)
                                * _I32_BYTES) * rung.astype(jnp.float32)
                    + comm.count_wire_bytes(1, self.n_dev))
            else:
                ring, counts = local_pathway(self.s_max_loc, ring)
                over = jax.lax.psum(
                    jnp.maximum(counts - self.s_max_loc, 0).sum(),
                    self.all_axes)
        elif self.backend != "event":
            # Local pathway, dense wire: complete this device's areas over
            # the subgroup, then deliver via the shared dispatch.
            area_spikes = comm.gather_area(s8, subgroup_axis=self.subgroup)
            ring = delivery_lib.deliver_intra(
                ring, area_spikes.astype(jnp.float32), net, t,
                backend=self.backend)
        if net.k_intra == 0:
            shipped = jnp.float32(0)
        return ring, over, shipped

    def _cycle_conventional(self, ring, spikes, t, net, gids):
        """One mesh-wide exchange feeds both pathways (round-robin layout).

        The exchange and the long-range deposit run under the
        ``inter_exchange`` scope; the short-range deposit stays in the
        caller's ``intra_deliver``.
        """
        A, n_pad = net.n_areas, net.n_pad
        n_loc = spikes.shape[-1]
        r_len = ring.shape[-1]
        s8 = spikes.astype(jnp.int8)
        over = jnp.int32(0)
        shipped = jnp.float32(self._cycle_wire)
        if self.backend == "event":
            noff = self._axis_offset(self.all_axes, n_loc)

            # Both scatters go straight into this device's neuron window
            # (rows [noff, noff + n_loc) of every area) -- no full
            # [A, n_pad, R] buffer.
            def win_local(i):
                il = i - noff
                keep = (il >= 0) & (il < n_loc)
                return jnp.where(keep, il, -1)

            def exchange_cycle(s_max, ring):
                with jax.named_scope(INTER_EXCHANGE):
                    packet, count = delivery_lib.compact_fired(
                        spikes, gids, s_max=s_max, invalid=A * n_pad)
                    wire = jax.lax.all_gather(
                        packet, self.all_axes, axis=0,
                        tiled=True)                          # [n_dev*s]
                if net.k_intra > 0:
                    # Short-range: per-area within-area ids from the list.
                    areas = jnp.arange(A, dtype=jnp.int32)
                    ids_a = jnp.where(
                        wire[None, :] // n_pad == areas[:, None],
                        wire[None, :] % n_pad, n_pad)       # [A, S]
                    ring = kops.event_deliver_per_area(
                        ring, ids_a, *self._intra_tables(net), t,
                        tgt_map=win_local)
                # Long-range: global target id -> (area row, local window).
                if net.k_inter > 0:
                    tgt_f, w_f, d_f = self._inter_tables(net)

                    def glob_local(g):
                        il = g % n_pad - noff
                        keep = (il >= 0) & (il < n_loc)
                        return jnp.where(keep, (g // n_pad) * n_loc + il, -1)

                    with jax.named_scope(INTER_EXCHANGE):
                        ring = kops.event_deliver_ids(
                            ring.reshape(A * n_loc, r_len), wire, tgt_f,
                            w_f, d_f, t, tgt_map=glob_local,
                        ).reshape(A, n_loc, r_len)
                return ring, count

            if self.adaptive:
                # Phase 1: mesh-max fired count this cycle; phase 2: one
                # rung-sized packet per device. Top rung = the device's
                # whole shard (A * n_loc), so no count can exceed it.
                with jax.named_scope(INTER_EXCHANGE):
                    need = comm.count_max(
                        spikes.sum(dtype=jnp.int32), self.all_axes)
                ring, _ = kops.ladder_switch(
                    self.ladder_dev, need, exchange_cycle, ring)
                rung = kops.ladder_rung(self.ladder_dev, need)
                shipped = (
                    jnp.float32(self.n_dev * (self.n_dev - 1) * _I32_BYTES)
                    * rung.astype(jnp.float32)
                    + comm.count_wire_bytes(1, self.n_dev))
            else:
                ring, count = exchange_cycle(self.s_max_dev, ring)
                with jax.named_scope(INTER_EXCHANGE):
                    over = jax.lax.psum(
                        jnp.maximum(count - self.s_max_dev, 0),
                        self.all_axes)
        else:
            # One global all_gather per cycle: every device needs the full
            # vector because its neurons' sources are scattered everywhere.
            with jax.named_scope(INTER_EXCHANGE):
                full = comm.gather_full(s8, self.all_axes)
                full_f = full.astype(jnp.float32)  # [A, n_pad]
            ring = delivery_lib.deliver_intra(
                ring, full_f, net, t, backend=self.backend)
            with jax.named_scope(INTER_EXCHANGE):
                ring = delivery_lib.deliver_inter(
                    ring, full_f.reshape(-1), net, t, backend=self.backend)
        return ring, over, shipped

    def window_end(self, ring, block, t0, net, gids, *, blocked: bool):
        if net.k_inter == 0:
            return ring, jnp.int32(0), jnp.float32(0)
        a_loc, n_loc, r_len = ring.shape
        A, n_pad = net.n_areas, net.n_pad
        d_win = block.shape[0]
        shipped = jnp.float32(self._window_wire)
        if self.backend == "event":
            tgt_f, w_f, d_f = self._inter_tables(net)
            to_local = self._global_to_local(a_loc, n_loc, net)

            def exchange_window(s_max, ring):
                # Sparse wire: one (id, step) packet for the whole window.
                packets, counts = delivery_lib.compact_fired_block(
                    block, gids, s_max=s_max, invalid=A * n_pad)
                wire = jax.lax.all_gather(
                    packets, self.all_axes, axis=1, tiled=True)
                ring_flat = ring.reshape(a_loc * n_loc, r_len)
                if blocked:
                    # Single-pass blocked receive: all D packets at once.
                    ring_flat = kops.event_deliver_block(
                        ring_flat, wire, tgt_f, w_f, d_f, t0,
                        tgt_map=to_local)
                else:
                    def deliver_s(s, rf):
                        return kops.event_deliver_ids(
                            rf, wire[s], tgt_f, w_f, d_f, t0 + s,
                            tgt_map=to_local)

                    ring_flat = jax.lax.fori_loop(
                        0, d_win, deliver_s, ring_flat)
                return ring_flat.reshape(a_loc, n_loc, r_len), counts

            if self.adaptive:
                # Phase 1: the window's mesh-max per-cycle fired count (one
                # scalar pmax); phase 2: all D cycles ship rung-sized
                # packets. Top rung = the whole device shard -> zero drop.
                need = comm.count_max(
                    block.reshape(d_win, -1).sum(
                        axis=-1, dtype=jnp.int32).max(),
                    self.all_axes)
                ring, _ = kops.ladder_switch(
                    self.ladder_dev, need, exchange_window, ring)
                rung = kops.ladder_rung(self.ladder_dev, need)
                shipped = (
                    jnp.float32(self.n_dev * d_win * (self.n_dev - 1)
                                * _I32_BYTES) * rung.astype(jnp.float32)
                    + comm.count_wire_bytes(1, self.n_dev))
                return ring, jnp.int32(0), shipped
            ring, counts = exchange_window(self.s_max_dev, ring)
            over = jax.lax.psum(
                jnp.maximum(counts - self.s_max_dev, 0).sum(), self.all_axes)
            return ring, over, shipped

        gblock = comm.gather_global(
            block.astype(jnp.int8), area_axes=self.area_axes,
            subgroup_axis=self.subgroup)          # [D, A, n_pad] int8
        gflat = gblock.astype(jnp.float32).reshape(d_win, A * n_pad)
        if blocked:
            ring = delivery_lib.deliver_inter_block(
                ring, gflat, net, t0, backend=self.backend)
            return ring, jnp.int32(0), shipped

        def deliver_s(s, ring):
            return delivery_lib.deliver_inter(
                ring, gflat[s], net, t0 + s, backend=self.backend)

        ring = jax.lax.fori_loop(0, d_win, deliver_s, ring)
        return ring, jnp.int32(0), shipped

    # -- overlapped pipeline split ------------------------------------------

    def start_window_end(self, block, t0, net, gids, *, blocked: bool):
        del blocked
        t0 = jnp.asarray(t0, jnp.int32)
        d_win = block.shape[0]
        if net.k_inter == 0:
            return (InflightWindow(jnp.zeros((d_win, 0), jnp.int32), t0),
                    jnp.int32(0), jnp.float32(0))
        A, n_pad = net.n_areas, net.n_pad
        invalid = A * n_pad
        shipped = jnp.float32(self._window_wire)
        if self.backend == "event":
            if self.adaptive:
                # Phase 1 (the counts are final at the end of this window's
                # block) + the payload all_gather, both issued here; the pad
                # to the ladder cap keeps every bucket branch on one static
                # in-flight shape, extra slots carrying the fill id.
                cap = self.ladder_dev[-1]
                need = comm.count_max(
                    block.reshape(d_win, -1).sum(
                        axis=-1, dtype=jnp.int32).max(),
                    self.all_axes)

                def assemble(b):
                    packets, _ = delivery_lib.compact_fired_block(
                        block, gids, s_max=b, invalid=invalid)
                    gw = jax.lax.all_gather(
                        packets, self.all_axes, axis=1, tiled=True)
                    gw = gw.reshape(d_win, self.n_dev, b)
                    gw = jnp.pad(gw, ((0, 0), (0, 0), (0, cap - b)),
                                 constant_values=invalid)
                    return gw.reshape(d_win, self.n_dev * cap)

                wire = kops.ladder_switch(self.ladder_dev, need, assemble)
                rung = kops.ladder_rung(self.ladder_dev, need)
                shipped = (
                    jnp.float32(self.n_dev * d_win * (self.n_dev - 1)
                                * _I32_BYTES) * rung.astype(jnp.float32)
                    + comm.count_wire_bytes(1, self.n_dev))
                return InflightWindow(wire, t0), jnp.int32(0), shipped
            packets, counts = delivery_lib.compact_fired_block(
                block, gids, s_max=self.s_max_dev, invalid=invalid)
            wire = jax.lax.all_gather(
                packets, self.all_axes, axis=1, tiled=True)
            over = jax.lax.psum(
                jnp.maximum(counts - self.s_max_dev, 0).sum(), self.all_axes)
            return InflightWindow(wire, t0), over, shipped
        gblock = comm.gather_global(
            block.astype(jnp.int8), area_axes=self.area_axes,
            subgroup_axis=self.subgroup)          # [D, A, n_pad] int8
        return InflightWindow(gblock, t0), jnp.int32(0), shipped

    def finish_window_end(self, ring, inflight, net, gids, *, blocked: bool):
        del gids
        if net.k_inter == 0 or inflight.wire.shape[1] == 0:
            return ring
        a_loc, n_loc, r_len = ring.shape
        A, n_pad = net.n_areas, net.n_pad
        wire, t0 = inflight.wire, inflight.t0
        d_win = wire.shape[0]
        if self.backend == "event":
            tgt_f, w_f, d_f = self._inter_tables(net)
            to_local = self._global_to_local(a_loc, n_loc, net)
            ring_flat = ring.reshape(a_loc * n_loc, r_len)
            if blocked:
                ring_flat = kops.event_deliver_block(
                    ring_flat, wire, tgt_f, w_f, d_f, t0, tgt_map=to_local)
            else:
                def deliver_s(s, rf):
                    return kops.event_deliver_ids(
                        rf, wire[s], tgt_f, w_f, d_f, t0 + s,
                        tgt_map=to_local)

                ring_flat = jax.lax.fori_loop(0, d_win, deliver_s, ring_flat)
            return ring_flat.reshape(a_loc, n_loc, r_len)
        gflat = wire.astype(jnp.float32).reshape(d_win, A * n_pad)
        if blocked:
            return delivery_lib.deliver_inter_block(
                ring, gflat, net, t0, backend=self.backend)

        def deliver_s(s, ring):
            return delivery_lib.deliver_inter(
                ring, gflat[s], net, t0 + s, backend=self.backend)

        return jax.lax.fori_loop(0, d_win, deliver_s, ring)

    def init_inflight(self, net: Network) -> InflightWindow:
        d_win = max(net.delay_ratio, 1)
        A, n_pad = net.n_areas, net.n_pad
        if net.k_inter == 0:
            wire = jnp.zeros((d_win, 0), jnp.int32)
        elif self.backend == "event":
            cap = self.ladder_dev[-1] if self.adaptive else self.s_max_dev
            wire = jnp.full((d_win, self.n_dev * cap), A * n_pad, jnp.int32)
        else:
            wire = jnp.zeros((d_win, A, n_pad), jnp.int8)
        return InflightWindow(wire=wire, t0=jnp.int32(0))

    def inflight_pspecs(self) -> InflightWindow:
        from jax.sharding import PartitionSpec as P

        # The dense wire is the result of a whole-mesh gather: identical on
        # every device, so the in-flight state is replicated.
        return InflightWindow(wire=P(), t0=P())

    # -- static wire accounting ---------------------------------------------

    def wire_bytes(self, net: Network) -> dict:
        rep = dense_wire_bytes(
            net, backend=self.backend, schedule=self.schedule,
            n_groups=self.n_groups, gsz=self.gsz,
            headroom=self.headroom, floor=self.floor)
        rep["adaptive"] = adaptive_wire_bytes(
            net, backend=self.backend, schedule=self.schedule,
            n_groups=self.n_groups, gsz=self.gsz,
            headroom=self.headroom, floor=self.floor)
        rep["adaptive_on"] = self.adaptive
        return rep


class RoutedExchange(DenseMeshExchange):
    """Connectivity-routed global pathway (see the module docstring).

    The local pathway is inherited from :class:`DenseMeshExchange` -- the
    intra-area subgroup exchange already mirrors network structure. The
    window-end global pathway replaces the mesh-wide ``all_gather`` with
    ppermute rotation rounds over the group graph: each group's window
    packet is masked and re-compacted *per destination group* (only ids
    whose source area projects along the edge, bound ``RouteRound.s_max``),
    shipped only along edges that exist, and scattered through this
    device's inter receive tables on arrival (the sharded inbound slice by
    default, see ``_inter_tables``). Requires
    ``build_network(outgoing=True)`` for the inter tables, under every
    delivery backend (the routed wire format is id packets).
    """

    name = "routed"

    def __init__(self, net: Network, cfg, mesh, adjacency: np.ndarray):
        super().__init__(net, cfg, mesh)
        if cfg.schedule != STRUCTURE_AWARE:
            raise ValueError(
                "RoutedExchange routes the structure-aware window's lumped "
                "global pathway; the conventional schedule has none")
        if (net.k_inter > 0 and net.tgt_inter is None
                and net.tgt_inter_in is None):
            raise ValueError(
                "RoutedExchange ships id packets and scatters through the "
                "outgoing tables: build_network(outgoing=True) required")
        # The routed global pathway ships device packets regardless of the
        # delivery backend, so the bound must exist for the dense ones too
        # (the parent already set it for 'event').
        if self.backend != "event":
            _, self.s_max_dev = _mesh_bounds(
                net, n_groups=self.n_groups, gsz=self.gsz,
                headroom=cfg.s_max_headroom, floor=cfg.s_max_floor)
        exp_area = delivery_lib.expected_area_spikes(net)
        # Hierarchical round order on a multi-pod mesh: the leading area
        # axis is the pod tier, so groups-per-pod consecutive groups share
        # the fast tier and their offsets are scheduled first.
        intra_tier = (
            self.n_groups // mesh.shape[self.area_axes[0]]
            if len(self.area_axes) > 1 else None
        )
        self.routing = build_routing(
            adjacency, self.n_groups, exp_area_spikes=exp_area,
            headroom=cfg.s_max_headroom, floor=cfg.s_max_floor,
            intra_tier=intra_tier)
        # Baked constants: area -> destination-group projection (row A
        # absorbs the packet fill id) and the group graph for the
        # receive-validity mask.
        self._proj_const = np.concatenate(
            [self.routing.proj, np.zeros((1, self.n_groups), bool)], axis=0)
        # Adaptive per-round machinery: the edge-packet ladder tops out at
        # the whole source group's population (areas/group x n_pad -- also
        # exactly the assembled group packet's id capacity), and each
        # round's static [G, areas/group] mask selects, from the phase-1
        # per-area count table, the areas feeding that round's edges -- so
        # every device derives the round's *exact* packet need.
        A, n_pad = net.alive.shape
        a_grp = A // self.n_groups
        self.ladder_edge = delivery_lib.bucket_ladder(
            cfg.s_max_floor, a_grp * n_pad)
        proj_r = self.routing.proj.reshape(self.n_groups, a_grp,
                                           self.n_groups)
        self._round_masks = {
            rnd.offset: np.stack([
                proj_r[g, :, (g + rnd.offset) % self.n_groups]
                for g in range(self.n_groups)
            ]).astype(np.int32)                      # [G, areas/group]
            for rnd in self.routing.rounds
        }
        # The routed global pathway's static shipped-byte constant replaces
        # the dense parent's (same accounting routed_wire_bytes reports).
        self._window_wire = float(routed_wire_bytes(
            net, self.routing, backend=self.backend, gsz=self.gsz,
            headroom=self.headroom, floor=self.floor)["global_bytes"])

    def window_end(self, ring, block, t0, net, gids, *, blocked: bool):
        # The routed receive is always the single-pass blocked scatter; a
        # window of per-cycle scatters would be bit-identical (grid-exact
        # weights), so ``blocked`` has nothing to select.
        del blocked
        if net.k_inter == 0 or not self.routing.rounds:
            return ring, jnp.int32(0), jnp.float32(0)
        if self.adaptive:
            return self._window_end_adaptive(ring, block, t0, net, gids)
        a_loc, n_loc, r_len = ring.shape
        A, n_pad = net.n_areas, net.n_pad
        G = self.routing.n_groups
        invalid = A * n_pad

        # 1. Assemble the *group* packet on the fast tier: compact this
        # device's fired ids, complete over the intra-area subgroup.
        packets, counts = delivery_lib.compact_fired_block(
            block, gids, s_max=self.s_max_dev, invalid=invalid)
        over = jax.lax.psum(
            jnp.maximum(counts - self.s_max_dev, 0).sum(), self.all_axes)
        gwire = jax.lax.all_gather(
            packets, self.subgroup, axis=1, tiled=True)      # [D, gsz*s_dev]

        my_g = self._group_index()
        lane0 = jax.lax.axis_index(self.subgroup) == 0
        src_area = jnp.where(gwire < invalid, gwire // n_pad, A)
        proj = jnp.asarray(self._proj_const)                 # [A+1, G]
        gadj = jnp.asarray(self.routing.group_adj)           # [G, G]

        # 2. One rotation round per *existing* offset of the group graph;
        # every received packet keeps its [D, s] row=cycle layout, so the
        # rounds concatenate along the id axis into ONE blocked scatter.
        received = []
        for rnd in self.routing.rounds:
            dst_g = jnp.mod(my_g + rnd.offset, G)
            keep = proj[src_area, dst_g]                     # [D, L]
            pkt, cnt = kops.compact_ids_block(
                keep, gwire, size=rnd.s_max, fill_id=invalid)
            # Per-edge spill: every subgroup lane computes the same count,
            # so only lane 0 contributes to the psum.
            spill = jnp.maximum(cnt - rnd.s_max, 0).sum()
            over = over + jax.lax.psum(
                jnp.where(lane0, spill, 0), self.all_axes)
            if rnd.offset:
                axis = (self.area_axes if len(self.area_axes) > 1
                        else self.area_axes[0])
                pkt = jax.lax.ppermute(pkt, axis, rnd.pairs)
                # Groups with no inbound edge at this offset received zeros
                # from ppermute (id 0 is a real neuron): mask them invalid.
                ok = gadj[jnp.mod(my_g - rnd.offset, G), my_g]
                pkt = jnp.where(ok, pkt, invalid)
            received.append(pkt)

        tgt_f, w_f, d_f = self._inter_tables(net)
        to_local = self._global_to_local(a_loc, n_loc, net)
        ring_flat = kops.event_deliver_block(
            ring.reshape(a_loc * n_loc, r_len),
            jnp.concatenate(received, axis=1),
            tgt_f, w_f, d_f, t0, tgt_map=to_local)
        return (ring_flat.reshape(a_loc, n_loc, r_len), over,
                jnp.float32(self._window_wire))

    def _window_end_adaptive(self, ring, block, t0, net, gids):
        """The two-phase routed window: exact counts, then right-sized
        packets.

        Phase 1 ships the global ``[D, A]`` per-area spike-count table
        (``comm.gather_counts``) plus one scalar pmax -- from the table
        every device derives, identically, the *exact* packet need of the
        group assembly and of every rotation round's edges, so all bucket
        choices are branch-uniform and no packet can drop a spike (the
        ladders top out at the group population). Phase 2 assembles the
        group packet at the device bucket and re-compacts each round at its
        own edge bucket; each round scatters immediately (per-round
        ``event_deliver_block`` -- bit-identical to the static path's
        concatenated single scatter, grid-exact weights).
        """
        a_loc, n_loc, r_len = ring.shape
        A, n_pad = net.n_areas, net.n_pad
        G = self.routing.n_groups
        invalid = A * n_pad
        d_win = block.shape[0]
        gsz = self.gsz
        cap_dev = self.ladder_dev[-1]

        # -- phase 1: counts ------------------------------------------------
        counts_local = block.sum(axis=-1, dtype=jnp.int32)   # [D, A_loc]
        counts_all = comm.gather_counts(
            counts_local, area_axes=self.area_axes,
            subgroup_axis=self.subgroup)                     # [D, A]
        dev_need = comm.count_max(
            counts_local.sum(axis=-1).max(), self.all_axes)
        shipped = jnp.float32(
            comm.count_wire_bytes(d_win * A + 1, self.n_dev))

        # -- phase 2a: assemble the group packet at the device bucket -------
        def assemble(b):
            packets, _ = delivery_lib.compact_fired_block(
                block, gids, s_max=b, invalid=invalid)       # [D, b]
            gw = jax.lax.all_gather(
                packets, self.subgroup, axis=1, tiled=True)  # [D, gsz*b]
            # Pad each lane's slot out to the ladder cap so every bucket
            # branch returns the same [D, gsz*cap] shape (extra slots carry
            # the fill id, absorbed by the receive scatter).
            gw = gw.reshape(d_win, gsz, b)
            gw = jnp.pad(gw, ((0, 0), (0, 0), (0, cap_dev - b)),
                         constant_values=invalid)
            return gw.reshape(d_win, gsz * cap_dev)

        gwire = kops.ladder_switch(self.ladder_dev, dev_need, assemble)
        rung_dev = kops.ladder_rung(self.ladder_dev, dev_need)
        shipped = shipped + (
            jnp.float32(self.n_dev * (gsz - 1) * d_win * _I32_BYTES)
            * rung_dev.astype(jnp.float32))

        my_g = self._group_index()
        src_area = jnp.where(gwire < invalid, gwire // n_pad, A)
        proj = jnp.asarray(self._proj_const)                 # [A+1, G]
        gadj = jnp.asarray(self.routing.group_adj)           # [G, G]
        tgt_f, w_f, d_f = self._inter_tables(net)
        to_local = self._global_to_local(a_loc, n_loc, net)
        cg = counts_all.reshape(d_win, G, A // G)

        # -- phase 2b: one bucketed round per existing offset ---------------
        for rnd in self.routing.rounds:
            mask = jnp.asarray(self._round_masks[rnd.offset])  # [G, A/G]
            # Exact per-edge need: spikes of the areas projecting along
            # each edge at this offset, maxed over cycles and edges.
            need_r = (cg * mask[None]).sum(axis=-1).max()
            dst_g = jnp.mod(my_g + rnd.offset, G)
            keep = proj[src_area, dst_g]                     # [D, L]

            def round_fn(b, ring, rnd=rnd, keep=keep):
                pkt, _ = kops.compact_ids_block(
                    keep, gwire, size=b, fill_id=invalid)
                if rnd.offset:
                    axis = (self.area_axes if len(self.area_axes) > 1
                            else self.area_axes[0])
                    pkt = jax.lax.ppermute(pkt, axis, rnd.pairs)
                    ok = gadj[jnp.mod(my_g - rnd.offset, G), my_g]
                    pkt = jnp.where(ok, pkt, invalid)
                rf = kops.event_deliver_block(
                    ring.reshape(a_loc * n_loc, r_len), pkt,
                    tgt_f, w_f, d_f, t0, tgt_map=to_local)
                return rf.reshape(a_loc, n_loc, r_len)

            ring = kops.ladder_switch(
                self.ladder_edge, need_r, round_fn, ring)
            if rnd.offset:
                rung = kops.ladder_rung(self.ladder_edge, need_r)
                shipped = shipped + (
                    jnp.float32(len(rnd.pairs) * gsz * d_win * _I32_BYTES)
                    * rung.astype(jnp.float32))
        return ring, jnp.int32(0), shipped

    # -- overlapped pipeline split ------------------------------------------

    def start_window_end(self, block, t0, net, gids, *, blocked: bool):
        # All rotation rounds (collectives) run here; the received packets
        # keep their [D, s] row=cycle layout and concatenate along the id
        # axis into ONE in-flight wire, scattered by finish_window_end. The
        # leading size-1 axis is this group's slot of the global in-flight
        # state (the routed wire differs per group, unlike the dense one).
        del blocked
        t0 = jnp.asarray(t0, jnp.int32)
        d_win = block.shape[0]
        if net.k_inter == 0 or not self.routing.rounds:
            return (InflightWindow(jnp.zeros((1, d_win, 0), jnp.int32), t0),
                    jnp.int32(0), jnp.float32(0))
        if self.adaptive:
            return self._start_adaptive(block, t0, net, gids)
        A, n_pad = net.n_areas, net.n_pad
        G = self.routing.n_groups
        invalid = A * n_pad

        packets, counts = delivery_lib.compact_fired_block(
            block, gids, s_max=self.s_max_dev, invalid=invalid)
        over = jax.lax.psum(
            jnp.maximum(counts - self.s_max_dev, 0).sum(), self.all_axes)
        gwire = jax.lax.all_gather(
            packets, self.subgroup, axis=1, tiled=True)      # [D, gsz*s_dev]

        my_g = self._group_index()
        lane0 = jax.lax.axis_index(self.subgroup) == 0
        src_area = jnp.where(gwire < invalid, gwire // n_pad, A)
        proj = jnp.asarray(self._proj_const)                 # [A+1, G]
        gadj = jnp.asarray(self.routing.group_adj)           # [G, G]

        received = []
        for rnd in self.routing.rounds:
            dst_g = jnp.mod(my_g + rnd.offset, G)
            keep = proj[src_area, dst_g]                     # [D, L]
            pkt, cnt = kops.compact_ids_block(
                keep, gwire, size=rnd.s_max, fill_id=invalid)
            spill = jnp.maximum(cnt - rnd.s_max, 0).sum()
            over = over + jax.lax.psum(
                jnp.where(lane0, spill, 0), self.all_axes)
            if rnd.offset:
                axis = (self.area_axes if len(self.area_axes) > 1
                        else self.area_axes[0])
                pkt = jax.lax.ppermute(pkt, axis, rnd.pairs)
                ok = gadj[jnp.mod(my_g - rnd.offset, G), my_g]
                pkt = jnp.where(ok, pkt, invalid)
            received.append(pkt)
        wire = jnp.concatenate(received, axis=1)[None]       # [1, D, L]
        return (InflightWindow(wire, t0), over,
                jnp.float32(self._window_wire))

    def _start_adaptive(self, block, t0, net, gids):
        """Two-phase start: phase 1 + every bucketed round, no scatter.

        Identical collectives to ``_window_end_adaptive`` (the wire ships
        rung-sized packets), but each round's packet is padded out to the
        edge-ladder cap *after* the ppermute so all bucket branches share
        one static in-flight shape; the extra slots carry the fill id,
        which the deferred receive scatter absorbs bitwise.
        """
        A, n_pad = net.n_areas, net.n_pad
        G = self.routing.n_groups
        invalid = A * n_pad
        d_win = block.shape[0]
        gsz = self.gsz
        cap_dev = self.ladder_dev[-1]
        cap_edge = self.ladder_edge[-1]

        # -- phase 1: counts ------------------------------------------------
        counts_local = block.sum(axis=-1, dtype=jnp.int32)   # [D, A_loc]
        counts_all = comm.gather_counts(
            counts_local, area_axes=self.area_axes,
            subgroup_axis=self.subgroup)                     # [D, A]
        dev_need = comm.count_max(
            counts_local.sum(axis=-1).max(), self.all_axes)
        shipped = jnp.float32(
            comm.count_wire_bytes(d_win * A + 1, self.n_dev))

        # -- phase 2a: assemble the group packet at the device bucket -------
        def assemble(b):
            packets, _ = delivery_lib.compact_fired_block(
                block, gids, s_max=b, invalid=invalid)       # [D, b]
            gw = jax.lax.all_gather(
                packets, self.subgroup, axis=1, tiled=True)  # [D, gsz*b]
            gw = gw.reshape(d_win, gsz, b)
            gw = jnp.pad(gw, ((0, 0), (0, 0), (0, cap_dev - b)),
                         constant_values=invalid)
            return gw.reshape(d_win, gsz * cap_dev)

        gwire = kops.ladder_switch(self.ladder_dev, dev_need, assemble)
        rung_dev = kops.ladder_rung(self.ladder_dev, dev_need)
        shipped = shipped + (
            jnp.float32(self.n_dev * (gsz - 1) * d_win * _I32_BYTES)
            * rung_dev.astype(jnp.float32))

        my_g = self._group_index()
        src_area = jnp.where(gwire < invalid, gwire // n_pad, A)
        proj = jnp.asarray(self._proj_const)                 # [A+1, G]
        gadj = jnp.asarray(self.routing.group_adj)           # [G, G]
        cg = counts_all.reshape(d_win, G, A // G)

        # -- phase 2b: one bucketed round per existing offset ---------------
        received = []
        for rnd in self.routing.rounds:
            mask = jnp.asarray(self._round_masks[rnd.offset])  # [G, A/G]
            need_r = (cg * mask[None]).sum(axis=-1).max()
            dst_g = jnp.mod(my_g + rnd.offset, G)
            keep = proj[src_area, dst_g]                     # [D, L]

            def round_fn(b, rnd=rnd, keep=keep):
                pkt, _ = kops.compact_ids_block(
                    keep, gwire, size=b, fill_id=invalid)
                if rnd.offset:
                    axis = (self.area_axes if len(self.area_axes) > 1
                            else self.area_axes[0])
                    pkt = jax.lax.ppermute(pkt, axis, rnd.pairs)
                    ok = gadj[jnp.mod(my_g - rnd.offset, G), my_g]
                    pkt = jnp.where(ok, pkt, invalid)
                return jnp.pad(pkt, ((0, 0), (0, cap_edge - b)),
                               constant_values=invalid)

            received.append(
                kops.ladder_switch(self.ladder_edge, need_r, round_fn))
            if rnd.offset:
                rung = kops.ladder_rung(self.ladder_edge, need_r)
                shipped = shipped + (
                    jnp.float32(len(rnd.pairs) * gsz * d_win * _I32_BYTES)
                    * rung.astype(jnp.float32))
        wire = jnp.concatenate(received, axis=1)[None]       # [1, D, L]
        return InflightWindow(wire, t0), jnp.int32(0), shipped

    def finish_window_end(self, ring, inflight, net, gids, *, blocked: bool):
        # Collective-free: one blocked scatter of the concatenated rounds
        # (scatter-order independence makes it bit-identical to the
        # sequential path's per-round scatters; fill ids scatter nothing).
        del blocked, gids
        if net.k_inter == 0 or inflight.wire.shape[-1] == 0:
            return ring
        a_loc, n_loc, r_len = ring.shape
        tgt_f, w_f, d_f = self._inter_tables(net)
        to_local = self._global_to_local(a_loc, n_loc, net)
        ring_flat = kops.event_deliver_block(
            ring.reshape(a_loc * n_loc, r_len), inflight.wire[0],
            tgt_f, w_f, d_f, inflight.t0, tgt_map=to_local)
        return ring_flat.reshape(a_loc, n_loc, r_len)

    def init_inflight(self, net: Network) -> InflightWindow:
        d_win = max(net.delay_ratio, 1)
        if net.k_inter == 0 or not self.routing.rounds:
            width = 0
        elif self.adaptive:
            width = len(self.routing.rounds) * self.ladder_edge[-1]
        else:
            width = sum(rnd.s_max for rnd in self.routing.rounds)
        wire = jnp.full((self.n_groups, d_win, width),
                        net.n_areas * net.n_pad, jnp.int32)
        return InflightWindow(wire=wire, t0=jnp.int32(0))

    def inflight_pspecs(self) -> InflightWindow:
        from jax.sharding import PartitionSpec as P

        # The routed wire differs per device group: the global in-flight
        # state carries a leading group axis, sharded over the area axes
        # (local slice [1, D, L]); it is replicated over the subgroup axis.
        return InflightWindow(wire=P(self.area_axes, None, None), t0=P())

    def wire_bytes(self, net: Network) -> dict:
        rep = routed_wire_bytes(
            net, self.routing, backend=self.backend, gsz=self.gsz,
            headroom=self.headroom, floor=self.floor)
        rep["adaptive"] = adaptive_wire_bytes(
            net, backend=self.backend, schedule=STRUCTURE_AWARE,
            n_groups=self.n_groups, gsz=self.gsz,
            headroom=self.headroom, floor=self.floor, routing=self.routing)
        rep["adaptive_on"] = self.adaptive
        return rep


# ---------------------------------------------------------------------------
# Static wire accounting (mesh-total bytes received per window)
# ---------------------------------------------------------------------------


def _mesh_bounds(net: Network, *, n_groups, gsz, headroom, floor):
    s_max_area, s_max_all = delivery_lib.event_bounds(
        net, headroom=headroom, floor=floor)
    s_max_loc = max(floor, -(-s_max_area // gsz))
    s_max_dev = max(floor, -(-s_max_all // (n_groups * gsz)))
    return s_max_loc, s_max_dev


def dense_wire_bytes(
    net: Network, *, backend: str, schedule: str,
    n_groups: int, gsz: int, headroom: float = 8.0, floor: int = 16,
) -> dict:
    """Mesh-total received bytes per window of :class:`DenseMeshExchange`."""
    n_dev = n_groups * gsz
    d_win = net.delay_ratio
    A, n_pad = net.n_areas, net.n_pad
    s_max_loc, s_max_dev = _mesh_bounds(
        net, n_groups=n_groups, gsz=gsz, headroom=headroom, floor=floor)
    if schedule == CONVENTIONAL:
        n_loc = n_pad // n_dev
        if backend == "event":
            glob = n_dev * d_win * (n_dev - 1) * s_max_dev * _I32_BYTES
        else:
            glob = n_dev * d_win * A * (n_dev - 1) * -(-n_loc // 8)
        return dict(exchange="dense", schedule=schedule, backend=backend,
                    local_bytes=0, global_bytes=glob, total_bytes=glob)
    a_loc, n_loc = A // n_groups, n_pad // gsz
    per = -(-n_loc // 8)  # packed bytes per local spike-vector shard
    if net.k_intra == 0:
        local = 0
    elif backend == "event":
        local = n_dev * d_win * a_loc * (gsz - 1) * s_max_loc * _I32_BYTES
    else:
        local = n_dev * d_win * a_loc * (gsz - 1) * per
    if net.k_inter == 0:
        glob = 0
    elif backend == "event":
        glob = n_dev * d_win * (n_dev - 1) * s_max_dev * _I32_BYTES
    else:
        # gather_global: subgroup stage, then the area-axes stages.
        glob = n_dev * d_win * a_loc * per * (
            (gsz - 1) + (n_groups - 1) * gsz)
    return dict(exchange="dense", schedule=schedule, backend=backend,
                local_bytes=local, global_bytes=glob,
                total_bytes=local + glob)


def routed_wire_bytes(
    net: Network, routing: Routing, *, backend: str,
    gsz: int, headroom: float = 8.0, floor: int = 16,
) -> dict:
    """Mesh-total received bytes per window of :class:`RoutedExchange`.

    The local pathway is the dense structure-aware one; the global pathway is
    the subgroup assembly plus one ``[D, s_max]`` id packet per existing edge
    per subgroup lane -- offsets with no edge ship nothing at all.
    """
    n_groups = routing.n_groups
    n_dev = n_groups * gsz
    d_win = net.delay_ratio
    base = dense_wire_bytes(
        net, backend=backend, schedule=STRUCTURE_AWARE,
        n_groups=n_groups, gsz=gsz, headroom=headroom, floor=floor)
    _, s_max_dev = _mesh_bounds(
        net, n_groups=n_groups, gsz=gsz, headroom=headroom, floor=floor)
    if net.k_inter == 0:
        glob = 0
    else:
        assembly = n_dev * (gsz - 1) * d_win * s_max_dev * _I32_BYTES
        edges = sum(
            len(r.pairs) * gsz * d_win * r.s_max * _I32_BYTES
            for r in routing.rounds if r.offset != 0
        )
        glob = assembly + edges
    return dict(exchange="routed", schedule=STRUCTURE_AWARE, backend=backend,
                local_bytes=base["local_bytes"], global_bytes=glob,
                total_bytes=base["local_bytes"] + glob,
                rounds=routing.n_wire_rounds,
                dense_rounds=max(n_groups - 1, 0),
                edges=routing.n_edges)


def adaptive_wire_bytes(
    net: Network,
    *,
    backend: str,
    schedule: str = STRUCTURE_AWARE,
    n_groups: int,
    gsz: int,
    headroom: float = 8.0,
    floor: int = 16,
    routing: Routing | None = None,
) -> dict:
    """The adaptive two-phase exchange's byte model (pure shape arithmetic).

    Prices, per window: ``counts_bytes`` (the phase-1 count collectives),
    ``payload_bytes_expected`` (phase-2 packets sized by the rung an
    expectation-sized window lands on, :func:`repro.core.delivery
    .expected_bucket` -- the *typical*-window bytes; live runs report the
    actually-measured value in ``SimState.shipped_bytes``), and
    ``payload_bytes_worst`` (every ladder at its hard-cap top rung -- the
    bound that makes overflow impossible). ``saved_bytes`` is the
    expectation-window saving vs the static-bound path; ``applies=False``
    marks pathways with no id packets to size (the dense exchange's
    bit-packed backends), where the numbers simply restate the static case.
    Mirrors the runtime constants of the exchange hooks term for term, so
    modelled and measured bytes agree whenever counts sit on the modelled
    rung.
    """
    n_dev = n_groups * gsz
    d_win = net.delay_ratio
    A, n_pad = net.n_areas, net.n_pad
    exp_area = delivery_lib.expected_area_spikes(net)
    if routing is not None:
        static = routed_wire_bytes(
            net, routing, backend=backend, gsz=gsz,
            headroom=headroom, floor=floor)
    else:
        static = dense_wire_bytes(
            net, backend=backend, schedule=schedule, n_groups=n_groups,
            gsz=gsz, headroom=headroom, floor=floor)
    out = dict(
        exchange=static["exchange"], backend=backend, applies=False,
        static_total_bytes=static["total_bytes"], counts_bytes=0,
        payload_bytes_expected=static["total_bytes"],
        payload_bytes_worst=static["total_bytes"],
        total_bytes_expected=static["total_bytes"],
        saved_bytes=0, buckets={},
    )
    if routing is None and backend != "event":
        return out  # bit-packed dense wire: nothing to size adaptively
    out["applies"] = True
    buckets: dict = {}
    counts = 0
    payload_exp = 0
    payload_worst = 0
    if schedule == CONVENTIONAL:
        n_loc = n_pad // n_dev
        ladder = delivery_lib.bucket_ladder(floor, A * n_loc)
        b = delivery_lib.expected_bucket(ladder, exp_area * A / n_dev)
        buckets["device"] = b
        counts = d_win * comm.count_wire_bytes(1, n_dev)
        payload_exp = n_dev * d_win * (n_dev - 1) * b * _I32_BYTES
        payload_worst = n_dev * d_win * (n_dev - 1) * ladder[-1] * _I32_BYTES
    else:
        a_loc, n_loc = A // n_groups, n_pad // gsz
        if net.k_intra > 0 and backend == "event":
            ladder_loc = delivery_lib.bucket_ladder(floor, n_loc)
            bl = delivery_lib.expected_bucket(ladder_loc, exp_area / gsz)
            buckets["local"] = bl
            counts += d_win * comm.count_wire_bytes(1, n_dev)
            payload_exp += (n_dev * d_win * a_loc * (gsz - 1)
                            * bl * _I32_BYTES)
            payload_worst += (n_dev * d_win * a_loc * (gsz - 1)
                              * ladder_loc[-1] * _I32_BYTES)
        else:
            # The dense local pathway stays bit-packed (not adaptively
            # sized); restate its static bytes so totals remain comparable.
            payload_exp += static["local_bytes"]
            payload_worst += static["local_bytes"]
        if net.k_inter > 0:
            ladder_dev = delivery_lib.bucket_ladder(floor, a_loc * n_loc)
            if routing is None:
                bd = delivery_lib.expected_bucket(
                    ladder_dev, exp_area * A / n_dev)
                buckets["device"] = bd
                counts += comm.count_wire_bytes(1, n_dev)
                payload_exp += (n_dev * d_win * (n_dev - 1)
                                * bd * _I32_BYTES)
                payload_worst += (n_dev * d_win * (n_dev - 1)
                                  * ladder_dev[-1] * _I32_BYTES)
            else:
                G = routing.n_groups
                bd = delivery_lib.expected_bucket(
                    ladder_dev, exp_area * A / n_dev)
                buckets["assembly"] = bd
                counts += comm.count_wire_bytes(d_win * A + 1, n_dev)
                payload_exp += (n_dev * (gsz - 1) * d_win * bd * _I32_BYTES)
                payload_worst += (n_dev * (gsz - 1) * d_win
                                  * ladder_dev[-1] * _I32_BYTES)
                ladder_edge = delivery_lib.bucket_ladder(
                    floor, a_loc * n_pad)
                proj_r = routing.proj.reshape(G, A // G, G)
                round_buckets = {}
                for rnd in routing.rounds:
                    if rnd.offset == 0:
                        continue
                    n_src = max(int(proj_r[g, :, h].sum())
                                for g, h in rnd.pairs)
                    br = delivery_lib.expected_bucket(
                        ladder_edge, exp_area * n_src)
                    round_buckets[rnd.offset] = br
                    payload_exp += (len(rnd.pairs) * gsz * d_win
                                    * br * _I32_BYTES)
                    payload_worst += (len(rnd.pairs) * gsz * d_win
                                      * ladder_edge[-1] * _I32_BYTES)
                buckets["rounds"] = round_buckets
    out.update(
        counts_bytes=counts,
        payload_bytes_expected=payload_exp,
        payload_bytes_worst=payload_worst,
        total_bytes_expected=counts + payload_exp,
        saved_bytes=static["total_bytes"] - (counts + payload_exp),
        buckets=buckets,
    )
    return out


def inter_table_report(
    net: Network,
    *,
    n_groups: int,
    gsz: int,
    schedule: str = STRUCTURE_AWARE,
    headroom: float = 8.0,
    floor: int = 16,
    routing: Routing | None = None,
    subgroup: int = 1,
) -> dict:
    """Per-device inter receive-table bytes and receive-side scatter work,
    replicated vs sharded -- the static accounting of the sharded-table
    tentpole (pure shape arithmetic, no devices).

    ``table_bytes.replicated`` prices the legacy layout (every device holds
    the full ``[A * n_pad, K_out]`` outgoing tables,
    ``Network.bytes_per_synapse()`` B/synapse); ``table_bytes.sharded``
    prices the inbound slice one device keeps after
    :func:`repro.core.connectivity.shard_inter_tables` (one shard of the
    ``[S, A * n_pad, K_in]`` stack, or one ``[S, gsz, A * n_pad, K_in]``
    lane of the subgroup-sliced layout -- detected from the table rank, or
    requested via ``subgroup`` for the width-bound fallback). Widths come
    from the network's own tables when it carries them and fall back to the
    deterministic ``network_sds`` bounds otherwise, so the report matches
    what the dry-run lowers. ``receive`` counts synapse touches per device
    per window of the event receive scatter (ids scattered x table width):
    the id volume is unchanged by sharding -- the win is the ~S x narrower
    table each id fans out over. Feeds ``launch/dryrun.py``,
    ``benchmarks/bench_delivery.py`` and ``cost_model.receive_time_s``.
    """
    from repro.core import connectivity as connectivity_lib

    n_dev = n_groups * gsz
    d_win = net.delay_ratio
    rows = net.n_areas * net.n_pad
    n_shards = n_groups if schedule == STRUCTURE_AWARE else n_dev
    k_e = net.k_inter
    syn_b = net.bytes_per_synapse()
    if net.tgt_inter is not None:
        k_rep = net.tgt_inter.shape[-1]
    else:
        k_rep = connectivity_lib._outgoing_k_bound(k_e)
    if net.tgt_inter_in is not None:
        k_sh = net.tgt_inter_in.shape[-1]
        # [S, rows, K] -> S shards; [S, gsz, rows, K] -> S * gsz slices.
        n_shards = int(np.prod(net.tgt_inter_in.shape[:-2]))
    else:
        n_shards = n_shards * max(subgroup, 1)
        k_sh = connectivity_lib._inbound_k_bound(k_e, n_shards)
    bytes_rep = rows * k_rep * syn_b
    bytes_sh = rows * k_sh * syn_b
    _, s_max_dev = _mesh_bounds(
        net, n_groups=n_groups, gsz=gsz, headroom=headroom, floor=floor)
    # Ids scattered per device per window by each global pathway.
    ids = {"dense": d_win * n_dev * s_max_dev}
    if routing is not None:
        ids["routed"] = d_win * sum(r.s_max for r in routing.rounds)
    receive = {
        name: dict(
            ids_per_window=n,
            syn_touches_replicated=n * k_rep,
            syn_touches_sharded=n * k_sh,
        )
        for name, n in ids.items()
    }
    return dict(
        rows=rows,
        n_shards=n_shards,
        k_out_replicated=k_rep,
        k_in_sharded=k_sh,
        table_bytes=dict(
            replicated=bytes_rep,
            sharded=bytes_sh,
            reduction=bytes_rep / bytes_sh if bytes_sh else float("inf"),
        ),
        receive=receive,
    )


def priced_inter_table_report(
    net: Network,
    *,
    n_groups: int,
    gsz: int,
    schedule: str = STRUCTURE_AWARE,
    headroom: float = 8.0,
    floor: int = 16,
    routing: Routing | None = None,
    subgroup: int = 1,
) -> dict:
    """:func:`inter_table_report` with *both* table layouts priced from one
    network.

    A network normally carries one layout (replicated before
    ``shard_inter_tables`` / inbound after); the missing side would fall
    back to the deterministic width bound, whose per-shard slack
    misprices small configs. This instantiates the sharded slices from a
    replicated-only network (or their SDS bound for stand-ins) and
    re-attaches the replicated leaves, so every caller of the
    replicated-vs-sharded comparison (``benchmarks/bench_delivery.py``,
    ``launch/simulate.py --profile``, ``launch/dryrun.py``) prices the
    same thing the same way.
    """
    if (net.k_inter > 0 and net.tgt_inter is not None
            and net.tgt_inter_in is None):
        from repro.core import connectivity as connectivity_lib

        n_shards = n_groups if schedule == STRUCTURE_AWARE else n_groups * gsz
        mode = "group" if schedule == STRUCTURE_AWARE else "window"
        sharded = connectivity_lib.shard_inter_tables(
            net, n_shards, mode=mode,
            subgroup=subgroup if mode == "group" else 1)
        net = dataclasses.replace(
            sharded, tgt_inter=net.tgt_inter, wout_inter=net.wout_inter,
            dout_inter=net.dout_inter)
    return inter_table_report(
        net, n_groups=n_groups, gsz=gsz, schedule=schedule,
        headroom=headroom, floor=floor, routing=routing, subgroup=subgroup)


def wire_report(
    net: Network,
    adjacency: np.ndarray,
    *,
    backend: str,
    n_groups: int,
    gsz: int,
    headroom: float = 8.0,
    floor: int = 16,
) -> dict:
    """Dense-vs-routed wire volume for a hypothetical ``n_groups x gsz``
    mesh -- pure static accounting, no devices required. Feeds
    ``benchmarks/bench_delivery.py`` and ``simulate.py --profile``.

    Each entry carries *both* sizings: the top-level fields are the static
    worst case (fixed ``s_max`` packets -- what a non-adaptive run always
    ships), and ``["adaptive"]`` is the two-phase model
    (:func:`adaptive_wire_bytes`: phase-1 count bytes + expectation-sized
    payload + hard-cap worst case), so dry-run and benchmark rows stay
    honest when ``EngineConfig.adaptive_exchange`` is on. Live runs report
    the measured value in ``SimState.shipped_bytes``.
    """
    exp_area = delivery_lib.expected_area_spikes(net)
    routing = build_routing(
        adjacency, n_groups, exp_area_spikes=exp_area,
        headroom=headroom, floor=floor)
    dense = dense_wire_bytes(
        net, backend=backend, schedule=STRUCTURE_AWARE,
        n_groups=n_groups, gsz=gsz, headroom=headroom, floor=floor)
    dense["adaptive"] = adaptive_wire_bytes(
        net, backend=backend, schedule=STRUCTURE_AWARE,
        n_groups=n_groups, gsz=gsz, headroom=headroom, floor=floor)
    routed = routed_wire_bytes(
        net, routing, backend=backend, gsz=gsz,
        headroom=headroom, floor=floor)
    routed["adaptive"] = adaptive_wire_bytes(
        net, backend=backend, schedule=STRUCTURE_AWARE,
        n_groups=n_groups, gsz=gsz, headroom=headroom, floor=floor,
        routing=routing)
    return dict(dense=dense, routed=routed)
