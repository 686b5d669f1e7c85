"""Network instantiation: fixed-indegree connectivity with tiered delays.

NEST stores connections in per-thread *connection/source/target tables* and the
structure-aware implementation duplicates them into short-range and long-range
variants (paper §4.1.2, Fig. 10). The TPU-native rethink keeps the same split
but replaces pointer-chasing tables with rectangular tensors:

* intra-area synapses of area ``a``:  ``src_intra[a, n, k]`` (index *within*
  the area), ``w_intra[a, n, k]``, ``delay_intra[a, n, k]`` (steps).
* inter-area synapses: ``src_inter[a, n, k]`` holds *global* source ids
  (``area * n_pad + index``), with delays ``>= D`` steps (the paper's
  ``d_min_inter`` cutoff).

Areas are padded to a common ``n_pad`` ('ghost neurons', §4.1.1); the
``alive`` mask freezes the padding. Weights are drawn on a 1/256 grid --
every sum of such weights below 2^23/256 is exactly representable in f32, so
ring-buffer accumulation is associative-exact and the two communication
schedules (and all four delivery backends) are bit-identical.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import os
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.areas import MultiAreaSpec
from repro.core.partition import shard_pathway_rows

__all__ = [
    "Network",
    "build_network",
    "network_sds",
    "area_adjacency",
    "shard_inter_tables",
    "draw_pathway_rows",
    "ShardedBuildPlan",
    "sharded_build_plan",
    "cached_sharded_build_plan",
    "plan_cache_key",
    "build_shard_tables",
    "build_group_intra_tables",
    "build_lane_intra_tables",
    "construction_cost_model",
    "tile_network",
    "tile_gids",
]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Network:
    """Instantiated multi-area network (a pytree of arrays).

    Shapes: ``A`` areas, ``n_pad`` padded neurons per area, ``K_i``/``K_e``
    intra-/inter-area in-degrees.
    """

    # [A, n_pad] bool -- live-neuron mask (False = ghost/frozen neuron).
    alive: jax.Array
    # [A, n_pad] f32 -- per-neuron target rate (drive/emission), Hz.
    rate_hz: jax.Array
    # intra-area synapses ---------------------------------------------------
    # Delays are stored int8 whenever the spec's step cutoffs fit in [1, 127]
    # (the production MAM tops out at steps_inter_max=100) and widened to
    # int32 only at the gather/deposit sites -- a third off every synapse's
    # delay bytes. Tables fall back to int32 for exotic specs.
    src_intra: jax.Array    # [A, n_pad, K_i] int32, index within the same area
    w_intra: jax.Array      # [A, n_pad, K_i] f32
    delay_intra: jax.Array  # [A, n_pad, K_i] int8/int32, steps in [1, steps_intra_max]
    # inter-area synapses ---------------------------------------------------
    src_inter: jax.Array    # [A, n_pad, K_e] int32, global id = area * n_pad + idx
    w_inter: jax.Array      # [A, n_pad, K_e] f32
    delay_inter: jax.Array  # [A, n_pad, K_e] int8/int32, steps in [D, steps_inter_max]

    # Optional *outgoing* adjacency (event-driven delivery, see
    # kernels/ops.event_deliver): per source neuron, padded target lists.
    # Built by build_network(outgoing=True); None otherwise.
    tgt_intra: jax.Array | None = None   # [A, n_pad, K_out_i] target idx in area
    wout_intra: jax.Array | None = None
    dout_intra: jax.Array | None = None
    tgt_inter: jax.Array | None = None   # [A, n_pad, K_out_e] global target ids
    wout_inter: jax.Array | None = None
    dout_inter: jax.Array | None = None

    # *Sharded* inbound inter-area tables (the distributed event/routed
    # receive path, see :func:`shard_inter_tables`): the replicated
    # ``tgt_inter`` table re-cut into per-target-shard slices. Row layout
    # ``[S, A * n_pad, K_in]``: shard ``s`` of the leading axis holds, for
    # every *source* row (global id order -- so rows are naturally grouped
    # by source device group), only the outgoing synapses whose target
    # lives in shard ``s``. Targets stay global ids (the receive side's
    # ``tgt_map`` remaps them exactly as for the replicated table), padded
    # with -1 / weight 0. ``K_in`` ~= K_out / S, so each device holds
    # ~1/S of the replicated table bytes.
    #
    # With ``subgroup > 1`` the tables are additionally sliced over the
    # within-group neuron-window axis: ``[S, gsz, A * n_pad, K_in]`` where
    # lane ``l`` of group ``s`` keeps only the synapses landing in its own
    # ``n_pad / gsz`` window of each owned area -- each *device* (not just
    # each group) holds ~1/(S * gsz) of the inter edges, and ``K_in``
    # shrinks another ~gsz x.
    tgt_inter_in: jax.Array | None = None   # [S(, gsz), A*n_pad, K_in] int32
    wout_inter_in: jax.Array | None = None  # [S(, gsz), A*n_pad, K_in] f32
    dout_inter_in: jax.Array | None = None  # [S(, gsz), A*n_pad, K_in] int8/int32

    # static metadata (ints are fine as static fields of the dataclass pytree)
    n_pad: int = dataclasses.field(metadata=dict(static=True), default=0)
    n_areas: int = dataclasses.field(metadata=dict(static=True), default=0)
    ring_len: int = dataclasses.field(metadata=dict(static=True), default=0)
    delay_ratio: int = dataclasses.field(metadata=dict(static=True), default=1)
    dt_ms: float = dataclasses.field(metadata=dict(static=True), default=0.1)
    # Per-pathway delay windows, computed at build time from the actual delay
    # draws: all intra delays live in [steps_lo_intra, steps_lo_intra +
    # r_span_intra) and likewise for inter. Delay-resolved delivery (the
    # Pallas backend, see core/delivery.py) iterates only over this window
    # instead of the full ring -- the short/long pathway split of §4.1.2 is
    # what keeps each window narrow. r_span == 0 means "no synapses".
    steps_lo_intra: int = dataclasses.field(metadata=dict(static=True), default=1)
    r_span_intra: int = dataclasses.field(metadata=dict(static=True), default=0)
    steps_lo_inter: int = dataclasses.field(metadata=dict(static=True), default=1)
    r_span_inter: int = dataclasses.field(metadata=dict(static=True), default=0)
    # How the ``*_inter_in`` tables slice their targets (see
    # :func:`shard_inter_tables`): '' (no sharded tables), 'group' (shard =
    # device area group, the structure-aware placement) or 'window' (shard =
    # within-area neuron window, the conventional round-robin placement).
    # Static so engine assembly can validate the tables match the mesh.
    inter_shard_mode: str = dataclasses.field(
        metadata=dict(static=True), default="")
    # The *realised* area->area adjacency as nested tuples (hashable, so it
    # can ride along as static metadata): ``area_adj[src][tgt]`` truthy iff
    # any neuron of target area ``tgt`` drew a source from area ``src``.
    # Set by the sharded (host-free) build path, where the dense incoming
    # ``src_inter`` tensors :func:`area_adjacency` would otherwise inspect
    # are zero-row stand-ins; ``None`` means "inspect the tensors/spec".
    area_adj: tuple | None = dataclasses.field(
        metadata=dict(static=True), default=None)

    @property
    def k_intra(self) -> int:
        return self.src_intra.shape[-1]

    @property
    def live_window(self) -> int:
        """Width W of the superstep's live window buffer (static).

        Relative slots [0, D) are the window's own input columns; intra
        deposits (delay <= steps_lo + r_span - 1) reach at most slot
        D - 1 + max_intra_delay, so W = D + max_intra_delay makes every
        within-window slot index wrap-free. Shared by both engines -- the
        single source of truth for the window-width formula.
        """
        if self.k_intra == 0:
            return self.delay_ratio
        return self.delay_ratio + self.steps_lo_intra + self.r_span_intra - 1

    @property
    def k_inter(self) -> int:
        return self.src_inter.shape[-1]

    @property
    def n_total_padded(self) -> int:
        return self.n_areas * self.n_pad

    def bytes_per_synapse(self) -> int:
        # src/tgt int32 + weight f32 + the delay table's own dtype: int8 (9
        # B/syn) whenever the spec's step cutoffs fit [1, 127] -- every
        # production config -- int32 (12 B/syn) otherwise. Delays widen to
        # int32 only at the gather sites.
        return 8 + np.dtype(self.delay_inter.dtype).itemsize

    def synapse_count(self) -> int:
        return int(
            self.alive.sum() * (self.k_intra + self.k_inter)
        )


def _outgoing_k_bound(k: int) -> int:
    """Deterministic upper estimate of ``build_network``'s outgoing row width.

    The real ``K_out`` is the maximum in-edge count over source neurons --
    data-dependent, concentrated around the in-degree ``k`` with Poisson
    fluctuations. The dry-run only needs a shape of the right order to lower
    and compile, so we take mean + ~6 sigma (+ slack for tiny ``k``).
    """
    import math

    if k <= 0:
        return 0
    return int(k + math.ceil(6.0 * math.sqrt(k)) + 8)


def _delay_dtype(hi_steps: int):
    """The narrowest delay-table dtype covering ``[1, hi_steps]``.

    int8 whenever the pathway's step cutoff fits in 127 (the production MAM
    tops out at ``steps_inter_max=100``); int32 otherwise. Every consumer
    widens to int32 at its gather/deposit site, so the choice is pure
    storage layout -- trajectories are bitwise identical either way.
    """
    return np.int8 if hi_steps <= 127 else np.int32


def _inbound_k_bound(k: int, n_shards: int) -> int:
    """Deterministic upper estimate of one shard's inbound row width.

    A source's outgoing inter-area synapses spread ~uniformly over the
    target shards, so the per-(source row, shard) count concentrates around
    ``k / n_shards`` with Poisson fluctuations -- but the max is now taken
    over ``n_shards`` x more cells than :func:`_outgoing_k_bound` covers,
    so the slack is a little wider (+6 sigma + 16). The dry-run lowers with
    this bound; instantiated widths are data-dependent and smaller.
    """
    import math

    if k <= 0 or n_shards <= 0:
        return 0
    k_s = -(-k // n_shards)  # ceil
    return int(k_s + math.ceil(6.0 * math.sqrt(k_s)) + 16)


def network_sds(
    spec: MultiAreaSpec,
    *,
    size_multiple: int = 1,
    outgoing: bool = False,
    inter_shards: int = 0,
    inter_shard_mode: str = "group",
    subgroup: int = 1,
) -> Network:
    """ShapeDtypeStruct stand-in for :func:`build_network` (no allocation).

    The production-scale MAM has ~25 billion synapses (~300 GB of
    connectivity tensors) -- far beyond this host. The dry-run only needs
    shapes/dtypes to lower and compile, so this constructs the Network pytree
    with ShapeDtypeStruct leaves, mirroring build_network -- including, with
    ``outgoing=True``, the inverted ``tgt_*/wout_*/dout_*`` tables the event
    backend (and the routed exchange's global pathway) scatter through, so
    ``launch/dryrun.py`` can lower those paths at production scale. The
    outgoing row width is the deterministic bound of
    :func:`_outgoing_k_bound` (the instantiated width is data-dependent).

    ``inter_shards > 0`` mirrors :func:`shard_inter_tables` instead: the
    stand-in carries the ``[S, A * n_pad, K_in]`` *inbound* inter tables
    (width bound :func:`_inbound_k_bound`) and no replicated inter tables,
    so the dry-run lowers -- and its memory analysis prices -- the sharded
    receive path at production scale. ``subgroup > 1`` additionally slices
    the inbound stand-in over the within-group neuron-window axis
    (``[S, subgroup, A * n_pad, K_in]``, width bound over ``S * subgroup``
    effective shards), matching ``shard_inter_tables(subgroup=)`` -- and
    the outgoing intra tables the same way (``[subgroup, A, n_pad,
    K_lane]``, matching :func:`slice_intra_tables`), since their
    lane-replication otherwise dominates the event path's per-device HBM.
    """
    import jax

    A = spec.n_areas
    n_pad = spec.padded_area_size(size_multiple)
    K_i, K_e = spec.k_intra, spec.k_inter
    dt_i = _delay_dtype(spec.steps_intra_max)
    dt_e = _delay_dtype(spec.steps_inter_max)
    s = jax.ShapeDtypeStruct
    out: dict = {}
    if outgoing:
        if subgroup > 1:
            # Subgroup-sliced outgoing intra tables
            # (:func:`slice_intra_tables`): [gsz, A, n_pad, K_lane], the
            # leading lane axis sharded over the subgroup so the local
            # pathway's tables stop being lane-replicated.
            k_li = _inbound_k_bound(K_i, subgroup)
            out.update(
                tgt_intra=s((subgroup, A, n_pad, k_li), jnp.int32),
                wout_intra=s((subgroup, A, n_pad, k_li), jnp.float32),
                dout_intra=s((subgroup, A, n_pad, k_li), dt_i),
            )
        else:
            k_oi = _outgoing_k_bound(K_i)
            out.update(
                tgt_intra=s((A, n_pad, k_oi), jnp.int32),
                wout_intra=s((A, n_pad, k_oi), jnp.float32),
                dout_intra=s((A, n_pad, k_oi), dt_i),
            )
        if K_e > 0 and inter_shards > 0:
            if subgroup > 1 and inter_shard_mode != "group":
                raise ValueError(
                    "subgroup slicing applies to the 'group' mode only "
                    "(the 'window' mode is already per-device)")
            k_ie = _inbound_k_bound(K_e, inter_shards * max(subgroup, 1))
            lead = ((inter_shards, subgroup) if subgroup > 1
                    else (inter_shards,))
            out.update(
                tgt_inter_in=s((*lead, A * n_pad, k_ie), jnp.int32),
                wout_inter_in=s((*lead, A * n_pad, k_ie), jnp.float32),
                dout_inter_in=s((*lead, A * n_pad, k_ie), dt_e),
                inter_shard_mode=inter_shard_mode,
            )
        elif K_e > 0:
            k_oe = _outgoing_k_bound(K_e)
            out.update(
                tgt_inter=s((A, n_pad, k_oe), jnp.int32),
                wout_inter=s((A, n_pad, k_oe), jnp.float32),
                dout_inter=s((A, n_pad, k_oe), dt_e),
            )
    return Network(
        alive=s((A, n_pad), jnp.bool_),
        rate_hz=s((A, n_pad), jnp.float32),
        src_intra=s((A, n_pad, K_i), jnp.int32),
        w_intra=s((A, n_pad, K_i), jnp.float32),
        delay_intra=s((A, n_pad, K_i), dt_i),
        src_inter=s((A, n_pad, K_e), jnp.int32),
        w_inter=s((A, n_pad, K_e), jnp.float32),
        delay_inter=s((A, n_pad, K_e), dt_e),
        n_pad=n_pad,
        n_areas=A,
        ring_len=spec.ring_len,
        delay_ratio=spec.delay_ratio,
        dt_ms=spec.dt_ms,
        # No delay draws to inspect: use the spec's tier cutoffs (a superset
        # of any instantiated window, so lowering covers the real kernel).
        steps_lo_intra=1,
        r_span_intra=spec.steps_intra_max if K_i > 0 else 0,
        steps_lo_inter=spec.steps_inter_min,
        r_span_inter=(spec.steps_inter_max - spec.steps_inter_min + 1)
        if K_e > 0 else 0,
        **out,
    )


def _quantize_weights(w: np.ndarray, grid: float = 1.0 / 256.0) -> np.ndarray:
    """Snap weights onto an exactly-representable grid (see module docstring)."""
    return np.round(w / grid) * grid


# ---------------------------------------------------------------------------
# Counter-based draws: every synapse attribute is a pure function of
# (seed, pathway tag, flat synapse index), where the flat index is
# ``global_target_row * K + k``. Any subset of target rows therefore
# regenerates *exactly* the values the full build would have drawn for them
# -- the init-sharding property the host-free construction path relies on
# (each shard draws only its own rows; no sequential RNG stream to replay).
# The mixer mirrors ``repro.core.neuron._splitmix32`` (the drive's
# counter-based RNG) in numpy.
# ---------------------------------------------------------------------------

# Per-draw-site domain tags: each (tag, index) pair is hashed independently,
# so e.g. a synapse's source pick and its weight magnitude are uncorrelated.
_TAG_SRC_INTRA = 1
_TAG_SRC_AREA = 2
_TAG_SRC_IDX = 3
_TAG_W_INTRA = 4
_TAG_W_INTER = 5
_TAG_D_INTRA_U1 = 6
_TAG_D_INTRA_U2 = 7
_TAG_D_INTER_U1 = 8
_TAG_D_INTER_U2 = 9


def _np_mix32(x: np.ndarray) -> np.ndarray:
    """numpy mirror of ``neuron._splitmix32`` (uint32 wraparound arithmetic)."""
    x = x.astype(np.uint32, copy=True)
    x += np.uint32(0x9E3779B9)
    x = (x ^ (x >> np.uint32(16))) * np.uint32(0x21F0AAAD)
    x = (x ^ (x >> np.uint32(15))) * np.uint32(0x735A2D97)
    return x ^ (x >> np.uint32(15))


def _counter_hash(seed: int, tag: int, idx: np.ndarray) -> np.ndarray:
    """uint32 hash of (seed, tag, flat synapse index).

    ``idx`` may exceed 2^32 (production: 4.2M rows x 4200 K), so it is
    folded in as two uint32 words through chained mixes.
    """
    idx = np.asarray(idx, dtype=np.uint64)
    lo = (idx & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (idx >> np.uint64(32)).astype(np.uint32)
    s0 = np.uint32((int(seed) + int(tag) * 0x85EBCA6B) & 0xFFFFFFFF)
    return _np_mix32(_np_mix32(_np_mix32(lo + s0) + hi))


def _counter_uniform(seed: int, tag: int, idx: np.ndarray) -> np.ndarray:
    """Uniform draw strictly inside (0, 1) (Box-Muller-safe: log never sees 0)."""
    h = _counter_hash(seed, tag, idx)
    return (h.astype(np.float64) + 0.5) * (2.0 ** -32)


def _flat_idx(rows: np.ndarray, k: int) -> np.ndarray:
    """[R, k] uint64 flat synapse indices ``row * k + j`` for global rows."""
    return (np.asarray(rows, dtype=np.uint64)[:, None] * np.uint64(k)
            + np.arange(k, dtype=np.uint64)[None, :])


def _counter_weights(
    spec: MultiAreaSpec,
    seed: int,
    tag: int,
    idx: np.ndarray,
    src_idx_within_area: np.ndarray,
    sizes_of_src: np.ndarray,
) -> np.ndarray:
    """80/20 excitatory/inhibitory by source index, on the 1/256 grid."""
    exc = src_idx_within_area < np.maximum(
        1, (spec.exc_fraction * sizes_of_src).astype(np.int64))
    u = _counter_uniform(seed, tag, idx)
    mag = _quantize_weights((0.5 + u) * spec.w_exc).astype(np.float32)
    return np.where(exc, mag, -spec.g * mag).astype(np.float32)


def _counter_delays(
    seed: int,
    tag_u1: int,
    tag_u2: int,
    idx: np.ndarray,
    mean_ms: float,
    std_ms: float,
    lo_steps: int,
    hi_steps: int,
    dt_ms: float,
) -> np.ndarray:
    """Gaussian delays on the dt grid with [lo, hi] cutoffs (paper §4.2),
    via Box-Muller over two independent counter-uniform draws."""
    u1 = _counter_uniform(seed, tag_u1, idx)
    u2 = _counter_uniform(seed, tag_u2, idx)
    z = np.sqrt(-2.0 * np.log(u1)) * np.cos(2.0 * np.pi * u2)
    d = (mean_ms + std_ms * z) / dt_ms
    return np.clip(np.round(d), lo_steps, hi_steps).astype(_delay_dtype(hi_steps))


def _allowed_source_areas(spec: MultiAreaSpec):
    """Padded per-target-area source-area lists from the spec adjacency.

    ``(allowed[A, max_deg] int64, n_allowed[A] int64)`` -- row ``a`` lists
    the areas allowed to project into ``a`` (garbage past ``n_allowed[a]``).
    """
    adj = spec.adjacency_matrix()
    A = spec.n_areas
    n_allowed = adj.sum(axis=0).astype(np.int64)
    allowed = np.zeros((A, max(int(n_allowed.max(initial=0)), 1)), np.int64)
    for a in range(A):
        srcs = np.flatnonzero(adj[:, a])
        allowed[a, : len(srcs)] = srcs
    return allowed, n_allowed


def _intra_src_rows(spec, seed, rows, n_pad, sizes) -> np.ndarray:
    """[R, K_i] int32 within-area source indices for global target rows."""
    rows = np.asarray(rows, dtype=np.int64)
    idx = _flat_idx(rows, spec.k_intra)
    sz = sizes.astype(np.int64)[rows // n_pad][:, None]
    h = _counter_hash(seed, _TAG_SRC_INTRA, idx)
    return (h.astype(np.int64) % sz).astype(np.int32)


def _intra_delay_rows(spec, seed, rows) -> np.ndarray:
    idx = _flat_idx(np.asarray(rows, np.int64), spec.k_intra)
    return _counter_delays(
        seed, _TAG_D_INTRA_U1, _TAG_D_INTRA_U2, idx,
        spec.delay_intra_mean_ms, spec.delay_intra_std_ms,
        1, spec.steps_intra_max, spec.dt_ms)


def _intra_rows(spec, seed, rows, n_pad, sizes):
    """(src, w, delay) intra-area tables [R, K_i] for global target rows."""
    rows = np.asarray(rows, dtype=np.int64)
    R, K_i = len(rows), spec.k_intra
    if K_i == 0:
        return (np.zeros((R, 0), np.int32), np.zeros((R, 0), np.float32),
                np.zeros((R, 0), _delay_dtype(spec.steps_intra_max)))
    src = _intra_src_rows(spec, seed, rows, n_pad, sizes)
    sz = sizes.astype(np.int64)[rows // n_pad][:, None]
    w = _counter_weights(
        spec, seed, _TAG_W_INTRA, _flat_idx(rows, K_i),
        src.astype(np.int64), sz)
    return src, w, _intra_delay_rows(spec, seed, rows)


def _inter_src_rows(spec, seed, rows, n_pad, sizes, allowed, n_allowed):
    """[R, K_e] int32 global source ids (``area * n_pad + idx``)."""
    rows = np.asarray(rows, dtype=np.int64)
    a_of = rows // n_pad
    idx = _flat_idx(rows, spec.k_inter)
    pick = (_counter_hash(seed, _TAG_SRC_AREA, idx).astype(np.int64)
            % n_allowed[a_of][:, None])
    src_area = np.take_along_axis(allowed[a_of], pick, axis=1)
    src_idx = (_counter_hash(seed, _TAG_SRC_IDX, idx).astype(np.int64)
               % sizes.astype(np.int64)[src_area])
    return (src_area * n_pad + src_idx).astype(np.int32)


def _inter_delay_rows(spec, seed, rows) -> np.ndarray:
    idx = _flat_idx(np.asarray(rows, np.int64), spec.k_inter)
    return _counter_delays(
        seed, _TAG_D_INTER_U1, _TAG_D_INTER_U2, idx,
        spec.delay_inter_mean_ms, spec.delay_inter_std_ms,
        spec.steps_inter_min, spec.steps_inter_max, spec.dt_ms)


def _inter_rows(spec, seed, rows, n_pad, sizes, allowed=None, n_allowed=None):
    """(src, w, delay) inter-area tables [R, K_e] for global target rows."""
    rows = np.asarray(rows, dtype=np.int64)
    R, K_e = len(rows), spec.k_inter
    if K_e == 0:
        return (np.zeros((R, 0), np.int32), np.zeros((R, 0), np.float32),
                np.zeros((R, 0), _delay_dtype(spec.steps_inter_max)))
    if allowed is None:
        allowed, n_allowed = _allowed_source_areas(spec)
    src = _inter_src_rows(spec, seed, rows, n_pad, sizes, allowed, n_allowed)
    src_area = src.astype(np.int64) // n_pad
    src_idx = src.astype(np.int64) % n_pad
    w = _counter_weights(
        spec, seed, _TAG_W_INTER, _flat_idx(rows, K_e),
        src_idx, sizes.astype(np.int64)[src_area])
    return src, w, _inter_delay_rows(spec, seed, rows)


def draw_pathway_rows(
    spec: MultiAreaSpec,
    seed: int,
    rows: np.ndarray,
    *,
    pathway: str,
    size_multiple: int = 1,
):
    """Counter-based (src, w, delay) draws for the given *global* target rows.

    The row-subset identity that makes construction shardable: for any
    subset (in any order) of ``arange(A * n_pad)``, the returned ``[R, K]``
    tables equal the corresponding rows of :func:`build_network`'s global
    tensors, bitwise -- each synapse is a pure function of
    ``(seed, pathway, row, k)``, never of which other rows were drawn.
    ``pathway`` is ``'intra'`` (src = index within the target's area) or
    ``'inter'`` (src = global id ``area * n_pad + idx``).
    """
    n_pad = spec.padded_area_size(size_multiple)
    sizes = spec.area_sizes()
    rows = np.asarray(rows, dtype=np.int64)
    if pathway == "intra":
        return _intra_rows(spec, seed, rows, n_pad, sizes)
    if pathway == "inter":
        return _inter_rows(spec, seed, rows, n_pad, sizes)
    raise ValueError(f"unknown pathway {pathway!r} ('intra' | 'inter')")


# Synapses per task of the host build's threaded draws and inversion: small
# enough that a task's temporaries stay in cache, large enough that numpy's
# per-call overhead is small against the work.
_DRAW_TASK_SYNAPSES = 1 << 18
_MAX_BUILD_THREADS = 16


def _build_pool() -> concurrent.futures.ThreadPoolExecutor:
    """Threads for the host build (numpy releases the GIL in its loops)."""
    cores = (len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity")
             else os.cpu_count() or 1)
    return concurrent.futures.ThreadPoolExecutor(
        min(_MAX_BUILD_THREADS, cores))


def _draw_parallel(pool, draw, rows: np.ndarray, k: int):
    """``draw(rows)`` over row chunks on ``pool``, concatenated: bitwise the
    one call, as each synapse is a pure function of its own row."""
    step = max(1, _DRAW_TASK_SYNAPSES // max(k, 1))
    if len(rows) <= step:
        return draw(rows)
    drawn = list(pool.map(draw, [rows[i:i + step]
                                 for i in range(0, len(rows), step)]))
    return tuple(np.concatenate(x) for x in zip(*drawn))


def _invert_adjacency(
    src: np.ndarray,      # [N_tgt, K] source ids (within some id space)
    w: np.ndarray,        # [N_tgt, K]
    d: np.ndarray,        # [N_tgt, K]
    n_src: int,
    tgt_base: int = 0,
    tgt_ids: np.ndarray | None = None,   # [N_tgt] explicit target ids
    pool: concurrent.futures.Executor | None = None,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Incoming [N_tgt, K] tables -> outgoing padded [n_src, K_out_max].

    Rows are padded with target id ``-1`` / weight 0 (event_deliver masks
    weight-0 entries into the absorbing row). Target ids default to
    ``arange(N_tgt) + tgt_base``; ``tgt_ids`` overrides them for
    non-contiguous target selections (the per-shard inbound slices of
    :func:`shard_inter_tables`). ``pool`` (optional) places the entries in
    slices of the sorted order on its threads.
    """
    n_tgt, k = src.shape
    flat_src = src.reshape(-1)
    # A stable sort of 16-bit keys is a radix sort: the same order, ~4x
    # sooner than on int32 keys.
    keys = flat_src.astype(np.uint16) if n_src <= 1 << 16 else flat_src
    order = np.argsort(keys, kind="stable")
    counts = np.bincount(flat_src, minlength=n_src)
    k_out = int(counts.max()) if counts.size else 0
    tgt = np.full((n_src, k_out), -1, dtype=np.int32)
    wout = np.zeros((n_src, k_out), dtype=np.float32)
    # Preserve the incoming delay dtype (int8 narrow tables stay narrow).
    dout = np.ones((n_src, k_out), dtype=d.dtype)
    if tgt_ids is None:
        tgt_ids = np.arange(n_tgt, dtype=np.int64) + tgt_base
    tgt_ids = np.asarray(tgt_ids, dtype=np.int64).astype(np.int32)
    starts = np.zeros(n_src + 1, dtype=np.int64)
    np.cumsum(counts, out=starts[1:])
    w_flat, d_flat = w.reshape(-1), d.reshape(-1)

    def place(lo: int, hi: int) -> None:
        # Entry j of the sorted order goes to its source's row, at its
        # position within that source's run.
        o = order[lo:hi]
        s = flat_src[o].astype(np.int64)
        dest = s * k_out + (np.arange(lo, hi) - starts[s])
        tgt.reshape(-1)[dest] = tgt_ids[o // k]
        wout.reshape(-1)[dest] = w_flat[o]
        dout.reshape(-1)[dest] = d_flat[o]

    step = _DRAW_TASK_SYNAPSES
    spans = [(i, min(i + step, len(order)))
             for i in range(0, len(order), step)]
    if pool is None:
        for lo, hi in spans:
            place(lo, hi)
    else:
        list(pool.map(lambda span: place(*span), spans))
    return tgt, wout, dout


def build_network(
    spec: MultiAreaSpec,
    *,
    seed: int = 12,
    size_multiple: int = 1,
    outgoing: bool | str = False,
) -> Network:
    """Instantiate the connectivity tensors for ``spec``.

    Connectivity generation is deterministic in ``seed`` (the paper runs seeds
    {12, 654, 91856}); every synapse attribute is a *counter-based* pure
    function of ``(seed, pathway, global target row, k)`` (see
    :func:`draw_pathway_rows`), so this host build is definitionally
    bitwise-identical to generating any partition of the rows shard-locally
    (:func:`build_shard_tables` and friends) -- construction is a separate
    phase from state propagation, exactly as in the reference code.

    ``size_multiple`` rounds the padded per-area size up so that device
    sharding (e.g. 16-way model parallel) and VMEM tiling divide evenly.
    ``outgoing`` builds the inverted target tables: ``True`` for both
    pathways, ``'intra'`` for the intra tier only -- the cheap subset that
    suffices when the inter receive path uses the *inbound* slices of
    :func:`shard_inter_tables` (which never read the outgoing inter tables).
    """
    if outgoing not in (False, True, "intra"):
        raise ValueError(f"outgoing={outgoing!r} (expected bool or 'intra')")
    A = spec.n_areas
    n_pad = spec.padded_area_size(size_multiple)
    sizes = spec.area_sizes()  # [A]
    D = spec.delay_ratio

    alive = np.zeros((A, n_pad), dtype=bool)
    for a in range(A):
        alive[a, : sizes[a]] = True

    rate = np.zeros((A, n_pad), dtype=np.float32)
    for a, ar in enumerate(spec.areas):
        rate[a, : sizes[a]] = ar.rate_hz

    K_i, K_e = spec.k_intra, spec.k_inter
    rows = np.arange(A * n_pad, dtype=np.int64)

    # ---- intra-area: uniform sources within the same area; inter-area:
    # uniform source area over the allowed adjacency (all-to-all by
    # default), then uniform neuron within the source area. Weights 80/20
    # excitatory/inhibitory by source index on the 1/256 grid; delays on
    # the dt grid with tiered cutoffs (eq. (1) and §4.2). All draws are
    # the shared counter-based row functions.
    # Each phase carries a profiler span: the draws of the incoming tables
    # (``repro.build.draw``) and their inversion into outgoing tables, with
    # padding and upload (``repro.build.invert``).
    # The draws and the per-area inversions run on a thread pool.
    with _build_pool() as pool:
        with TraceAnnotation("repro.build.draw"):
            s_, w_, d_ = _draw_parallel(
                pool, lambda r: _intra_rows(spec, seed, r, n_pad, sizes),
                rows, K_i)
            src_intra = s_.reshape(A, n_pad, K_i)
            w_intra = w_.reshape(A, n_pad, K_i)
            delay_intra = d_.reshape(A, n_pad, K_i)
            allowed, n_allowed = _allowed_source_areas(spec)
            s_, w_, d_ = _draw_parallel(
                pool, lambda r: _inter_rows(spec, seed, r, n_pad, sizes,
                                            allowed, n_allowed),
                rows, K_e)
            src_inter = s_.reshape(A, n_pad, K_e)
            w_inter = w_.reshape(A, n_pad, K_e)
            delay_inter = d_.reshape(A, n_pad, K_e)

        out: dict = {}
        if outgoing:
            with TraceAnnotation("repro.build.invert"):
                # Invert the incoming tables per tier (paper's short/long split).
                ti, wi, di = zip(*pool.map(
                    lambda a: _invert_adjacency(
                        src_intra[a], w_intra[a], delay_intra[a], n_pad),
                    range(A)))
                k_i = max(t.shape[1] for t in ti)

                def padk(x, k, fill):
                    return np.pad(x, ((0, 0), (0, k - x.shape[1])),
                                  constant_values=fill)

                out["tgt_intra"] = jnp.asarray(
                    np.stack([padk(t, k_i, -1) for t in ti]))
                out["wout_intra"] = jnp.asarray(
                    np.stack([padk(w, k_i, 0.0) for w in wi]))
                out["dout_intra"] = jnp.asarray(
                    np.stack([padk(d, k_i, 1) for d in di]))
                if K_e > 0 and outgoing != "intra":
                    # Global id space for both sources and targets.
                    t_, w_, d_ = _invert_adjacency(
                        src_inter.reshape(A * n_pad, K_e),
                        w_inter.reshape(A * n_pad, K_e),
                        delay_inter.reshape(A * n_pad, K_e),
                        A * n_pad, pool=pool,
                    )
                    out["tgt_inter"] = jnp.asarray(t_.reshape(A, n_pad, -1))
                    out["wout_inter"] = jnp.asarray(w_.reshape(A, n_pad, -1))
                    out["dout_inter"] = jnp.asarray(d_.reshape(A, n_pad, -1))

    # Delay-window metadata for delay-resolved delivery: the tightest
    # [lo, lo + span) covering the actual draws of each pathway table.
    lo_i = int(delay_intra.min()) if delay_intra.size else 1
    span_i = int(delay_intra.max()) - lo_i + 1 if delay_intra.size else 0
    lo_e = int(delay_inter.min()) if delay_inter.size else D
    span_e = int(delay_inter.max()) - lo_e + 1 if delay_inter.size else 0

    return Network(
        alive=jnp.asarray(alive),
        rate_hz=jnp.asarray(rate),
        src_intra=jnp.asarray(src_intra),
        w_intra=jnp.asarray(w_intra),
        delay_intra=jnp.asarray(delay_intra),
        src_inter=jnp.asarray(src_inter),
        w_inter=jnp.asarray(w_inter),
        delay_inter=jnp.asarray(delay_inter),
        n_pad=n_pad,
        n_areas=A,
        ring_len=spec.ring_len,
        delay_ratio=D,
        dt_ms=spec.dt_ms,
        steps_lo_intra=lo_i,
        r_span_intra=span_i,
        steps_lo_inter=lo_e,
        r_span_inter=span_e,
        **out,
    )


def _inbound_target_rows(
    mode: str, shard: int, n_shards: int, n_areas: int, n_pad: int,
    subgroup: int = 1, lane: int = 0,
) -> np.ndarray:
    """Global row ids of the targets shard ``shard`` (lane ``lane``) owns.

    Thin alias of :func:`repro.core.partition.shard_pathway_rows`, where the
    shard -> pathway-row-range derivation now lives (the sharded build path
    needs it without importing connectivity).
    """
    return shard_pathway_rows(
        mode, shard, n_shards, n_areas, n_pad, subgroup=subgroup, lane=lane)


def shard_inter_tables(
    net: Network, n_shards: int, *, mode: str = "group", subgroup: int = 1
) -> Network:
    """Re-cut the replicated outgoing inter tables into per-shard inbound
    slices (the tentpole of the sharded receive path).

    The replicated ``tgt_inter/wout_inter/dout_inter`` tables make every
    device hold (and scan) *all* ``A * n_pad x K_out`` inter-area synapses
    -- the NEST every-rank-scans-all-spikes pattern the paper identifies as
    the scaling wall (~171 GiB/device at production MAM scale, see
    EXPERIMENTS.md). This builds the inbound-edge representation instead:
    ``tgt_inter_in[s]`` holds, for every source row, only the synapses
    whose target lives in shard ``s`` -- a ``[S, A * n_pad, K_in]`` stack
    whose leading axis the distributed engine shards over the device
    groups, so each device stores and scatters only the ~1/S of edges it
    actually owns. Because groups own consecutive areas, the row range
    ``[g * rows_loc, (g+1) * rows_loc)`` of a shard's table *is* the
    (source group ``g`` -> this shard) edge table -- arriving id packets
    index it directly, no extra indirection.

    Targets stay *global* ids (remapped by the receive side's ``tgt_map``
    exactly like the replicated path), weights stay on the 1/256 grid, and
    each synapse appears in exactly one shard -- so delivery is
    bit-identical to the replicated table by construction.

    Returns a new :class:`Network` carrying the sharded tables with any
    replicated inter tables dropped (``tgt_intra`` untouched -- its
    subgroup cut is the separate :func:`slice_intra_tables`). Built entirely from the
    *incoming* ``src_inter/w_inter/delay_inter`` tensors, so the replicated
    outgoing tables never need to exist: a production engine can go
    straight from ``build_network()`` to the ~1/S inbound slices without
    materialising the ~150 GiB replicated layout this refactor removes.
    With ``subgroup > 1`` ('group' mode only) the slices are cut once more
    over the within-group neuron-window axis into a
    ``[S, subgroup, A * n_pad, K_in]`` stack: lane ``l`` of group ``s``
    keeps only the synapses landing in its own ``n_pad / subgroup`` window
    of each owned area. The distributed engine shards BOTH leading axes
    (area groups x subgroup lanes), so each device holds ~1/(S * subgroup)
    of the inter edges and ``K_in`` shrinks another ~subgroup x. Delivery
    stays bitwise: every lane's receive ``tgt_map`` already masks targets
    outside its window to the absorbing row, so removing those synapses
    from its slice changes nothing it would have kept.

    Works on ShapeDtypeStruct stand-ins too (dry-run lowering), where the
    width is the deterministic bound of :func:`_inbound_k_bound`.
    """
    if subgroup > 1 and mode != "group":
        raise ValueError(
            "subgroup slicing applies to the 'group' mode only (the "
            "'window' mode is already per-device)")
    if net.k_inter == 0:
        return dataclasses.replace(net, inter_shard_mode=mode)
    A, n_pad = net.n_areas, net.n_pad
    if mode == "group" and A % n_shards != 0:
        raise ValueError(f"n_areas={A} not divisible by {n_shards} shards")
    if mode == "window" and n_pad % n_shards != 0:
        raise ValueError(f"n_pad={n_pad} not divisible by {n_shards} shards")
    if subgroup > 1 and n_pad % subgroup != 0:
        raise ValueError(
            f"n_pad={n_pad} not divisible by subgroup={subgroup}")
    n_rows = A * n_pad
    drop = dict(tgt_inter=None, wout_inter=None, dout_inter=None)
    lead = (n_shards, subgroup) if subgroup > 1 else (n_shards,)

    if not hasattr(net.src_inter, "__array__"):  # ShapeDtypeStruct stand-in
        k_in = _inbound_k_bound(net.k_inter, n_shards * max(subgroup, 1))
        s = jax.ShapeDtypeStruct
        return dataclasses.replace(
            net,
            tgt_inter_in=s((*lead, n_rows, k_in), jnp.int32),
            wout_inter_in=s((*lead, n_rows, k_in), jnp.float32),
            dout_inter_in=s((*lead, n_rows, k_in), net.delay_inter.dtype),
            inter_shard_mode=mode,
            **drop,
        )

    K_e = net.k_inter
    src = np.asarray(net.src_inter).reshape(n_rows, K_e)
    w = np.asarray(net.w_inter).reshape(n_rows, K_e)
    d = np.asarray(net.delay_inter).reshape(n_rows, K_e)
    ts, ws, ds = [], [], []
    for shard in range(n_shards):
        for lane in range(max(subgroup, 1)):
            rows = _inbound_target_rows(
                mode, shard, n_shards, A, n_pad, max(subgroup, 1), lane)
            t_, w_, d_ = _invert_adjacency(
                src[rows], w[rows], d[rows], n_rows, tgt_ids=rows)
            ts.append(t_), ws.append(w_), ds.append(d_)
    k_in = max(t.shape[1] for t in ts)

    def padk(x, fill):
        return np.pad(x, ((0, 0), (0, k_in - x.shape[1])),
                      constant_values=fill)

    def stack(parts, fill):
        out = np.stack([padk(p, fill) for p in parts])
        return jnp.asarray(out.reshape(*lead, n_rows, k_in))

    return dataclasses.replace(
        net,
        tgt_inter_in=stack(ts, -1),
        wout_inter_in=stack(ws, 0.0),
        dout_inter_in=stack(ds, 1),
        inter_shard_mode=mode,
        **drop,
    )


def slice_intra_tables(net: Network, subgroup: int) -> Network:
    """Slice the outgoing intra (local-pathway) tables over the subgroup
    (within-group neuron-window) axis.

    The structure-aware event path receives the *whole group's* fired ids
    each cycle (subgroup all-gather) and every lane scatters through the
    full ``[A, n_pad, K_out]`` outgoing intra tables, masking targets
    outside its own ``n_pad / subgroup`` window to the absorbing row
    (``to_local``). Those tables are therefore replicated over the
    subgroup axis -- at production MAM scale that replication, not the
    inter tables, dominates per-device HBM (~15 GiB of the event path's
    footprint). This cuts them the same way :func:`shard_inter_tables`
    cuts the inbound inter slices: lane ``l`` keeps, per source row, only
    the synapses whose within-area target lands in its own window, stacked
    into a ``[subgroup, A, n_pad, K_lane]`` table whose leading axis the
    distributed engine shards over the subgroup -- ``K_lane`` shrinks
    ~subgroup x and the replication is gone.

    Bitwise-safe by the same argument as the inter cut: the surviving
    entries of each row keep their original relative order (stable
    compaction), and the entries removed are exactly the ones the lane's
    ``tgt_map`` already masked out -- the ring-buffer deposits a lane
    actually makes are the same values in the same order.

    Works on ShapeDtypeStruct stand-ins too (dry-run lowering), where the
    width is the deterministic bound of :func:`_inbound_k_bound` (a
    source's intra targets spread ~uniformly over the lanes, like inter
    targets over shards).
    """
    if subgroup <= 1 or net.tgt_intra is None:
        return net
    if net.tgt_intra.ndim == 4:
        raise ValueError("outgoing intra tables are already subgroup-sliced")
    A, n_pad = net.n_areas, net.n_pad
    if n_pad % subgroup != 0:
        raise ValueError(
            f"n_pad={n_pad} not divisible by subgroup={subgroup}")

    if not hasattr(net.tgt_intra, "__array__"):  # ShapeDtypeStruct stand-in
        k_li = _inbound_k_bound(net.k_intra, subgroup)
        s = jax.ShapeDtypeStruct
        return dataclasses.replace(
            net,
            tgt_intra=s((subgroup, A, n_pad, k_li), jnp.int32),
            wout_intra=s((subgroup, A, n_pad, k_li), jnp.float32),
            dout_intra=s((subgroup, A, n_pad, k_li), net.dout_intra.dtype),
        )

    tgt = np.asarray(net.tgt_intra).reshape(A * n_pad, -1)
    w = np.asarray(net.wout_intra).reshape(A * n_pad, -1)
    d = np.asarray(net.dout_intra).reshape(A * n_pad, -1)
    K = tgt.shape[-1]
    n_loc = n_pad // subgroup
    cols = np.arange(K, dtype=np.int64)[None, :]
    lanes = []
    k_lane = 0
    for lane in range(subgroup):
        lo = lane * n_loc
        keep = (tgt >= lo) & (tgt < lo + n_loc)   # -1 padding never kept
        order = np.argsort(~keep, axis=1, kind="stable")
        cnt = keep.sum(axis=1)
        valid = cols < cnt[:, None]
        lanes.append((
            np.where(valid, np.take_along_axis(tgt, order, axis=1),
                     tgt.dtype.type(-1)),
            np.where(valid, np.take_along_axis(w, order, axis=1),
                     w.dtype.type(0)),
            np.where(valid, np.take_along_axis(d, order, axis=1),
                     d.dtype.type(1)),
        ))
        k_lane = max(k_lane, int(cnt.max(initial=0)))

    def stack(i):
        return jnp.asarray(
            np.stack([ln[i][:, :k_lane] for ln in lanes])
            .reshape(subgroup, A, n_pad, k_lane))

    return dataclasses.replace(
        net, tgt_intra=stack(0), wout_intra=stack(1), dout_intra=stack(2))


def area_adjacency(
    net: Network, spec: MultiAreaSpec | None = None
) -> np.ndarray:
    """The realised area->area adjacency: ``adj[src, tgt]`` iff any neuron of
    target area ``tgt`` (live or ghost -- ghosts receive deposits too, so the
    routed exchange must ship to them for bit-identical rings) draws a source
    from area ``src``.

    Computed from the instantiated ``src_inter`` tables when the network
    carries data; for a :func:`network_sds` stand-in (ShapeDtypeStruct
    leaves, nothing to inspect) it falls back to the *spec-level* adjacency
    (``MultiAreaSpec.area_adjacency``, all-to-all by default) -- a superset
    of any instantiation, which is the safe direction: routing over a
    superset ships some empty packets but never drops a synapse.
    """
    A = net.n_areas
    if net.k_inter == 0:
        return np.zeros((A, A), dtype=bool)
    if net.area_adj is not None:
        # Sharded (host-free) build: the realised adjacency was computed at
        # plan time and rides along as static metadata -- the dense incoming
        # tensors below are zero-row stand-ins with nothing to inspect.
        return np.asarray(net.area_adj, dtype=bool)
    if not hasattr(net.src_inter, "__array__"):  # ShapeDtypeStruct stand-in
        if spec is None:
            return ~np.eye(A, dtype=bool)
        return spec.adjacency_matrix()
    src_area = np.asarray(net.src_inter) // net.n_pad        # [A_tgt, n, K]
    adj = np.zeros((A, A), dtype=bool)
    for tgt in range(A):
        adj[np.unique(src_area[tgt]), tgt] = True
    return adj


# ---------------------------------------------------------------------------
# Host-free sharded construction.
#
# The counter-based draws above make every synapse a pure function of
# (seed, pathway, global target row, k) -- so a shard can regenerate exactly
# its own rows and invert them locally, bitwise-identical to slicing the
# host-built global network, without any process ever materialising the
# global src_inter/w_inter/delay_inter tensors. The only *global* facts a
# shard needs are the padded table widths (the stacked layouts pad every
# shard/lane to the max width over all of them) and the delay-window
# metadata -- both derivable from counts alone. sharded_build_plan computes
# them in one streaming pass whose peak RSS is a single row chunk, and the
# per-shard builders below consume the plan.
# ---------------------------------------------------------------------------

# Streaming chunk size for the planning pass, in synapses (rows x K): caps
# the pass's peak RSS at a few hundred MB regardless of model scale.
_PLAN_CHUNK_SYNAPSES = 4_000_000


@dataclasses.dataclass(frozen=True)
class ShardedBuildPlan:
    """Global layout facts for host-free per-shard table construction.

    Everything here is derived from *counts* of the counter-based draws
    (one streaming pass, no global tensor): the padded widths every
    shard/lane table must share, the realised delay windows, and the
    realised area adjacency. Hashable (nested tuples only), so it can ride
    into static Network metadata.
    """

    n_shards: int
    subgroup: int
    mode: str            # 'group' | 'window' (see shard_pathway_rows)
    size_multiple: int
    n_pad: int
    # Padded widths (max over all shards/lanes -- identical to what
    # shard_inter_tables / build_network(outgoing) / slice_intra_tables
    # would compute from the global tensors).
    k_in: int            # inbound inter slice width
    k_out_intra: int     # outgoing intra width (subgroup == 1 layout)
    k_lane_intra: int    # lane-cut outgoing intra width (subgroup > 1)
    # Realised delay windows (build_network metadata).
    steps_lo_intra: int
    r_span_intra: int
    steps_lo_inter: int
    r_span_inter: int
    # Realised area->area adjacency as nested tuples of 0/1.
    area_adj: tuple


def _plan_row_chunks(rows: np.ndarray, k: int):
    step = max(1, _PLAN_CHUNK_SYNAPSES // max(k, 1))
    for i in range(0, len(rows), step):
        yield rows[i: i + step]


def sharded_build_plan(
    spec: MultiAreaSpec,
    seed: int,
    n_shards: int,
    *,
    mode: str = "group",
    subgroup: int = 1,
    size_multiple: int = 1,
) -> ShardedBuildPlan:
    """Pass 1 of the host-free build: global widths/windows/adjacency.

    Streams over the counter-based draws in bounded chunks (peak RSS ~ one
    chunk, independent of model size) and records exactly the global facts
    the host path's ``max over shards`` padding and ``min/max over draws``
    metadata would produce -- so pass-2 shard tables padded to these widths
    are bitwise-identical to slicing the host-built network.
    """
    A = spec.n_areas
    n_pad = spec.padded_area_size(size_multiple)
    sizes = spec.area_sizes()
    K_i, K_e = spec.k_intra, spec.k_inter
    sub = max(subgroup, 1)
    if sub > 1 and mode != "group":
        raise ValueError(
            "subgroup slicing applies to the 'group' mode only (the "
            "'window' mode is already per-device)")
    if mode == "group" and A % n_shards != 0:
        raise ValueError(f"n_areas={A} not divisible by {n_shards} shards")
    if mode == "window" and n_pad % n_shards != 0:
        raise ValueError(f"n_pad={n_pad} not divisible by {n_shards} shards")
    if sub > 1 and n_pad % sub != 0:
        raise ValueError(f"n_pad={n_pad} not divisible by subgroup={sub}")
    if mode not in ("group", "window"):
        raise ValueError(f"unknown inter_shard_mode {mode!r}")

    # ---- intra pathway: per-area outgoing widths + delay window.
    k_out_intra = 0
    k_lane_intra = 0
    lo_i, hi_i = None, None
    n_loc = n_pad // sub
    if K_i > 0:
        counts = np.zeros(n_pad, dtype=np.int64)
        lane_counts = np.zeros(n_pad * sub, dtype=np.int64)
        for a in range(A):
            counts[:] = 0
            lane_counts[:] = 0
            area_rows = np.arange(a * n_pad, (a + 1) * n_pad, dtype=np.int64)
            for rows in _plan_row_chunks(area_rows, K_i):
                src = _intra_src_rows(spec, seed, rows, n_pad, sizes)
                d = _intra_delay_rows(spec, seed, rows)
                lo_c, hi_c = int(d.min()), int(d.max())
                lo_i = lo_c if lo_i is None else min(lo_i, lo_c)
                hi_i = hi_c if hi_i is None else max(hi_i, hi_c)
                counts += np.bincount(src.reshape(-1), minlength=n_pad)
                if sub > 1:
                    lane_of_tgt = (rows % n_pad) // n_loc        # [R]
                    key = (src.astype(np.int64) * sub
                           + lane_of_tgt[:, None])
                    lane_counts += np.bincount(
                        key.reshape(-1), minlength=n_pad * sub)
            k_out_intra = max(k_out_intra, int(counts.max(initial=0)))
            if sub > 1:
                k_lane_intra = max(
                    k_lane_intra, int(lane_counts.max(initial=0)))

    # ---- inter pathway: per-(shard, lane) inbound widths + window + adj.
    k_in = 0
    lo_e, hi_e = None, None
    adj = np.zeros((A, A), dtype=bool)
    if K_e > 0:
        allowed, n_allowed = _allowed_source_areas(spec)
        counts = np.zeros(A * n_pad, dtype=np.int64)
        for shard in range(n_shards):
            for lane in range(sub):
                counts[:] = 0
                own = shard_pathway_rows(
                    mode, shard, n_shards, A, n_pad, subgroup=sub, lane=lane)
                for rows in _plan_row_chunks(own, K_e):
                    src = _inter_src_rows(
                        spec, seed, rows, n_pad, sizes, allowed, n_allowed)
                    d = _inter_delay_rows(spec, seed, rows)
                    lo_c, hi_c = int(d.min()), int(d.max())
                    lo_e = lo_c if lo_e is None else min(lo_e, lo_c)
                    hi_e = hi_c if hi_e is None else max(hi_e, hi_c)
                    counts += np.bincount(
                        src.reshape(-1), minlength=A * n_pad)
                    # Realised adjacency: flat (src_area, tgt_area) pairs.
                    pairs = np.unique(
                        (src.astype(np.int64) // n_pad) * A
                        + (rows // n_pad)[:, None])
                    adj.reshape(-1)[pairs] = True
                k_in = max(k_in, int(counts.max(initial=0)))

    return ShardedBuildPlan(
        n_shards=n_shards,
        subgroup=sub,
        mode=mode,
        size_multiple=size_multiple,
        n_pad=n_pad,
        k_in=k_in,
        k_out_intra=k_out_intra,
        k_lane_intra=k_lane_intra,
        steps_lo_intra=lo_i if lo_i is not None else 1,
        r_span_intra=(hi_i - lo_i + 1) if lo_i is not None else 0,
        steps_lo_inter=lo_e if lo_e is not None else spec.delay_ratio,
        r_span_inter=(hi_e - lo_e + 1) if lo_e is not None else 0,
        area_adj=tuple(tuple(int(v) for v in row) for row in adj),
    )


# ---------------------------------------------------------------------------
# Plan de-duplication. The planning pass is deterministic in
# (spec, seed, shard layout) but costs a full streaming sweep over every
# synapse draw -- and in a multi-process run each process used to repeat it
# identically. The keyed cache below computes it ONCE (process 0, or
# whichever process first takes the key) and shares it: in-memory memo for
# repeat builds in one process, an atomic JSON file for the other processes
# (ShardedBuildPlan is counts-only -- ints and a 0/1 adjacency -- so JSON
# round-trips it exactly).
# ---------------------------------------------------------------------------

_PLAN_MEMO: "dict[str, ShardedBuildPlan]" = {}

# Seconds a non-computing process waits for the computing one's file.
_PLAN_CACHE_WAIT_S = 600.0


def plan_cache_key(
    spec: MultiAreaSpec,
    seed: int,
    n_shards: int,
    *,
    mode: str = "group",
    subgroup: int = 1,
    size_multiple: int = 1,
) -> str:
    """Content digest keying one planning pass (spec + draw + layout)."""
    import hashlib
    import json

    payload = json.dumps(
        {
            "spec": dataclasses.asdict(spec),
            "seed": int(seed),
            "n_shards": int(n_shards),
            "mode": mode,
            "subgroup": int(subgroup),
            "size_multiple": int(size_multiple),
        },
        sort_keys=True,
        default=repr,
    )
    return hashlib.sha256(payload.encode()).hexdigest()[:24]


def _plan_to_json(plan: ShardedBuildPlan) -> dict:
    return dataclasses.asdict(plan)


def _plan_from_json(d: dict) -> ShardedBuildPlan:
    d = dict(d)
    d["area_adj"] = tuple(tuple(int(v) for v in row) for row in d["area_adj"])
    return ShardedBuildPlan(**d)


def cached_sharded_build_plan(
    spec: MultiAreaSpec,
    seed: int,
    n_shards: int,
    *,
    mode: str = "group",
    subgroup: int = 1,
    size_multiple: int = 1,
    cache_dir: str | None = None,
    process_index: int | None = None,
    wait_s: float = _PLAN_CACHE_WAIT_S,
) -> ShardedBuildPlan:
    """:func:`sharded_build_plan`, computed once per key instead of per call.

    Resolution order: in-memory memo -> ``cache_dir`` JSON file -> compute.
    ``cache_dir`` defaults to ``$REPRO_PLAN_CACHE``; with it set in a
    multi-process run, process 0 computes and atomically publishes the
    plan while every other process polls for the file instead of repeating
    the sweep (``process_index`` defaults to :func:`jax.process_index`).
    Without a cache_dir every process computes its own -- correct, just
    duplicated -- so launchers should set one on shared storage.
    """
    import json
    import os
    import time

    key = plan_cache_key(
        spec, seed, n_shards, mode=mode, subgroup=subgroup,
        size_multiple=size_multiple)
    if key in _PLAN_MEMO:
        return _PLAN_MEMO[key]

    if cache_dir is None:
        cache_dir = os.environ.get("REPRO_PLAN_CACHE") or None
    path = (os.path.join(cache_dir, f"plan_{key}.json")
            if cache_dir else None)

    def _read() -> "ShardedBuildPlan | None":
        if path is None or not os.path.exists(path):
            return None
        with open(path) as f:
            return _plan_from_json(json.load(f))

    plan = _read()
    if plan is None:
        if process_index is None:
            process_index = jax.process_index()
        multi = jax.process_count() > 1
        if path is not None and multi and process_index != 0:
            # Another process owns the compute; wait for its publish.
            deadline = time.monotonic() + wait_s
            while plan is None and time.monotonic() < deadline:
                time.sleep(0.2)
                plan = _read()
            if plan is None:
                raise TimeoutError(
                    f"process {process_index} waited {wait_s:.0f}s for "
                    f"{path} (is process 0 running with the same "
                    "REPRO_PLAN_CACHE?)")
        else:
            plan = sharded_build_plan(
                spec, seed, n_shards, mode=mode, subgroup=subgroup,
                size_multiple=size_multiple)
            if path is not None:
                # Atomic publish: readers only ever see a complete file.
                os.makedirs(cache_dir, exist_ok=True)
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    json.dump(_plan_to_json(plan), f)
                os.replace(tmp, path)

    _PLAN_MEMO[key] = plan
    return plan


def _padk_to(x: np.ndarray, k: int, fill) -> np.ndarray:
    if x.shape[1] > k:
        raise AssertionError(
            f"shard table width {x.shape[1]} exceeds plan width {k}")
    return np.pad(x, ((0, 0), (0, k - x.shape[1])), constant_values=fill)


def build_shard_tables(
    spec: MultiAreaSpec,
    seed: int,
    shard: int,
    *,
    plan: ShardedBuildPlan,
    lane: int = 0,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pass 2, inter pathway: one shard's (lane's) inbound inter slice.

    Returns ``(tgt, wout, dout)`` of shape ``[A * n_pad, plan.k_in]`` --
    bitwise-identical to ``shard_inter_tables(...)``'s slice ``[shard]``
    (or ``[shard, lane]`` under subgroup slicing) of the host-built
    network, but generated from the shard's own rows only: peak RSS is the
    shard's ~1/(S * subgroup) of the inter synapses, not the global table.
    """
    A = spec.n_areas
    n_pad, K_e = plan.n_pad, spec.k_inter
    n_rows = A * n_pad
    if K_e == 0:
        return (np.full((n_rows, 0), -1, np.int32),
                np.zeros((n_rows, 0), np.float32),
                np.ones((n_rows, 0), _delay_dtype(spec.steps_inter_max)))
    rows = shard_pathway_rows(
        plan.mode, shard, plan.n_shards, A, n_pad,
        subgroup=plan.subgroup, lane=lane)
    src, w, d = _inter_rows(spec, seed, rows, n_pad, spec.area_sizes())
    t_, w_, d_ = _invert_adjacency(src, w, d, n_rows, tgt_ids=rows)
    return (_padk_to(t_, plan.k_in, -1),
            _padk_to(w_, plan.k_in, 0.0),
            _padk_to(d_, plan.k_in, 1))


def build_group_intra_tables(
    spec: MultiAreaSpec,
    seed: int,
    areas: np.ndarray,
    *,
    plan: ShardedBuildPlan,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pass 2, intra pathway (subgroup == 1 layout): outgoing intra tables
    for the given areas, ``[len(areas), n_pad, plan.k_out_intra]`` --
    bitwise-identical to ``build_network(outgoing=...)``'s ``tgt_intra``
    rows for those areas."""
    n_pad, sizes = plan.n_pad, spec.area_sizes()
    ts, ws, ds = [], [], []
    for a in np.asarray(areas, dtype=np.int64):
        rows = np.arange(a * n_pad, (a + 1) * n_pad, dtype=np.int64)
        src, w, d = _intra_rows(spec, seed, rows, n_pad, sizes)
        t_, w_, d_ = _invert_adjacency(src, w, d, n_pad)
        ts.append(_padk_to(t_, plan.k_out_intra, -1))
        ws.append(_padk_to(w_, plan.k_out_intra, 0.0))
        ds.append(_padk_to(d_, plan.k_out_intra, 1))
    return np.stack(ts), np.stack(ws), np.stack(ds)


def build_lane_intra_tables(
    spec: MultiAreaSpec,
    seed: int,
    areas: np.ndarray,
    lane: int,
    *,
    plan: ShardedBuildPlan,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pass 2, intra pathway (subgroup > 1 layout): lane ``lane``'s cut of
    the outgoing intra tables for the given areas,
    ``[len(areas), n_pad, plan.k_lane_intra]`` -- bitwise-identical to
    ``slice_intra_tables(...)``'s ``[lane, areas]`` rows of the host-built
    network.

    The compaction is padded-width-invariant (a ``-1`` pad target is never
    inside a lane's window, and the stable compaction preserves the kept
    entries' relative order), so compacting each area's *own* inversion
    (its natural width) equals compacting the globally-padded table.
    """
    n_pad, sizes = plan.n_pad, spec.area_sizes()
    n_loc = n_pad // plan.subgroup
    lo = lane * n_loc
    k_lane = plan.k_lane_intra
    ts, ws, ds = [], [], []
    for a in np.asarray(areas, dtype=np.int64):
        rows = np.arange(a * n_pad, (a + 1) * n_pad, dtype=np.int64)
        src, w, d = _intra_rows(spec, seed, rows, n_pad, sizes)
        t_, w_, d_ = _invert_adjacency(src, w, d, n_pad)
        keep = (t_ >= lo) & (t_ < lo + n_loc)        # -1 padding never kept
        order = np.argsort(~keep, axis=1, kind="stable")
        cnt = keep.sum(axis=1)
        cols = np.arange(t_.shape[1], dtype=np.int64)[None, :]
        valid = cols < cnt[:, None]
        ts.append(_padk_to(
            np.where(valid, np.take_along_axis(t_, order, axis=1),
                     t_.dtype.type(-1))[:, :k_lane], k_lane, -1))
        ws.append(_padk_to(
            np.where(valid, np.take_along_axis(w_, order, axis=1),
                     w_.dtype.type(0))[:, :k_lane], k_lane, 0.0))
        ds.append(_padk_to(
            np.where(valid, np.take_along_axis(d_, order, axis=1),
                     d_.dtype.type(1))[:, :k_lane], k_lane, 1))
    return np.stack(ts), np.stack(ws), np.stack(ds)


def construction_cost_model(
    spec: MultiAreaSpec,
    *,
    n_shards: int,
    subgroup: int = 1,
    size_multiple: int = 1,
) -> dict:
    """Modelled host peak RSS of network construction, host-build vs sharded.

    Deterministic byte arithmetic (no allocation), mirroring what each path
    actually materialises:

    * **host build** (``build_network(outgoing=True)`` +
      ``shard_inter_tables`` + ``slice_intra_tables``): the global incoming
      tensors of both pathways, the outgoing intra inversion, the
      accumulated per-shard inbound inter slices (all S x subgroup of them
      live on the host before stacking) plus the stack copy, and the lane
      intra cuts likewise.
    * **sharded build** (plan + per-shard builders): one (shard, lane)'s
      own draws and inversion temporaries, the global counts array of the
      planning pass, and that shard's single output slice.

    Width estimates use the same deterministic bounds as the dry-run's SDS
    stand-ins (:func:`_outgoing_k_bound` / :func:`_inbound_k_bound`).
    """
    A = spec.n_areas
    n_pad = spec.padded_area_size(size_multiple)
    K_i, K_e = spec.k_intra, spec.k_inter
    sub = max(subgroup, 1)
    n_rows = A * n_pad
    by_i = 8 + np.dtype(_delay_dtype(spec.steps_intra_max)).itemsize
    by_e = 8 + np.dtype(_delay_dtype(spec.steps_inter_max)).itemsize

    k_oi = _outgoing_k_bound(K_i)
    k_ie = _inbound_k_bound(K_e, n_shards * sub)
    k_li = _inbound_k_bound(K_i, sub) if sub > 1 else k_oi

    incoming = n_rows * (K_i * by_i + K_e * by_e)
    outgoing_intra = n_rows * k_oi * by_i
    inbound_slices = n_shards * sub * n_rows * k_ie * by_e
    lane_intra = sub * n_rows * k_li * by_i if sub > 1 else 0
    # Slices accumulate, then np.stack copies them once more (x2 transient).
    host_peak = incoming + outgoing_intra + 2 * inbound_slices + 2 * lane_intra

    rows_loc = n_rows // (n_shards * sub) if spec.k_inter else 0
    # One shard's draws (src int32 + w f32 + d) + inversion temporaries
    # (int64 flat order/sort/repeat ~ 3 x 8 B per synapse) + the planning
    # pass's global counts array + the single output slice.
    shard_draws = rows_loc * K_e * (by_e + 24)
    shard_intra = n_pad * K_i * (by_i + 24)
    shard_out = n_rows * k_ie * by_e + n_pad * max(k_li, k_oi) * by_i
    counts_arr = n_rows * 8
    shard_peak = max(shard_draws, shard_intra) + shard_out + counts_arr

    return dict(
        n_shards=n_shards,
        subgroup=sub,
        build_bytes_host_modelled=int(host_peak),
        build_bytes_shard_modelled=int(shard_peak),
        host_incoming_bytes=int(incoming),
        host_inbound_slice_bytes=int(inbound_slices),
        reduction=float(host_peak) / float(max(shard_peak, 1)),
    )


def tile_gids(n_areas: int, n_pad: int, copies: int) -> jax.Array:
    """The folded batch's gid table: the single-trial ids, tiled per copy.

    ``[copies * n_areas, n_pad]`` where every copy repeats
    ``arange(n_areas * n_pad)``. Fed to the engines' ``gids`` override so
    each block of a :func:`tile_network` super-network draws the
    single-trial counter noise stream bit-for-bit -- the per-*trial*
    distinction comes from the per-trial ``seed`` SimState leaf, not the
    gid table.
    """
    one = jnp.arange(n_areas * n_pad, dtype=jnp.int32).reshape(n_areas, n_pad)
    return jnp.tile(one, (copies, 1))


def tile_network(net: Network, copies: int) -> Network:
    """``copies`` disjoint replicas of ``net`` as one block-diagonal network.

    The serving layer's folded trial batching: the area axis is tiled
    ``B = copies`` times (``[A, n_pad, ...]`` -> ``[B * A, n_pad, ...]``)
    and every *global* neuron id is offset by ``b * A * n_pad`` in copy
    ``b``, so no synapse crosses a copy boundary. Within-area indices
    (``src_intra``, ``tgt_intra``) are copy-local already and tile
    unchanged. Each block then reproduces the single-trial trajectory
    bit-for-bit: delivery weights live on the 1/256 grid (accumulation is
    associative-exact) and the per-copy scatter order is the single-trial
    scatter order.

    Sentinel conventions, load-bearing for the id offsets:

    * outgoing ``tgt_inter`` pads with ``-1`` / weight 0 -- offsets apply
      only to non-negative entries (a shifted sentinel would become a
      *valid* id in another copy);
    * incoming ``src_inter`` has no sentinels (ghost rows carry valid
      draws nullified by the alive mask / zero weights) -- offsets apply
      unconditionally.

    Sharded inbound tables don't tile (their leading axis is a device
    placement, not a network axis): tile the host-built network first,
    then re-cut with :func:`shard_inter_tables` if needed.
    """
    if copies < 1:
        raise ValueError(f"copies must be >= 1, got {copies}")
    if copies == 1:
        return net
    if net.tgt_inter_in is not None:
        raise ValueError(
            "tile_network needs the unsharded network (sharded inbound "
            "inter tables slice a device layout, not a network axis); "
            "tile first, then shard_inter_tables")
    A, n_pad = net.alive.shape
    B = copies
    block = A * n_pad
    if B * block > jnp.iinfo(jnp.int32).max:
        raise ValueError(
            f"{B} copies x {block} padded neurons overflows the int32 "
            "global-id space")

    def rep(x):
        return jnp.tile(x, (B,) + (1,) * (x.ndim - 1))

    # Per-row copy offset, broadcast against [B * A, n_pad, K] tables.
    offs = jnp.repeat(
        jnp.arange(B, dtype=jnp.int32) * jnp.int32(block), A
    )[:, None, None]

    def rep_global(x, sentinel: bool):
        t = rep(x)
        if sentinel:
            return jnp.where(t < 0, t, t + offs)
        return t + offs

    arrays = dict(
        alive=rep(net.alive),
        rate_hz=rep(net.rate_hz),
        src_intra=rep(net.src_intra),
        w_intra=rep(net.w_intra),
        delay_intra=rep(net.delay_intra),
        src_inter=(
            rep_global(net.src_inter, sentinel=False)
            if net.src_inter.size else rep(net.src_inter)
        ),
        w_inter=rep(net.w_inter),
        delay_inter=rep(net.delay_inter),
    )
    if net.tgt_intra is not None:
        arrays.update(
            tgt_intra=rep(net.tgt_intra),
            wout_intra=rep(net.wout_intra),
            dout_intra=rep(net.dout_intra),
        )
    if net.tgt_inter is not None:
        arrays.update(
            tgt_inter=rep_global(net.tgt_inter, sentinel=True),
            wout_inter=rep(net.wout_inter),
            dout_inter=rep(net.dout_inter),
        )
    area_adj = None
    if net.area_adj is not None:
        base = np.asarray(net.area_adj, dtype=bool)
        big = np.zeros((B * A, B * A), dtype=bool)
        for b in range(B):
            big[b * A:(b + 1) * A, b * A:(b + 1) * A] = base
        area_adj = tuple(tuple(int(x) for x in row) for row in big)
    return dataclasses.replace(
        net, n_areas=B * A, area_adj=area_adj, **arrays)
