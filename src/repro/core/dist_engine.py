"""Distributed engine: the shared window core on a (pod, data, model) mesh.

Placement:

* **structure-aware**: the area dimension ``A`` is sharded over the slow axes
  ``(pod, data)``; each area's ``n_pad`` neurons are sharded over the fast
  ``model`` axis (the intra-area device subgroup -- the paper's ``MPI_Group``
  generalisation). Per cycle only the subgroup communicates (local pathway);
  every D-th cycle the lumped ``[D, ...]`` spike block crosses the area-group
  graph (global pathway).

* **conventional**: the round-robin analogue -- every device hosts a slice of
  *every* area (``n_pad`` sharded over all axes). Perfect balance, zero
  structure: the full spike vector must be exchanged globally every cycle.

The window body itself lives in :mod:`repro.core.schedule` (shared with the
single-host engine -- superstep, legacy window and conventional scan
included); this module only validates the placement, selects the exchange
(``EngineConfig.exchange``) and wraps the body in ``shard_map``:

* ``'dense'`` (:class:`repro.core.exchange.DenseMeshExchange`): the dense
  backends exchange bit-packed spike vectors (``comm.gather_*``); the
  ``event`` backend compacts fired neurons into fixed-size *id packets*
  before each exchange (NEST's sparse wire format) and the receive side
  scatters the ids through this device's *sharded inbound* inter tables
  (``connectivity.shard_inter_tables`` -- only the ~1/S of edges the
  device owns; ``EngineConfig.shard_inter_tables=False`` keeps the legacy
  replicated tables as the equivalence reference). Either way the
  global pathway is a mesh-wide ``all_gather``: every device receives every
  fired id, even from areas that project nothing into its shard.

* ``'routed'`` (:class:`repro.core.exchange.RoutedExchange`): the global
  pathway mirrors network structure. The area->area adjacency computed at
  build time (:func:`repro.core.connectivity.area_adjacency`) is folded to
  the device-group graph; the window-end exchange ships id packets only
  along group->group edges that exist, via ``ppermute`` rotation rounds
  with per-edge ``s_max`` bounds. Sparse area graphs skip most rounds and
  ship strictly fewer bytes (see ``Engine.wire_bytes`` and
  ``benchmarks/bench_delivery.py``).

All exchanges produce spike trains bit-identical to the single-host
reference engine (tests/test_distributed.py, tests/test_exchange.py run them
in 8-device subprocesses). Packet bounds are static; spills are counted in
``SimState.overflow`` (any nonzero value means spikes were dropped and the
bounds must be raised).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.areas import MultiAreaSpec
from repro.core import connectivity as connectivity_lib
from repro.core.connectivity import Network
from repro.core import exchange as exchange_lib
from repro.core import neuron as neuron_lib
from repro.core import schedule as schedule_lib
from repro.core.engine import (
    CONVENTIONAL,
    STRUCTURE_AWARE,
    Engine,
    EngineConfig,
    SimState,
    make_fused_lif_update,
    resolve_params,
)

__all__ = [
    "make_dist_engine",
    "build_network_sharded",
    "network_pspecs",
    "state_pspecs",
    "shard_network",
]


def _area_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(mesh.axis_names[:-1])


def _subgroup_axis(mesh: Mesh) -> str:
    return mesh.axis_names[-1]


def network_pspecs(mesh: Mesh, schedule: str, like: Network | None = None) -> Network:
    """A Network-shaped pytree of PartitionSpecs for the given schedule.

    ``like`` supplies the static metadata fields (pytree structure must match
    exactly when used as shard_map in_specs). When ``like`` carries outgoing
    (event-path) tables: intra tables are replicated over the subgroup (each
    device scans its areas' complete fired lists); the *inbound* inter
    tables (``connectivity.shard_inter_tables``, the default assembly) are
    sharded over their leading shard axis -- the device-group grid under
    structure-aware placement, the full device grid under conventional --
    so each device holds only the ~1/S of inter edges it owns. Legacy
    replicated inter tables (``shard_inter_tables=False``, the equivalence
    reference) keep the NEST every-rank-holds-everything layout.
    """
    if schedule == STRUCTURE_AWARE:
        area = P(_area_axes(mesh), _subgroup_axis(mesh))
        syn = P(_area_axes(mesh), _subgroup_axis(mesh), None)
        out_intra = P(_area_axes(mesh), None, None)
        if like is not None and like.tgt_intra is not None \
                and like.tgt_intra.ndim == 4:
            # [gsz, A, n_pad, K_lane]: subgroup-sliced outgoing intra
            # tables (connectivity.slice_intra_tables) -- the leading lane
            # axis shards over the subgroup, so the local pathway's tables
            # stop being replicated across the gsz lanes of each group.
            out_intra = P(_subgroup_axis(mesh), _area_axes(mesh), None,
                          None)
        # [G, n_rows, K_in]: one group slice per area-group shard,
        # replicated over the subgroup (every lane scatters its own
        # neuron window of the group's targets).
        inter_in = P(_area_axes(mesh), None, None)
        if like is not None and like.tgt_inter_in is not None \
                and like.tgt_inter_in.ndim == 4:
            # [G, gsz, n_rows, K_in]: subgroup-sliced inbound tables -- the
            # second axis shards over the subgroup so each lane holds only
            # the rows targeting its own neuron window.
            inter_in = P(_area_axes(mesh), _subgroup_axis(mesh), None, None)
    else:  # conventional round-robin analogue: slice every area everywhere
        area = P(None, tuple(mesh.axis_names))
        syn = P(None, tuple(mesh.axis_names), None)
        out_intra = P(None, None, None)
        # [n_dev, n_rows, K_in]: one neuron-window slice per device.
        inter_in = P(tuple(mesh.axis_names), None, None)
    arrays = dict(
        alive=area, rate_hz=area,
        src_intra=syn, w_intra=syn, delay_intra=syn,
        src_inter=syn, w_inter=syn, delay_inter=syn,
    )
    if like is None or like.tgt_intra is not None:
        arrays.update(tgt_intra=out_intra, wout_intra=out_intra,
                      dout_intra=out_intra)
    if like is not None and like.tgt_inter is not None:
        rep = P(None, None, None)
        arrays.update(tgt_inter=rep, wout_inter=rep, dout_inter=rep)
    if like is None or like.tgt_inter_in is not None:
        arrays.update(tgt_inter_in=inter_in, wout_inter_in=inter_in,
                      dout_inter_in=inter_in)
    if like is not None:
        return dataclasses.replace(like, **arrays)
    return Network(
        n_pad=0, n_areas=0, ring_len=0, delay_ratio=1, dt_ms=0.1, **arrays
    )


def state_pspecs(
    mesh: Mesh,
    schedule: str,
    neuron_model: str,
    trial_leaves: bool = False,
) -> SimState:
    """A SimState-shaped pytree of PartitionSpecs.

    ``trial_leaves=True`` adds specs for the optional per-trial ``seed``/
    ``stim`` drive leaves (same ``[A, n_pad]`` placement as the neuron
    state); the default matches the classic leafless state exactly, so
    every existing state tree, checkpoint and shard_map spec is unchanged.
    """
    if schedule == STRUCTURE_AWARE:
        area = P(_area_axes(mesh), _subgroup_axis(mesh))
        ring = P(_area_axes(mesh), _subgroup_axis(mesh), None)
    else:
        area = P(None, tuple(mesh.axis_names))
        ring = P(None, tuple(mesh.axis_names), None)
    if neuron_model == "lif":
        nstate = neuron_lib.LIFState(v=area, i_syn=area, refrac=area)
    else:
        nstate = neuron_lib.IafState(countdown=area)
    return SimState(neuron=nstate, ring=ring, t=P(), spike_count=area,
                    overflow=P(), shipped_bytes=P(),
                    seed=area if trial_leaves else None,
                    stim=area if trial_leaves else None)


def shard_network(net: Network, mesh: Mesh, schedule: str) -> Network:
    """device_put the connectivity with the schedule's shardings."""
    specs = network_pspecs(mesh, schedule, like=net)

    def put(x, spec):
        if isinstance(x, jax.Array):
            return jax.device_put(x, NamedSharding(mesh, spec))
        return x

    return jax.tree.map(put, net, specs)


def _validate(net: Network, mesh: Mesh, schedule: str) -> None:
    A, n_pad = net.alive.shape
    if schedule == STRUCTURE_AWARE:
        n_groups = math.prod(mesh.shape[a] for a in _area_axes(mesh))
        gsz = mesh.shape[_subgroup_axis(mesh)]
        if A % n_groups != 0:
            raise ValueError(
                f"n_areas={A} not divisible by area shards={n_groups} "
                f"(mesh {dict(mesh.shape)})"
            )
        if n_pad % gsz != 0:
            raise ValueError(
                f"padded area size {n_pad} not divisible by subgroup {gsz}"
            )
    else:
        total = math.prod(mesh.shape.values())
        if n_pad % total != 0:
            raise ValueError(
                f"padded area size {n_pad} not divisible by {total} devices"
            )


def _make_exchange(
    net: Network, spec: MultiAreaSpec, mesh: Mesh, cfg: EngineConfig
) -> exchange_lib.Exchange:
    name = cfg.exchange or "dense"
    if name == "local":
        raise ValueError(
            "exchange='local' is the single-host identity; the distributed "
            "engine needs 'dense' or 'routed'"
        )
    if name == "routed":
        adjacency = connectivity_lib.area_adjacency(net, spec)
        return exchange_lib.RoutedExchange(net, cfg, mesh, adjacency)
    return exchange_lib.DenseMeshExchange(net, cfg, mesh)


def build_network_sharded(
    spec: MultiAreaSpec,
    mesh: Mesh,
    config: EngineConfig,
    *,
    seed: int = 12,
    size_multiple: int = 1,
) -> Network:
    """Host-free construction: each device's tables straight from the rules.

    The counter-based draws (:func:`repro.core.connectivity.draw_pathway_rows`)
    make every synapse a pure function of ``(seed, pathway, row, k)``, so a
    shard can regenerate exactly its own inbound inter slice and lane-cut
    intra tables -- bitwise-identical to slicing the host-built global
    network -- without any process materialising the global
    ``src_inter/w_inter/delay_inter`` tensors. This assembles that Network:

    * a streaming planning pass (:func:`~repro.core.connectivity.
      sharded_build_plan`, peak RSS ~ one row chunk) fixes the global padded
      widths, delay windows and realised area adjacency;
    * every synapse-table leaf is a ``jax.make_array_from_callback`` whose
      callback generates one shard's slice on demand (memoised per shard
      index, shared across the src/w/delay sibling leaves), so host memory
      holds at most the addressable shards' own tables;
    * the O(N) ``alive``/``rate_hz`` masks are built host-side (they are
      the model's *state* scale, not its synapse scale) and placed sharded;
    * the dense incoming inter tensors become the zero-row stand-ins the
      event engine would have dropped at build anyway, and the realised
      adjacency rides along as static ``area_adj`` metadata for the routed
      exchange.

    Structure-aware placement only (``config.sharded_build`` semantics):
    groups own consecutive areas, lanes own ``n_pad / gsz`` windows.
    """
    import numpy as np

    cfg = config
    if cfg.schedule != STRUCTURE_AWARE:
        raise ValueError(
            "build_network_sharded targets the structure-aware placement")
    if cfg.backend != "event":
        raise ValueError("build_network_sharded builds the event-path tables")
    area_axes = _area_axes(mesh)
    sg_axis = _subgroup_axis(mesh)
    n_groups = math.prod(mesh.shape[a] for a in area_axes)
    gsz = mesh.shape[sg_axis]
    A = spec.n_areas
    n_pad = spec.padded_area_size(size_multiple)
    if A % n_groups != 0:
        raise ValueError(
            f"n_areas={A} not divisible by area shards={n_groups} "
            f"(mesh {dict(mesh.shape)})")
    if n_pad % gsz != 0:
        raise ValueError(
            f"padded area size {n_pad} not divisible by subgroup {gsz}")
    sub = gsz if (cfg.subgroup_inter_tables and gsz > 1) else 1
    K_i, K_e = spec.k_intra, spec.k_inter

    # De-duplicated planning: the memo/keyed-file cache computes the
    # streaming sweep once per (spec, seed, layout) -- in multi-process
    # runs process 0 publishes and the rest read ($REPRO_PLAN_CACHE).
    plan = connectivity_lib.cached_sharded_build_plan(
        spec, seed, n_groups, mode="group", subgroup=sub,
        size_multiple=size_multiple)

    sizes = spec.area_sizes()
    alive = np.zeros((A, n_pad), dtype=bool)
    rate = np.zeros((A, n_pad), dtype=np.float32)
    for a, ar in enumerate(spec.areas):
        alive[a, : sizes[a]] = True
        rate[a, : sizes[a]] = ar.rate_hz

    area_sh = NamedSharding(mesh, P(area_axes, sg_axis))
    syn_sh = NamedSharding(mesh, P(area_axes, sg_axis, None))

    def _rng(sl, n: int) -> tuple[int, int]:
        # Callback indices arrive as slices; replicated dims come as
        # slice(None), so normalise both ends against the dim size.
        return (sl.start or 0, n if sl.stop is None else sl.stop)

    def from_cb(shape, sharding, cb):
        return jax.make_array_from_callback(shape, sharding, cb)

    # ---- incoming intra tables: each device draws its own rows.
    intra_cache: dict = {}

    def intra_slices(index):
        key = _rng(index[0], A) + _rng(index[1], n_pad)
        if key not in intra_cache:
            a0, a1, n0, n1 = key
            rows = (np.arange(a0, a1, dtype=np.int64)[:, None] * n_pad
                    + np.arange(n0, n1, dtype=np.int64)[None, :]).reshape(-1)
            s_, w_, d_ = connectivity_lib.draw_pathway_rows(
                spec, seed, rows, pathway="intra",
                size_multiple=size_multiple)
            shp = (a1 - a0, n1 - n0, K_i)
            intra_cache[key] = (s_.reshape(shp), w_.reshape(shp),
                                d_.reshape(shp))
        return intra_cache[key]

    shp_syn = (A, n_pad, K_i)
    src_intra = from_cb(shp_syn, syn_sh, lambda i: intra_slices(i)[0])
    w_intra = from_cb(shp_syn, syn_sh, lambda i: intra_slices(i)[1])
    delay_intra = from_cb(shp_syn, syn_sh, lambda i: intra_slices(i)[2])

    # ---- outgoing intra tables: lane-cut [gsz, A, n_pad, K_lane] when the
    # subgroup slicing is on, replicated [A, n_pad, K_out] otherwise.
    out_cache: dict = {}
    if sub > 1:
        out_sh = NamedSharding(mesh, P(sg_axis, area_axes, None, None))
        shp_out = (gsz, A, n_pad, plan.k_lane_intra)

        def out_slices(index):
            key = _rng(index[0], gsz) + _rng(index[1], A)
            if key not in out_cache:
                l0, l1, a0, a1 = key
                areas = np.arange(a0, a1, dtype=np.int64)
                parts = [connectivity_lib.build_lane_intra_tables(
                    spec, seed, areas, lane, plan=plan)
                    for lane in range(l0, l1)]
                out_cache[key] = tuple(
                    np.stack([p[j] for p in parts]) for j in range(3))
            return out_cache[key]
    else:
        out_sh = NamedSharding(mesh, P(area_axes, None, None))
        shp_out = (A, n_pad, plan.k_out_intra)

        def out_slices(index):
            key = _rng(index[0], A)
            if key not in out_cache:
                a0, a1 = key
                out_cache[key] = connectivity_lib.build_group_intra_tables(
                    spec, seed, np.arange(a0, a1, dtype=np.int64), plan=plan)
            return out_cache[key]

    tgt_intra = from_cb(shp_out, out_sh, lambda i: out_slices(i)[0])
    wout_intra = from_cb(shp_out, out_sh, lambda i: out_slices(i)[1])
    dout_intra = from_cb(shp_out, out_sh, lambda i: out_slices(i)[2])

    # ---- inbound inter slices: [S(, sub), A * n_pad, K_in].
    inter: dict = {}
    if K_e > 0:
        in_cache: dict = {}
        n_rows = A * n_pad
        if sub > 1:
            in_sh = NamedSharding(mesh, P(area_axes, sg_axis, None, None))
            shp_in = (n_groups, sub, n_rows, plan.k_in)

            def in_slices(index):
                key = _rng(index[0], n_groups) + _rng(index[1], sub)
                if key not in in_cache:
                    s0, s1, l0, l1 = key
                    rows = [[connectivity_lib.build_shard_tables(
                        spec, seed, s, plan=plan, lane=l)
                        for l in range(l0, l1)] for s in range(s0, s1)]
                    in_cache[key] = tuple(
                        np.stack([[b[j] for b in r] for r in rows])
                        for j in range(3))
                return in_cache[key]
        else:
            in_sh = NamedSharding(mesh, P(area_axes, None, None))
            shp_in = (n_groups, n_rows, plan.k_in)

            def in_slices(index):
                key = _rng(index[0], n_groups)
                if key not in in_cache:
                    s0, s1 = key
                    parts = [connectivity_lib.build_shard_tables(
                        spec, seed, s, plan=plan) for s in range(s0, s1)]
                    in_cache[key] = tuple(
                        np.stack([p[j] for p in parts]) for j in range(3))
                return in_cache[key]

        inter = dict(
            tgt_inter_in=from_cb(shp_in, in_sh, lambda i: in_slices(i)[0]),
            wout_inter_in=from_cb(shp_in, in_sh, lambda i: in_slices(i)[1]),
            dout_inter_in=from_cb(shp_in, in_sh, lambda i: in_slices(i)[2]),
            inter_shard_mode="group",
        )

    # Dense incoming inter tensors: the zero-row stand-ins the event engine
    # drops at build anyway (K_e axis preserved -- `k_inter` reads it).
    d_e = connectivity_lib._delay_dtype(spec.steps_inter_max)
    return Network(
        alive=jax.device_put(alive, area_sh),
        rate_hz=jax.device_put(rate, area_sh),
        src_intra=src_intra, w_intra=w_intra, delay_intra=delay_intra,
        src_inter=jnp.zeros((0, 0, K_e), jnp.int32),
        w_inter=jnp.zeros((0, 0, K_e), jnp.float32),
        delay_inter=jnp.zeros((0, 0, K_e), d_e),
        tgt_intra=tgt_intra, wout_intra=wout_intra, dout_intra=dout_intra,
        n_pad=n_pad,
        n_areas=A,
        ring_len=spec.ring_len,
        delay_ratio=spec.delay_ratio,
        dt_ms=spec.dt_ms,
        steps_lo_intra=plan.steps_lo_intra,
        r_span_intra=plan.r_span_intra,
        steps_lo_inter=plan.steps_lo_inter,
        r_span_inter=plan.r_span_inter,
        area_adj=plan.area_adj,
        **inter,
    )


def _make_dist_engine(
    net: Network | None,
    spec: MultiAreaSpec,
    mesh: Mesh,
    config: EngineConfig = EngineConfig(),
    *,
    build_seed: int = 12,
    gids: jax.Array | None = None,
    trial_leaves: bool = False,
) -> Engine:
    """Build the distributed engine. ``net`` may be host-resident; callers on
    real hardware should pass ``shard_network(net, mesh, schedule)``.

    ``net=None`` requires ``config.sharded_build`` and constructs the
    connectivity host-free on this mesh (:func:`build_network_sharded`,
    seeded by ``build_seed``) -- no global tensors ever exist.

    ``gids`` overrides the global-id table (see the single-host engine).
    ``trial_leaves=True`` sizes the shard_map state specs for the optional
    per-trial ``seed``/``stim`` drive leaves; ``init()`` then always
    materialises them (defaulting to the engine-wide seed / unit stimulus)."""
    cfg = config
    cfg.check(distributed=True)
    backend = cfg.backend
    if net is None:
        if not cfg.sharded_build:
            raise ValueError(
                "net=None needs config.sharded_build=True (otherwise pass "
                "a build_network(...) network)")
        net = build_network_sharded(spec, mesh, cfg, seed=build_seed)
    _validate(net, mesh, cfg.schedule)
    if backend == "event" and net.tgt_intra is None:
        raise ValueError("event delivery needs build_network(outgoing=True)")
    # The event/routed receive path scatters arriving id packets through
    # inter receive tables. By default (cfg.shard_inter_tables) those are
    # the *sharded inbound* slices: the replicated [A*n_pad, K_out] tables
    # are re-cut per target shard (connectivity.shard_inter_tables) and the
    # replicated leaves dropped, so each device holds ~1/S of the edges.
    # A network that already carries inbound tables (network_sds
    # inter_shards, the dry-run path) is validated against the mesh.
    if (backend == "event" or cfg.exchange == "routed") and net.k_inter > 0:
        if cfg.schedule == STRUCTURE_AWARE:
            n_shards = math.prod(mesh.shape[a] for a in _area_axes(mesh))
            gsz = mesh.shape[_subgroup_axis(mesh)]
            mode = "group"
        else:
            n_shards, gsz, mode = mesh.size, 1, "window"
        if net.tgt_inter_in is not None:
            got_sub = (net.tgt_inter_in.shape[1]
                       if net.tgt_inter_in.ndim == 4 else 1)
            want_sub = gsz if net.tgt_inter_in.ndim == 4 else 1
            if (net.tgt_inter_in.shape[0] != n_shards
                    or got_sub != want_sub
                    or net.inter_shard_mode != mode):
                raise ValueError(
                    f"sharded inter tables ({net.tgt_inter_in.shape[0]} "
                    f"{net.inter_shard_mode!r} shards x {got_sub} lanes) "
                    f"do not match the "
                    f"mesh ({n_shards} {mode!r} shards x {want_sub} lanes)")
        elif cfg.shard_inter_tables:
            # Built from the incoming tensors -- no replicated outgoing
            # inter tables needed (build_network(outgoing=True) is only
            # required for the event backend's intra tables above).
            # With subgroup_inter_tables the structure-aware cut also
            # slices each group's table over the gsz neuron windows
            # ([S, gsz, rows, K]) so a lane holds only its own targets.
            sub = (gsz if cfg.subgroup_inter_tables and mode == "group"
                   else 1)
            net = connectivity_lib.shard_inter_tables(
                net, n_shards, mode=mode, subgroup=sub)
    # The outgoing intra tables get the same subgroup treatment: under the
    # structure-aware event path every lane scatters the whole group's
    # fired ids through them, masking foreign targets -- so they are
    # lane-replicated unless each lane's slice is cut down to its own
    # neuron window (connectivity.slice_intra_tables). At production scale
    # that replication, not the inter tables, dominates per-device HBM.
    if net.tgt_intra is not None and net.tgt_intra.ndim == 4:
        gsz = mesh.shape[_subgroup_axis(mesh)]
        if cfg.schedule != STRUCTURE_AWARE:
            raise ValueError(
                "subgroup-sliced intra tables need the structure-aware "
                "schedule (the conventional cut is already per-device)")
        if net.tgt_intra.shape[0] != gsz:
            raise ValueError(
                f"subgroup-sliced intra tables ({net.tgt_intra.shape[0]} "
                f"lanes) do not match the mesh subgroup ({gsz})")
    elif (backend == "event" and net.tgt_intra is not None
          and cfg.schedule == STRUCTURE_AWARE
          and cfg.shard_inter_tables and cfg.subgroup_inter_tables):
        net = connectivity_lib.slice_intra_tables(
            net, mesh.shape[_subgroup_axis(mesh)])
    D = net.delay_ratio
    A, n_pad = net.alive.shape
    R = net.ring_len
    area_axes = _area_axes(mesh)
    subgroup = _subgroup_axis(mesh)
    all_axes = tuple(mesh.axis_names)
    lif_params, _ = resolve_params(net, spec, cfg)
    fused_lif = make_fused_lif_update(lif_params) if cfg.fused else None

    exchange = _make_exchange(net, spec, mesh, cfg)
    if net.tgt_inter_in is not None and (
            backend == "event" or cfg.exchange == "routed"):
        # Every inter receive on these paths scatters id packets through
        # the inbound tables (`_inter_tables`); the dense incoming
        # src_inter/w_inter/delay_inter tensors are never read again after
        # the slices are cut (area_adjacency above was their last reader),
        # so free them here instead of keeping both layouts live.
        # Zero-row stand-ins keep the pytree structure and the K_e axis
        # (`k_inter` gates the window-end exchange on shape[-1] > 0).
        k_e = net.k_inter
        net = dataclasses.replace(
            net,
            src_inter=jnp.zeros((0, 0, k_e), net.src_inter.dtype),
            w_inter=jnp.zeros((0, 0, k_e), net.w_inter.dtype),
            delay_inter=jnp.zeros((0, 0, k_e), net.delay_inter.dtype),
        )
    update_fn = schedule_lib.make_update_fn(
        cfg, spec, net.dt_ms, lif_params, fused_lif)
    window_body = schedule_lib.make_window_fn(cfg, exchange, update_fn)

    # ---------------- assemble jitted entry points ---------------------------

    st_specs = state_pspecs(
        mesh, cfg.schedule, cfg.neuron_model, trial_leaves=trial_leaves)
    nt_specs = network_pspecs(mesh, cfg.schedule, like=net)
    gid_spec = (
        P(area_axes, subgroup)
        if cfg.schedule == STRUCTURE_AWARE
        else P(None, all_axes)
    )
    if cfg.schedule == STRUCTURE_AWARE:
        block_spec = P(None, area_axes, subgroup)
    else:
        block_spec = P(None, None, all_axes)

    window_sm = jax.shard_map(
        window_body,
        mesh=mesh,
        in_specs=(st_specs, nt_specs, gid_spec),
        out_specs=(st_specs, block_spec),
        check_vma=False,
    )

    gids_global = jax.device_put(
        jnp.arange(A * n_pad, dtype=jnp.int32).reshape(A, n_pad)
        if gids is None else gids,
        NamedSharding(mesh, gid_spec))
    # Place the connectivity once with the schedule's shardings: the jitted
    # entry points take it as an argument, so each device holds only its
    # shard of the tables (already-placed leaves are left where they are).
    net = shard_network(net, mesh, cfg.schedule)

    overlap_jit = drain_jit = init_inflight = None
    if cfg.overlap_exchange:
        overlap_body, drain_body = schedule_lib.make_overlap_window_fn(
            cfg, exchange, update_fn)
        # The in-flight wire's specs come from the exchange: the dense wire
        # is a whole-mesh gather (replicated), the routed wire differs per
        # device group (leading group axis sharded over the area axes).
        # Finish is collective-free, so `drain` is safe as its own
        # shard_map'd program -- no SPMD deadlock risk from running it at a
        # host-decided boundary.
        if_specs = exchange.inflight_pspecs()
        overlap_sm = jax.shard_map(
            overlap_body,
            mesh=mesh,
            in_specs=(st_specs, if_specs, nt_specs, gid_spec),
            out_specs=(st_specs, if_specs, block_spec),
            check_vma=False,
        )
        drain_sm = jax.shard_map(
            drain_body,
            mesh=mesh,
            in_specs=(st_specs, if_specs, nt_specs, gid_spec),
            out_specs=st_specs,
            check_vma=False,
        )
        inflight_shardings = jax.tree.map(
            lambda s: NamedSharding(mesh, s), if_specs,
            is_leaf=lambda x: isinstance(x, P),
        )

        def init_inflight():
            return jax.device_put(
                exchange.init_inflight(net), inflight_shardings)

        overlap_jit = schedule_lib.bind_network(overlap_sm, net, gids_global)
        drain_jit = schedule_lib.bind_network(drain_sm, net, gids_global)

        # Compatibility `window`: one overlapped window drained on the spot
        # (finish of an empty inflight is a no-op) -- bit-identical to the
        # sequential window for every unpipelined caller.
        def window_drained(state: SimState, net, gids):
            st, inf, block = overlap_sm(
                state, exchange.init_inflight(net), net, gids)
            return drain_sm(st, inf, net, gids), block

        window = schedule_lib.bind_network(window_drained, net, gids_global)

    else:
        window = schedule_lib.bind_network(window_sm, net, gids_global)

    state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), st_specs,
        is_leaf=lambda x: isinstance(x, P),
    )

    def shard_state(state: SimState) -> SimState:
        """Scatter a host/global SimState over the mesh (checkpoint restore:
        the state layout is area-keyed and global, so the same arrays place
        onto any group count -- elastic reshard-restart is this device_put
        plus the re-cut inter tables above)."""
        return jax.device_put(state, state_shardings)

    def init(seed=None, stim=None) -> SimState:
        if seed is not None or stim is not None:
            if not trial_leaves:
                raise ValueError(
                    "per-trial seed/stim need make_simulation(..., "
                    "trial_leaves=True) -- the shard_map state specs are "
                    "sized at engine build"
                )
            if cfg.neuron_model != "lif":
                raise ValueError(
                    "per-trial seed/stim drive the LIF Poisson input; "
                    "ignore_and_fire has no seed or input dependence"
                )
        if cfg.neuron_model == "lif":
            nstate = neuron_lib.lif_init((A, n_pad))
        else:
            nstate = neuron_lib.ignore_and_fire_init(
                net.alive, net.rate_hz, net.dt_ms, gids_global
            )
        if trial_leaves:
            # The spec'd leaves always exist; absent overrides fall back to
            # the engine-wide seed / unit stimulus (bit-identical drive).
            seed_leaf = jnp.broadcast_to(
                jnp.asarray(cfg.seed if seed is None else seed, jnp.uint32),
                (A, n_pad))
            stim_leaf = jnp.broadcast_to(
                jnp.asarray(1.0 if stim is None else stim, jnp.float32),
                (A, n_pad))
        else:
            seed_leaf = stim_leaf = None
        state = SimState(
            neuron=nstate,
            ring=jnp.zeros((A, n_pad, R), jnp.float32),
            t=jnp.int32(0),
            spike_count=jnp.zeros((A, n_pad), jnp.int32),
            overflow=jnp.int32(0),
            shipped_bytes=jnp.float32(0),
            seed=seed_leaf,
            stim=stim_leaf,
        )
        return shard_state(state)

    if cfg.overlap_exchange:
        def run_body(state: SimState, n_windows: int, net, gids):
            def step(carry, _):
                st, inf = carry
                st, inf, block = overlap_sm(st, inf, net, gids)
                return (st, inf), block.astype(jnp.int32).sum()

            (state, inf), spikes = jax.lax.scan(
                step, (state, exchange.init_inflight(net)), None,
                length=n_windows)
            return drain_sm(state, inf, net, gids), spikes
    else:
        def run_body(state: SimState, n_windows: int, net, gids):
            def step(st, _):
                st, block = window_sm(st, net, gids)
                return st, block.astype(jnp.int32).sum()

            return jax.lax.scan(step, state, None, length=n_windows)

    run = schedule_lib.bind_network(
        run_body, net, gids_global, static_argnums=1)

    return Engine(init=init, window=window, run=run, config=cfg,
                  delay_ratio=D, window_raw=window_sm,
                  wire_bytes=exchange.wire_bytes(net),
                  shard_state=shard_state,
                  window_overlap=overlap_jit, drain=drain_jit,
                  init_inflight=init_inflight, net=net)


def make_dist_engine(
    net: Network | None,
    spec: MultiAreaSpec,
    mesh: Mesh,
    config: EngineConfig = EngineConfig(),
    *,
    build_seed: int = 12,
    gids: jax.Array | None = None,
    trial_leaves: bool = False,
) -> Engine:
    """Deprecated alias for :func:`repro.core.make_simulation`.

    Same engine, same trajectories -- only the entry point moved: the
    unified factory dispatches to this distributed assembly when a mesh is
    given.
    """
    import warnings

    warnings.warn(
        "make_dist_engine is deprecated; use repro.core.make_simulation"
        "(spec, config, net=net, mesh=mesh) -- it builds the identical "
        "distributed engine when a mesh is given",
        DeprecationWarning,
        stacklevel=2,
    )
    return _make_dist_engine(
        net, spec, mesh, config,
        build_seed=build_seed, gids=gids, trial_leaves=trial_leaves)
