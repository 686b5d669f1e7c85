"""Exchange-layer tests: the pluggable spike transport must be invisible.

The tentpole invariant: ``LocalExchange`` (single host), ``DenseMeshExchange``
(mesh-wide collectives) and ``RoutedExchange`` (connectivity-routed packet
rounds over the area-adjacency group graph) produce bit-identical spike
trains, ring buffers and overflow counts -- across schedules, delivery
backends, superstep/legacy windows and mesh shapes, including a deliberately
sparse area graph where routing actually skips rounds and ships strictly
fewer bytes.

Multi-device cases run in subprocesses with 8 forced host devices (per the
launch contract, the main pytest process must keep seeing one device).
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, n_devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=540,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_routed_exchange_equivalence_sparse_graph():
    """Tentpole: on a sparse area graph (directed ring over 8 areas), the
    routed exchange reproduces the single-host reference bitwise -- spike
    blocks AND rings -- for dense and event backends, under both the fused
    superstep and the legacy per-cycle window, with zero overflow; and its
    static wire accounting ships strictly fewer global bytes than the dense
    mesh exchange."""
    print(_run("""
        import numpy as np, jax
        from repro.core.areas import mam_benchmark_spec, ring_area_adjacency
        from repro.core.connectivity import build_network, area_adjacency
        from repro.core.engine import EngineConfig
        from repro.core.factory import make_simulation
        from repro.core import exchange as exchange_lib

        spec = mam_benchmark_spec(
            n_areas=8, n_per_area=32, k_intra=4, k_inter=4, rate_hz=30.0,
            area_adjacency=ring_area_adjacency(8, width=2))
        net = build_network(spec, seed=12, size_multiple=8, outgoing=True)
        adj = area_adjacency(net, spec)
        assert adj.sum() < adj.size - adj.shape[0], "graph must be sparse"
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        ref = make_simulation(spec, EngineConfig(
            neuron_model="ignore_and_fire", schedule="conventional"), net=net)
        s0 = ref.init()
        blocks, ring_ref = [], None
        for _ in range(6):
            s0, b = ref.window(s0)
            blocks.append(np.asarray(b))
        ring_ref = np.asarray(s0.ring)
        assert sum(b.sum() for b in blocks) > 0
        for backend in ("scatter", "event"):
            for superstep in (None, False):
                eng = make_simulation(spec, EngineConfig(
                    neuron_model="ignore_and_fire",
                    schedule="structure_aware", delivery_backend=backend,
                    exchange="routed", s_max_floor=32, superstep=superstep), net=net, mesh=mesh)
                st = eng.init()
                for w in range(6):
                    st, blk = eng.window(st)
                    assert np.array_equal(
                        np.asarray(blk).astype(bool), blocks[w]
                    ), (backend, superstep, w)
                assert np.array_equal(np.asarray(st.ring), ring_ref), (
                    backend, superstep, "ring")
                assert int(st.overflow) == 0, (backend, superstep)
                wire = eng.wire_bytes
                assert wire["rounds"] < wire["dense_rounds"], wire
        # Apples-to-apples wire volume (id packets both ways): routed < dense.
        rep = exchange_lib.wire_report(net, adj, backend="event",
                                       n_groups=4, gsz=2)
        assert (rep["routed"]["global_bytes"]
                < rep["dense"]["global_bytes"]), rep
        print("OK")
    """))


def test_routed_exchange_multi_pod_and_overflow():
    """The 3-axis (pod, data, model) mesh exercises the multi-axis group
    rotation (one ppermute over the (pod, data) axis-name tuple with pairs
    on the flattened row-major group index); LIF dynamics must stay
    bitwise. A forced-overflow run must surface the per-edge spill in
    SimState.overflow instead of dropping spikes silently."""
    print(_run("""
        import numpy as np, jax
        from repro.core.areas import mam_benchmark_spec, ring_area_adjacency
        from repro.core.connectivity import build_network
        from repro.core.engine import EngineConfig
        from repro.core.factory import make_simulation

        adj = ring_area_adjacency(8, width=1)
        spec = mam_benchmark_spec(n_areas=8, n_per_area=32, k_intra=4,
                                  k_inter=4, area_adjacency=adj)
        net = build_network(spec, seed=654, size_multiple=8, outgoing=True)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        ref = make_simulation(spec, EngineConfig(
            schedule="conventional", neuron_model="lif"), net=net)
        eng = make_simulation(spec, EngineConfig(
            schedule="structure_aware", neuron_model="lif",
            exchange="routed", s_max_floor=64), net=net, mesh=mesh)
        st, s0 = eng.init(), ref.init()
        for w in range(6):
            s0, blk_ref = ref.window(s0)
            st, blk = eng.window(st)
            assert np.array_equal(np.asarray(blk).astype(bool),
                                  np.asarray(blk_ref)), w
        assert np.array_equal(np.asarray(st.ring), np.asarray(s0.ring))
        assert int(st.overflow) == 0

        spec2 = mam_benchmark_spec(n_areas=8, n_per_area=32, k_intra=4,
                                   k_inter=4, rate_hz=2000.0,
                                   area_adjacency=adj)
        net2 = build_network(spec2, seed=12, size_multiple=8, outgoing=True)
        eng2 = make_simulation(spec2, EngineConfig(
            neuron_model="ignore_and_fire", schedule="structure_aware",
            exchange="routed", delivery_backend="event",
            s_max_headroom=0.0, s_max_floor=1), net=net2, mesh=mesh)
        st = eng2.init()
        for _ in range(5):
            st, _ = eng2.window(st)
        assert int(st.spike_count.sum()) > 0
        assert int(st.overflow) > 0, "routed edge spill must be visible"
        print("OK")
    """))


def test_sharded_inter_tables_equivalence():
    """Tentpole: the sharded inbound inter tables (the default distributed
    receive path) are bit-identical to the legacy replicated tables AND the
    single-host reference -- spike trains, rings and SimState.overflow --
    for both DenseMeshExchange and RoutedExchange on an 8-fake-device mesh,
    including the conventional schedule's window-sliced variant; and a
    forced per-edge s_max overflow run reports the *same* nonzero spill
    under either table layout (packets are cut send-side, so the layout
    cannot change what drops)."""
    print(_run("""
        import numpy as np, jax
        from repro.core.areas import mam_benchmark_spec, ring_area_adjacency
        from repro.core.connectivity import build_network
        from repro.core.engine import EngineConfig
        from repro.core.factory import make_simulation

        adj = ring_area_adjacency(8, width=2)
        spec = mam_benchmark_spec(
            n_areas=8, n_per_area=32, k_intra=4, k_inter=4, rate_hz=30.0,
            area_adjacency=adj)
        net = build_network(spec, seed=12, size_multiple=8, outgoing=True)
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        ref = make_simulation(spec, EngineConfig(
            neuron_model="ignore_and_fire", schedule="conventional"), net=net)
        s0 = ref.init()
        blocks = []
        for _ in range(6):
            s0, b = ref.window(s0)
            blocks.append(np.asarray(b))
        ring_ref = np.asarray(s0.ring)
        assert sum(b.sum() for b in blocks) > 0
        cells = [("structure_aware", "dense"), ("structure_aware", "routed"),
                 ("conventional", "dense")]
        for sched, exch in cells:
            for shard_tables in (True, False):
                eng = make_simulation(spec, EngineConfig(
                    neuron_model="ignore_and_fire", schedule=sched,
                    delivery_backend="event", exchange=exch,
                    s_max_floor=32, shard_inter_tables=shard_tables), net=net, mesh=mesh)
                st = eng.init()
                for w in range(6):
                    st, blk = eng.window(st)
                    assert np.array_equal(
                        np.asarray(blk).astype(bool), blocks[w]
                    ), (sched, exch, shard_tables, w)
                assert np.array_equal(np.asarray(st.ring), ring_ref), (
                    sched, exch, shard_tables, "ring")
                assert int(st.overflow) == 0, (sched, exch, shard_tables)

        # Forced per-edge spill: identical (nonzero) overflow and identical
        # surviving spike trains under both table layouts.
        spec2 = mam_benchmark_spec(n_areas=8, n_per_area=32, k_intra=4,
                                   k_inter=4, rate_hz=2000.0,
                                   area_adjacency=adj)
        net2 = build_network(spec2, seed=12, size_multiple=8, outgoing=True)
        got = {}
        for shard_tables in (True, False):
            eng = make_simulation(spec2, EngineConfig(
                neuron_model="ignore_and_fire", schedule="structure_aware",
                exchange="routed", delivery_backend="event",
                s_max_headroom=0.0, s_max_floor=1,
                shard_inter_tables=shard_tables), net=net2, mesh=mesh)
            st = eng.init()
            for _ in range(5):
                st, _ = eng.window(st)
            got[shard_tables] = (int(st.overflow),
                                 np.asarray(st.spike_count),
                                 np.asarray(st.ring))
        over_sh, spikes_sh, ring_sh = got[True]
        over_rep, spikes_rep, ring_rep = got[False]
        assert over_sh > 0, "forced spill must be visible"
        assert over_sh == over_rep, (over_sh, over_rep)
        assert np.array_equal(spikes_sh, spikes_rep)
        assert np.array_equal(ring_sh, ring_rep)
        print("OK")
    """))


def test_shard_inter_tables_partitions_the_replicated_table():
    """Host-only: every replicated inter synapse lands in exactly one shard
    (union over shards == the replicated table, per source row), each
    shard's targets belong to it, and the network_sds width bound covers
    the instantiated per-shard width for both slicing modes."""
    from repro.core.areas import mam_benchmark_spec
    from repro.core.connectivity import (
        _inbound_k_bound, build_network, shard_inter_tables)

    spec = mam_benchmark_spec(n_areas=8, n_per_area=32, k_intra=4, k_inter=6)
    net = build_network(spec, seed=12, size_multiple=8, outgoing=True)
    A, n_pad = net.alive.shape
    n_rows = A * n_pad
    tgt = np.asarray(net.tgt_inter).reshape(n_rows, -1)
    w = np.asarray(net.wout_inter).reshape(n_rows, -1)
    d = np.asarray(net.dout_inter).reshape(n_rows, -1)
    for mode, S in (("group", 4), ("window", 4)):
        sh = shard_inter_tables(net, S, mode=mode)
        assert sh.tgt_inter is None and sh.inter_shard_mode == mode
        t_in = np.asarray(sh.tgt_inter_in)
        w_in = np.asarray(sh.wout_inter_in)
        d_in = np.asarray(sh.dout_inter_in)
        assert t_in.shape[:2] == (S, n_rows)
        assert _inbound_k_bound(spec.k_inter, S) >= t_in.shape[2]
        for s in range(S):
            ts = t_in[s][t_in[s] >= 0]
            owner = ((ts // n_pad) // (A // S) if mode == "group"
                     else (ts % n_pad) // (n_pad // S))
            assert (owner == s).all(), (mode, s)
        for r in range(0, n_rows, 29):
            rep = sorted(
                (int(t), float(wv), int(dv))
                for t, wv, dv in zip(tgt[r], w[r], d[r]) if t >= 0)
            shd = sorted(
                (int(t_in[s, r, j]), float(w_in[s, r, j]),
                 int(d_in[s, r, j]))
                for s in range(S) for j in range(t_in.shape[2])
                if t_in[s, r, j] >= 0)
            assert rep == shd, (mode, r)
    with pytest.raises(ValueError, match="divisible"):
        shard_inter_tables(net, 3, mode="group")
    # Built from the *incoming* tensors: a network without the replicated
    # outgoing tables yields the identical inbound slices -- production
    # builds never need to materialise the replicated layout at all.
    lean = shard_inter_tables(
        build_network(spec, seed=12, size_multiple=8), 4)
    full = shard_inter_tables(net, 4)
    assert np.array_equal(np.asarray(lean.tgt_inter_in),
                          np.asarray(full.tgt_inter_in))
    assert np.array_equal(np.asarray(lean.wout_inter_in),
                          np.asarray(full.wout_inter_in))
    assert np.array_equal(np.asarray(lean.dout_inter_in),
                          np.asarray(full.dout_inter_in))


def test_sharded_tables_mesh_mismatch_rejected():
    """A network whose prebuilt inbound tables do not match the mesh's
    shard grid (wrong count or wrong slicing mode) must be rejected at
    assembly, not silently misdelivered."""
    import jax

    from repro.core.areas import mam_benchmark_spec
    from repro.core.connectivity import build_network, shard_inter_tables
    from repro.core.engine import EngineConfig
    from repro.core.factory import make_simulation

    spec = mam_benchmark_spec(n_areas=4, n_per_area=32, k_intra=4, k_inter=4)
    net = build_network(spec, seed=12, size_multiple=8, outgoing=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = EngineConfig(neuron_model="ignore_and_fire",
                       schedule="structure_aware", delivery_backend="event")
    with pytest.raises(ValueError, match="do not match the"):
        make_simulation(spec, cfg, net=shard_inter_tables(net, 2), mesh=mesh)
    with pytest.raises(ValueError, match="do not match the"):
        make_simulation(spec, cfg, net=shard_inter_tables(net, 1, mode="window"), mesh=mesh)


def test_build_routing_hierarchical_round_order():
    """Satellite: with ``intra_tier`` set (groups per pod on the (pod, data)
    group grid), rotation rounds are ordered group-local -> all-intra-pod ->
    pod-crossing, so most rounds stay on the fast tier; without it the flat
    offset order is preserved."""
    from repro.core import exchange as exchange_lib

    full = ~np.eye(8, dtype=bool)
    # 8 groups in 2 pods of 4: offsets 1-3 can stay intra-pod only for some
    # source groups, so with a full graph every nonzero offset crosses a pod
    # boundary somewhere -- use a block-diagonal graph to create genuinely
    # intra-pod offsets.
    intra = np.zeros((8, 8), dtype=bool)
    intra[:4, :4] = ~np.eye(4, dtype=bool)   # pod 0 areas talk to pod 0
    intra[4:, 4:] = ~np.eye(4, dtype=bool)
    intra[0, 4] = True                       # one slow-tier edge
    rt = exchange_lib.build_routing(
        intra, 8, exp_area_spikes=1.0, headroom=8.0, floor=2, intra_tier=4)

    def tier(rnd):
        if rnd.offset == 0:
            return 0
        return 1 if all(g // 4 == h // 4 for g, h in rnd.pairs) else 2

    tiers = [tier(r) for r in rt.rounds]
    assert tiers == sorted(tiers), [(r.offset, t) for r, t in
                                    zip(rt.rounds, tiers)]
    assert 1 in tiers and 2 in tiers, tiers
    # Within a tier the offsets stay ascending (stable order).
    for want in (1, 2):
        offs = [r.offset for r, t in zip(rt.rounds, tiers) if t == want]
        assert offs == sorted(offs)
    # Flat order without the tier hint (the single-pod mesh).
    rt_flat = exchange_lib.build_routing(
        full, 8, exp_area_spikes=1.0, headroom=8.0, floor=2)
    assert [r.offset for r in rt_flat.rounds] == sorted(
        r.offset for r in rt_flat.rounds)
    # The ordering must not change what ships: same offsets, same bounds.
    rt_h = exchange_lib.build_routing(
        full, 8, exp_area_spikes=1.0, headroom=8.0, floor=2, intra_tier=4)
    assert ({(r.offset, r.pairs, r.s_max) for r in rt_h.rounds}
            == {(r.offset, r.pairs, r.s_max) for r in rt_flat.rounds})


def test_routed_single_group_mesh_runs_inprocess():
    """A 1x1 mesh degenerates routing to the group-local round (offset 0, no
    ppermute) -- the full packet/compaction/scatter path on one device,
    bitwise against the single-host reference."""
    import jax

    from repro.core.areas import mam_benchmark_spec
    from repro.core.connectivity import build_network
    from repro.core.engine import EngineConfig
    from repro.core.factory import make_simulation

    spec = mam_benchmark_spec(n_areas=4, n_per_area=32, k_intra=4, k_inter=4,
                              rate_hz=30.0)
    net = build_network(spec, seed=12, size_multiple=8, outgoing=True)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    ref = make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", schedule="conventional"), net=net)
    eng = make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", schedule="structure_aware",
        exchange="routed", s_max_floor=32), net=net, mesh=mesh)
    assert eng.wire_bytes["exchange"] == "routed"
    s0, st = ref.init(), eng.init()
    for w in range(6):
        s0, blk_ref = ref.window(s0)
        st, blk = eng.window(st)
        assert np.array_equal(np.asarray(blk).astype(bool),
                              np.asarray(blk_ref)), w
    assert np.array_equal(np.asarray(st.ring), np.asarray(s0.ring))
    assert int(st.overflow) == 0


def test_routed_validation():
    """Config- and build-time guards: routed needs the structure-aware
    schedule, and -- only when the sharded inbound tables are disabled --
    the replicated outgoing tables."""
    import jax

    from repro.core.areas import mam_benchmark_spec
    from repro.core.connectivity import build_network
    from repro.core.engine import EngineConfig
    from repro.core.factory import make_simulation

    with pytest.raises(ValueError):
        EngineConfig(schedule="conventional", exchange="routed")
    with pytest.raises(ValueError):
        EngineConfig(exchange="mesh")
    spec = mam_benchmark_spec(n_areas=4, n_per_area=32, k_intra=4, k_inter=4)
    net = build_network(spec, seed=12, size_multiple=8)  # no outgoing tables
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # The legacy replicated receive path cannot exist without the outgoing
    # build; the default sharded path builds its inbound slices straight
    # from the incoming tensors, so outgoing=True is no longer required.
    with pytest.raises(ValueError, match="outgoing"):
        make_simulation(spec, EngineConfig(
            exchange="routed", shard_inter_tables=False), net=net, mesh=mesh)
    eng = make_simulation(spec, EngineConfig(exchange="routed"), net=net, mesh=mesh)
    assert eng.wire_bytes["exchange"] == "routed"
    with pytest.raises(ValueError, match="mesh"):
        make_simulation(spec, EngineConfig(exchange="dense"), net=net)


def test_build_routing_skips_rounds_and_bounds_edges():
    """Host-only routing-table checks: a sparse ring graph needs few
    rotation offsets, the all-to-all graph needs all of them, and per-edge
    packet bounds scale with the number of projecting source areas."""
    from repro.core import exchange as exchange_lib
    from repro.core.areas import ring_area_adjacency

    a, g = 16, 8
    sparse = np.asarray(ring_area_adjacency(a, width=1), dtype=bool)
    rt = exchange_lib.build_routing(
        sparse, g, exp_area_spikes=1.0, headroom=8.0, floor=2)
    # A width-1 ring over 2-area groups touches only offsets 0 and 1.
    assert {r.offset for r in rt.rounds} == {0, 1}
    assert rt.n_wire_rounds == 1
    full = ~np.eye(a, dtype=bool)
    rt_full = exchange_lib.build_routing(
        full, g, exp_area_spikes=1.0, headroom=8.0, floor=2)
    assert {r.offset for r in rt_full.rounds} == set(range(g))
    assert rt_full.n_wire_rounds == g - 1
    # Fuller edges (2 projecting areas) must get bigger packets than the
    # ring's single-area edges.
    s_sparse = {r.offset: r.s_max for r in rt.rounds}
    s_full = {r.offset: r.s_max for r in rt_full.rounds}
    assert s_full[1] > s_sparse[1]


def test_wire_report_routed_beats_dense_on_sparse_graph():
    """The static accounting that feeds BENCH_delivery.json: strictly fewer
    global bytes and fewer rounds on a sparse graph, honest (possibly
    larger) numbers on the all-to-all default."""
    from repro.core import exchange as exchange_lib
    from repro.core.areas import mam_benchmark_spec, ring_area_adjacency
    from repro.core.connectivity import area_adjacency, build_network

    spec = mam_benchmark_spec(n_areas=8, n_per_area=64, k_intra=4, k_inter=4,
                              area_adjacency=ring_area_adjacency(8, width=2))
    net = build_network(spec, seed=12, outgoing=True)
    rep = exchange_lib.wire_report(
        net, area_adjacency(net, spec), backend="event", n_groups=4, gsz=2)
    assert rep["routed"]["global_bytes"] < rep["dense"]["global_bytes"]
    assert rep["routed"]["rounds"] < rep["routed"]["dense_rounds"]
    assert rep["routed"]["local_bytes"] == rep["dense"]["local_bytes"]


def test_cost_model_prices_wire_counters():
    """The exchange wire counters feed simulate_rtf's communication term:
    strictly fewer routed bytes must price out as a strictly cheaper
    communicate RTF (same workload, same seed)."""
    from repro.core import cost_model as cm
    from repro.core import exchange as exchange_lib
    from repro.core.areas import mam_benchmark_spec, ring_area_adjacency
    from repro.core.connectivity import area_adjacency, build_network

    spec = mam_benchmark_spec(n_areas=8, n_per_area=64, k_intra=8, k_inter=8,
                              area_adjacency=ring_area_adjacency(8, width=2))
    net = build_network(spec, seed=12, outgoing=True)
    rep = exchange_lib.wire_report(
        net, area_adjacency(net, spec), backend="event", n_groups=8, gsz=2)
    wl = cm.WorkloadModel(n_m=64, k_n=16)
    rtf = {
        name: cm.simulate_rtf(
            wl, cm.SUPERMUC, 16, "structure_aware", seed=3,
            bytes_per_window=rep[name]["total_bytes"]).communicate
        for name in ("dense", "routed")
    }
    assert rep["routed"]["total_bytes"] < rep["dense"]["total_bytes"]
    assert rtf["routed"] < rtf["dense"]


def test_network_sds_outgoing_mirrors_build():
    """Satellite: the dry-run stand-in now carries the outgoing-table leaves
    (with a deterministic width bound), so the event backend and routed
    exchange lower at production scale; spec pspecs must cover them."""
    import jax

    from repro.core.areas import mam_benchmark_spec
    from repro.core.connectivity import build_network, network_sds

    spec = mam_benchmark_spec(n_areas=4, n_per_area=48, k_intra=8, k_inter=8)
    sds = network_sds(spec, size_multiple=8, outgoing=True)
    real = build_network(spec, seed=12, size_multiple=8, outgoing=True)
    for name in ("tgt_intra", "wout_intra", "dout_intra",
                 "tgt_inter", "wout_inter", "dout_inter"):
        leaf, ref = getattr(sds, name), getattr(real, name)
        assert leaf is not None, name
        assert leaf.dtype == ref.dtype, name
        assert leaf.shape[:2] == ref.shape[:2], name
        # The SDS width is a deterministic *bound* on the data-dependent one.
        assert leaf.shape[2] >= ref.shape[2], name
    assert network_sds(spec, outgoing=False).tgt_intra is None
    # The sharded variant (the dry-run's default since the sharded-table
    # PR): inbound [S, A*n_pad, K_in] stand-ins whose width bounds the
    # instantiated per-shard width, replicated inter tables dropped.
    from repro.core.connectivity import shard_inter_tables

    sds_sh = network_sds(spec, size_multiple=8, outgoing=True,
                         inter_shards=2)
    real_sh = shard_inter_tables(real, 2, mode="group")
    assert sds_sh.tgt_inter is None and sds_sh.inter_shard_mode == "group"
    for name in ("tgt_inter_in", "wout_inter_in", "dout_inter_in"):
        leaf, ref = getattr(sds_sh, name), getattr(real_sh, name)
        assert leaf.dtype == ref.dtype, name
        assert leaf.shape[:2] == ref.shape[:2], name
        assert leaf.shape[2] >= ref.shape[2], name
    # The stand-in must lower the event window through shard_map like the
    # dry-run does (1x1 mesh here; dryrun.py forces the production meshes).
    from jax.sharding import NamedSharding
    from repro.core.dist_engine import network_pspecs, state_pspecs
    from repro.core.factory import make_simulation
    from repro.core.engine import EngineConfig, SimState
    from repro.core import neuron as neuron_lib

    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = EngineConfig(neuron_model="lif", schedule="structure_aware",
                       delivery_backend="event", exchange="routed")
    sds = network_sds(spec, size_multiple=8, outgoing=True, inter_shards=1)
    eng = make_simulation(spec, cfg, net=sds, mesh=mesh)
    A, n_pad = sds.alive.shape
    s = jax.ShapeDtypeStruct
    st_specs = state_pspecs(mesh, cfg.schedule, cfg.neuron_model)

    def shard(sd, spec_):
        return s(sd.shape, sd.dtype, sharding=NamedSharding(mesh, spec_))

    state_sds = SimState(
        neuron=neuron_lib.LIFState(
            v=shard(s((A, n_pad), "float32"), st_specs.neuron.v),
            i_syn=shard(s((A, n_pad), "float32"), st_specs.neuron.i_syn),
            refrac=shard(s((A, n_pad), "int32"), st_specs.neuron.refrac),
        ),
        ring=shard(s((A, n_pad, sds.ring_len), "float32"), st_specs.ring),
        t=s((), "int32"),
        spike_count=shard(s((A, n_pad), "int32"), st_specs.spike_count),
        overflow=s((), "int32"),
        shipped_bytes=s((), "float32"),
    )
    nt_specs = network_pspecs(mesh, cfg.schedule, like=sds)
    net_in = jax.tree.map(
        lambda leaf, spec_: shard(leaf, spec_), sds, nt_specs,
        is_leaf=lambda x: isinstance(x, (jax.ShapeDtypeStruct,
                                         type(st_specs.t))),
    )
    gids_sds = shard(s((A, n_pad), "int32"), st_specs.spike_count)
    lowered = jax.jit(eng.window_raw).lower(state_sds, net_in, gids_sds)
    assert "ppermute" in lowered.as_text() or True  # lowering must succeed
    lowered.compile()


def test_chunked_event_deposit_distributed_bitwise():
    """The event deposit's chunk loop on the distributed exchanges, whose
    packets have holes (tiled all_gathers of per-device packets, routed
    rounds side by side, the conventional schedule's per-area split of one
    wire): with outgoing tables ~4,400 wide, so 8-slot chunks, every path
    fills the ring as the single-host scatter backend does, bitwise."""
    print(_run("""
        import numpy as np, jax
        from repro.core.areas import mam_benchmark_spec
        from repro.core.connectivity import build_network
        from repro.core.engine import EngineConfig
        from repro.core.factory import make_simulation
        from repro.kernels import ops

        spec = mam_benchmark_spec(n_areas=4, n_per_area=64, k_intra=4200,
                                  k_inter=4200, rate_hz=2000.0)
        net = build_network(spec, seed=5, size_multiple=8, outgoing=True)
        assert ops.deposit_chunk(net.tgt_intra.shape[-1], 1 << 10) == 8
        kw = dict(neuron_model="ignore_and_fire", s_max_floor=24)

        def run(eng, n=4):
            st, out = eng.init(), []
            for _ in range(n):
                st, b = eng.window(st)
                out.append((np.asarray(b).astype(bool),
                            np.asarray(st.ring)))
            return st, out

        ref_st, ref = run(make_simulation(
            spec, EngineConfig(**kw, delivery_backend="scatter"), net=net))
        assert sum(b.sum() for b, _ in ref) > 1500
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        for tag, extra in [
                ("dense", dict(exchange="dense")),
                ("dense-adaptive", dict(exchange="dense",
                                        adaptive_exchange=True)),
                ("routed", dict(exchange="routed")),
                ("routed-adaptive", dict(exchange="routed",
                                         adaptive_exchange=True)),
                ("conventional", dict(exchange="dense",
                                      schedule="conventional"))]:
            eng = make_simulation(
                spec, EngineConfig(**kw, delivery_backend="event", **extra),
                net=net, mesh=mesh)
            hlo = eng.window.lower(eng.init()).as_text(debug_info=True)
            assert ops.DEPOSIT_CHUNK_SCOPE in hlo, tag
            st, out = run(eng)
            for w, ((b, ring), (rb, rring)) in enumerate(zip(out, ref)):
                assert np.array_equal(b, rb), (tag, w)
                assert np.array_equal(ring, rring), (tag, w)
            assert int(st.overflow) == 0, tag
            print("OK", tag)
    """))
