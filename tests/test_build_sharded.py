"""Host-free sharded construction: the counter-based build tentpole.

The contract: ``build_network``'s per-pathway draws are pure functions of
``(seed, pathway, row)``, so any shard can regenerate exactly its own
inbound inter slice and lane-cut intra tables -- bitwise-identical to
slicing the host-built global network -- without any process ever
materialising the global ``src_inter/w_inter/delay_inter`` tensors. The
layout half (plan widths, per-shard builders vs the host cuts) runs in the
main process; the distributed half (``build_network_sharded`` engines vs a
single-host host-built reference) runs in subprocesses with 8 forced host
devices, per the launch contract.
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


def _spec(**kw):
    from repro.core.areas import mam_benchmark_spec

    kw.setdefault("n_areas", 4)
    kw.setdefault("n_per_area", 64)
    kw.setdefault("k_intra", 8)
    kw.setdefault("k_inter", 12)
    return mam_benchmark_spec(**kw)


def test_counter_draws_match_host_build_rows():
    """Any subset of rows, in any order, regenerates exactly the host-built
    global tensors' rows -- the init-sharding property the whole tentpole
    rests on (each synapse is a pure function of (seed, pathway, row, k))."""
    from repro.core.connectivity import build_network, draw_pathway_rows

    spec = _spec()
    net = build_network(spec, seed=12, size_multiple=8)
    A, n_pad, _ = net.src_intra.shape
    full = np.arange(A * n_pad, dtype=np.int64)
    rng = np.random.default_rng(0)
    for rows in (full, full[::3], rng.permutation(full)[:50]):
        for pathway, (s_g, w_g, d_g) in (
            ("intra", (net.src_intra, net.w_intra, net.delay_intra)),
            ("inter", (net.src_inter, net.w_inter, net.delay_inter)),
        ):
            s, w, d = draw_pathway_rows(
                spec, 12, rows, pathway=pathway, size_multiple=8)
            a, r = rows // n_pad, rows % n_pad
            assert np.array_equal(s, np.asarray(s_g)[a, r])
            assert np.array_equal(w, np.asarray(w_g)[a, r])
            assert np.array_equal(d, np.asarray(d_g)[a, r])
            assert d.dtype == np.asarray(d_g).dtype


def test_plan_matches_host_built_widths_and_metadata():
    """Pass 1's streamed global counts reproduce the host build's padded
    table widths, delay windows and realized area adjacency exactly -- so a
    sharded build compiles to the same shapes a host build would."""
    from repro.core.connectivity import (
        area_adjacency, build_network, shard_inter_tables,
        sharded_build_plan, slice_intra_tables)

    spec = _spec()
    S, sub = 4, 2
    net = build_network(spec, seed=12, size_multiple=8, outgoing=True)
    cut = slice_intra_tables(
        shard_inter_tables(net, S, mode="group", subgroup=sub), sub)
    plan = sharded_build_plan(spec, 12, S, mode="group", subgroup=sub,
                              size_multiple=8)
    assert plan.k_in == cut.tgt_inter_in.shape[-1]
    assert plan.k_lane_intra == cut.tgt_intra.shape[-1]
    assert plan.k_out_intra == net.tgt_intra.shape[-1]
    assert (plan.steps_lo_intra, plan.r_span_intra) == (
        net.steps_lo_intra, net.r_span_intra)
    assert (plan.steps_lo_inter, plan.r_span_inter) == (
        net.steps_lo_inter, net.r_span_inter)
    assert np.array_equal(np.asarray(plan.area_adj, dtype=bool),
                          area_adjacency(net, spec))


@pytest.mark.parametrize("layout", [(4, 1), (4, 2), (2, 4)])
def test_shard_tables_bitwise_vs_host_cut(layout):
    """Pass 2: every (shard, lane)'s regenerated inbound inter slice and
    lane intra tables are bitwise-identical to the host-built network's
    cuts, including the narrow delay dtype."""
    from repro.core.connectivity import (
        build_lane_intra_tables, build_network, build_shard_tables,
        shard_inter_tables, sharded_build_plan, slice_intra_tables)

    S, sub = layout
    spec = _spec()
    net = build_network(spec, seed=12, size_multiple=8, outgoing=True)
    cut = shard_inter_tables(net, S, mode="group", subgroup=sub)
    plan = sharded_build_plan(spec, 12, S, mode="group", subgroup=sub,
                              size_multiple=8)
    a_loc = spec.n_areas // S
    for s in range(S):
        for lane in range(sub):
            t, w, d = build_shard_tables(spec, 12, s, plan=plan, lane=lane)
            host = cut.tgt_inter_in[s, lane] if sub > 1 else \
                cut.tgt_inter_in[s]
            hw = cut.wout_inter_in[s, lane] if sub > 1 else \
                cut.wout_inter_in[s]
            hd = cut.dout_inter_in[s, lane] if sub > 1 else \
                cut.dout_inter_in[s]
            assert np.array_equal(t, np.asarray(host)), (s, lane)
            assert np.array_equal(w, np.asarray(hw)), (s, lane)
            assert np.array_equal(d, np.asarray(hd)), (s, lane)
            assert d.dtype == np.asarray(hd).dtype
        if sub > 1:
            cut_i = slice_intra_tables(net, sub)
            areas = list(range(s * a_loc, (s + 1) * a_loc))
            for lane in range(sub):
                ti, wi, di = build_lane_intra_tables(
                    spec, 12, areas, lane, plan=plan)
                assert np.array_equal(
                    ti, np.asarray(cut_i.tgt_intra[lane])[areas]), (s, lane)
                assert np.array_equal(
                    wi, np.asarray(cut_i.wout_intra[lane])[areas])
                assert np.array_equal(
                    di, np.asarray(cut_i.dout_intra[lane])[areas])
                assert di.dtype == np.asarray(cut_i.dout_intra).dtype


def test_window_mode_and_group_intra_tables():
    """The conventional 'window' cut and the subgroup==1 outgoing intra
    builder get the same bitwise guarantee."""
    from repro.core.connectivity import (
        build_group_intra_tables, build_network, build_shard_tables,
        shard_inter_tables, sharded_build_plan)

    spec = _spec()
    S = 4
    net = build_network(spec, seed=12, size_multiple=8, outgoing=True)
    cut = shard_inter_tables(net, S, mode="window")
    plan = sharded_build_plan(spec, 12, S, mode="window", size_multiple=8)
    for s in range(S):
        t, w, d = build_shard_tables(spec, 12, s, plan=plan)
        assert np.array_equal(t, np.asarray(cut.tgt_inter_in[s])), s
        assert np.array_equal(w, np.asarray(cut.wout_inter_in[s])), s
        assert np.array_equal(d, np.asarray(cut.dout_inter_in[s])), s
    plan_g = sharded_build_plan(spec, 12, 2, mode="group", size_multiple=8)
    areas = [1, 3]
    ti, wi, di = build_group_intra_tables(spec, 12, areas, plan=plan_g)
    assert np.array_equal(ti, np.asarray(net.tgt_intra)[areas])
    assert np.array_equal(wi, np.asarray(net.wout_intra)[areas])
    assert np.array_equal(di, np.asarray(net.dout_intra)[areas])


def test_outgoing_intra_skips_inter_inversion():
    """build_network(outgoing='intra') gives the intra tables the bounds
    verify needs without paying the dense outgoing inter inversion -- and
    the tensors it does build match outgoing=True bitwise."""
    from repro.core.connectivity import build_network

    spec = _spec()
    full = build_network(spec, seed=12, size_multiple=8, outgoing=True)
    lean = build_network(spec, seed=12, size_multiple=8, outgoing="intra")
    assert lean.tgt_inter is None and lean.wout_inter is None
    assert full.tgt_inter is not None
    assert np.array_equal(np.asarray(lean.tgt_intra),
                          np.asarray(full.tgt_intra))
    assert np.array_equal(np.asarray(lean.src_inter),
                          np.asarray(full.src_inter))
    with pytest.raises(ValueError):
        build_network(spec, seed=12, outgoing="bogus")


def test_build_network_threaded_tasks_bitwise(monkeypatch):
    """The host build's threaded draws and inversion, cut into tasks of 64
    synapses (about a hundred per pathway), give the tables of one task
    per pathway, bitwise."""
    import dataclasses

    from repro.core import connectivity

    spec = _spec()
    whole = connectivity.build_network(spec, seed=12, size_multiple=8,
                                       outgoing=True)
    monkeypatch.setattr(connectivity, "_DRAW_TASK_SYNAPSES", 64)
    tasks = connectivity.build_network(spec, seed=12, size_multiple=8,
                                       outgoing=True)
    for field in dataclasses.fields(whole):
        a, b = getattr(whole, field.name), getattr(tasks, field.name)
        if hasattr(a, "shape"):
            assert a.dtype == b.dtype, field.name
            assert np.array_equal(np.asarray(a), np.asarray(b)), field.name
        else:
            assert a == b, field.name


def test_invert_adjacency_threaded_placement_stress(monkeypatch):
    """Placement slices written from more threads than cores, with a short
    switch interval, leave the tables the serial placement leaves: no
    entry is lost or misplaced."""
    import concurrent.futures

    from repro.core import connectivity

    rng = np.random.default_rng(3)
    n_src, n_tgt, k = 300, 400, 50
    src = rng.integers(0, n_src, (n_tgt, k)).astype(np.int32)
    w = (rng.integers(-512, 512, (n_tgt, k)) / 256.0).astype(np.float32)
    d = rng.integers(1, 9, (n_tgt, k)).astype(np.int8)
    want = connectivity._invert_adjacency(src, w, d, n_src)
    monkeypatch.setattr(connectivity, "_DRAW_TASK_SYNAPSES", 37)
    workers = min((os.cpu_count() or 1) + 2, 64)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with concurrent.futures.ThreadPoolExecutor(workers) as pool:
            for _ in range(5):
                got = connectivity._invert_adjacency(
                    src, w, d, n_src, pool=pool)
                for g, x in zip(got, want):
                    assert g.dtype == x.dtype and np.array_equal(g, x)
    finally:
        sys.setswitchinterval(interval)


def test_k_inter_zero_edge():
    """K_e == 0: the plan degenerates cleanly and the shard builder returns
    width-0 tables matching the host build's empty inter pathway."""
    from repro.core.connectivity import (
        build_network, build_shard_tables, sharded_build_plan)

    spec = _spec(n_areas=2, k_inter=0)
    net = build_network(spec, seed=12, size_multiple=8)
    plan = sharded_build_plan(spec, 12, 2, mode="group", size_multiple=8)
    assert plan.k_in == 0
    assert plan.r_span_inter == net.r_span_inter == 0
    t, w, d = build_shard_tables(spec, 12, 0, plan=plan)
    assert t.shape[-1] == 0 and w.shape[-1] == 0 and d.shape[-1] == 0


def test_plan_and_config_validation():
    """Divisibility / mode errors at plan time; EngineConfig.sharded_build
    is refused off the event backend, off structure_aware, without sharded
    tables, and by the single-host engine (which holds the whole network
    anyway)."""
    from repro.core.connectivity import build_network, sharded_build_plan
    from repro.core.engine import EngineConfig
    from repro.core.factory import make_simulation

    spec = _spec()
    with pytest.raises(ValueError):
        sharded_build_plan(spec, 12, 3, mode="group")  # 3 does not divide 4
    with pytest.raises(ValueError):
        sharded_build_plan(spec, 12, 2, mode="bogus")
    with pytest.raises(ValueError):
        sharded_build_plan(spec, 12, 2, mode="window", subgroup=2)
    with pytest.raises(ValueError):
        EngineConfig(delivery_backend="scatter", sharded_build=True)
    with pytest.raises(ValueError):
        EngineConfig(delivery_backend="event", schedule="conventional",
                     sharded_build=True)
    with pytest.raises(ValueError):
        EngineConfig(delivery_backend="event", shard_inter_tables=False,
                     sharded_build=True)
    cfg = EngineConfig(delivery_backend="event", sharded_build=True,
                       neuron_model="ignore_and_fire")
    net = build_network(spec, seed=12, outgoing=True)
    with pytest.raises(ValueError, match="single-host"):
        make_simulation(spec, cfg, net=net)


@pytest.mark.parametrize("exchange", ["dense", "routed"])
def test_sharded_built_engine_bitwise_vs_host(exchange):
    """Acceptance matrix on 8 forced host devices: engines whose tables
    come from build_network_sharded (no global inter tensors ever
    materialised) reproduce the host-built single-host reference bitwise --
    spike blocks AND rings -- under {static,adaptive} x {superstep,legacy},
    with zero overflow; the sharded-built Network's tables equal the
    host-built shard cuts leaf for leaf."""
    print(_run(f"""
        import numpy as np, jax
        import dataclasses
        from repro.core.areas import mam_benchmark_spec
        from repro.core.connectivity import (
            build_network, shard_inter_tables, slice_intra_tables)
        from repro.core.engine import EngineConfig
        from repro.core.factory import make_simulation
        from repro.core.dist_engine import build_network_sharded

        spec = mam_benchmark_spec(n_areas=4, n_per_area=32, k_intra=4,
                                  k_inter=4, rate_hz=30.0)
        net = build_network(spec, seed=12, size_multiple=8)
        ref = make_simulation(spec, EngineConfig(
            neuron_model="ignore_and_fire", schedule="conventional"), net=net)
        s0 = ref.init()
        blocks = []
        for _ in range(4):
            s0, b = ref.window(s0)
            blocks.append(np.asarray(b))
        ring_ref = np.asarray(s0.ring)
        assert sum(b.sum() for b in blocks) > 0

        mesh = jax.make_mesh((4, 2), ("data", "model"))

        def cfg(adaptive=False, superstep=None):
            return EngineConfig(
                neuron_model="ignore_and_fire",
                schedule="structure_aware", delivery_backend="event",
                exchange={exchange!r}, s_max_floor=32,
                sharded_build=True,
                adaptive_exchange=adaptive, superstep=superstep)

        # The sharded-built Network's tables == the host-built shard cuts.
        snet = build_network_sharded(spec, mesh, cfg(), seed=12)
        host = build_network(spec, seed=12, size_multiple=8, outgoing=True)
        hcut = slice_intra_tables(
            shard_inter_tables(host, 4, mode="group", subgroup=2), 2)
        for name in ("tgt_inter_in", "wout_inter_in", "dout_inter_in",
                     "tgt_intra", "wout_intra", "dout_intra",
                     "alive", "rate_hz"):
            a = np.asarray(getattr(snet, name))
            b = np.asarray(getattr(hcut, name))
            assert a.dtype == b.dtype, name
            assert np.array_equal(a, b), name
        assert snet.src_inter.shape[0] == 0  # never materialised globally
        for f in ("steps_lo_intra", "r_span_intra", "steps_lo_inter",
                  "r_span_inter", "ring_len", "delay_ratio"):
            assert getattr(snet, f) == getattr(host, f), f

        # net=None: the engine builds its own tables host-free.
        for adaptive in (False, True):
            for superstep in (None, False):
                eng = make_simulation(spec, cfg(adaptive, superstep), net=None, mesh=mesh, build_seed=12)
                st = eng.init()
                for w in range(4):
                    st, blk = eng.window(st)
                    assert np.array_equal(
                        np.asarray(blk).astype(bool), blocks[w]
                    ), (adaptive, superstep, w)
                assert np.array_equal(np.asarray(st.ring), ring_ref), (
                    adaptive, superstep, "ring")
                assert int(st.overflow) == 0, (adaptive, superstep)
        print("sharded-build matrix OK:", {exchange!r})
    """))
