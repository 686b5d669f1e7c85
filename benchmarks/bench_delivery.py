"""Delivery-backend benchmark: the shared per-cycle hot path, timed two ways.

    PYTHONPATH=src python -m benchmarks.bench_delivery [--windows W]

For every backend of ``repro/core/delivery.py`` (onehot | scatter | pallas |
event) and two configs -- the quickstart network (4 x 256 neurons, K=64) and
a laptop-scale 32-area MAM -- this measures:

* ``phase=deliver``: the deliver phase in isolation (a jitted scan of
  intra+inter delivery cycles on a real spike vector). This is the paper's
  dominant phase (§3) and where the backends actually differ; the event
  backend's O(s_max * K_out) scatter must beat the one-hot reference's
  O(N * K * R) einsum by >= 10x on the quickstart config.
* ``phase=engine``: end-to-end engine cycles/s via ``Engine.run`` (one jit
  dispatch for all windows). Fixed per-cycle costs (ring read/clear, neuron
  update, scan bookkeeping) are shared by all backends, so the end-to-end
  ratio is smaller -- reported so the trajectory stays honest.

Results append to ``BENCH_delivery.json`` (machine-readable; one file, both
phases). Spike trains are asserted bit-identical across backends while
timing -- the benchmark is also an equivalence test.

On CPU the Pallas kernels run in interpret mode (the TPU lowering is the
target; interpret numbers measure semantics, not the kernel).
"""

from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

BACKENDS = ("onehot", "scatter", "pallas", "event")


def _time_best(fn, repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def bench_deliver_phase(name, spec, net, spikes, cycles: int, results: list):
    """Time a jitted scan of `cycles` intra+inter delivery steps."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import delivery

    A, n_pad = net.alive.shape
    ring0 = jnp.zeros((A, n_pad, net.ring_len), jnp.float32)
    sf = jnp.asarray(spikes, jnp.float32)
    # Workload-tuned packet bounds: the bit-exactness assertions below and
    # the engine-phase overflow check prove nothing is dropped at this size.
    s_max_area, s_max_all = delivery.event_bounds(net, headroom=8.0, floor=4)

    print(f"\n-- {name} / deliver phase ({cycles} cycles, "
          f"{int(sf.sum())} spikes/cycle) --")
    print(f"{'backend':10s} {'cycles/s':>12s} {'us/cycle':>10s} "
          f"{'vs onehot':>10s}")

    import numpy as np

    # The packet bounds must cover this raster or the event timing would
    # measure dropped work; the ring equality below would catch it anyway.
    per_area = np.asarray(sf).sum(axis=-1)
    assert per_area.max() <= s_max_area and per_area.sum() <= s_max_all, (
        "event packet bounds too small for the benchmark raster")

    base = None
    ref_ring = None
    for backend in BACKENDS:

        @functools.partial(jax.jit, static_argnames=())
        def burn(ring, sf_, backend=backend):
            def body(r, t):
                r = delivery.deliver_intra(
                    r, sf_, net, t, backend=backend, s_max=s_max_area)
                r = delivery.deliver_inter(
                    r, sf_.reshape(-1), net, t,
                    backend=backend, s_max=s_max_all)
                return r, None
            r, _ = jax.lax.scan(
                body, ring, jnp.arange(cycles, dtype=jnp.int32))
            return r

        out = jax.block_until_ready(burn(ring0, sf))  # compile
        if ref_ring is None:
            ref_ring = np.asarray(out)
        else:
            assert np.array_equal(np.asarray(out), ref_ring), (
                f"{backend} deliver phase diverged from the reference ring")
        wall = _time_best(lambda: jax.block_until_ready(burn(ring0, sf)))
        cps = cycles / wall
        if base is None:
            base = cps
        speedup = cps / base
        print(f"{backend:10s} {cps:12.1f} {wall / cycles * 1e6:10.1f} "
              f"{speedup:9.2f}x")
        results.append(dict(
            config=name, phase="deliver", backend=backend,
            cycles_per_s=round(cps, 2), us_per_cycle=round(wall / cycles * 1e6, 2),
            n_cycles=cycles, spikes_per_cycle=int(sf.sum()),
            n_neurons=spec.n_total, k_total=spec.k_total,
            ring_len=net.ring_len, speedup_vs_onehot=round(speedup, 3),
        ))


def bench_engine(name, spec, net, windows: int, results: list):
    """End-to-end engine cycles/s (Engine.run: one dispatch, scan inside).

    The four backend rows run the default structure-aware window -- since the
    superstep refactor that is the fused D-cycle superstep (blocked ring
    read/clear, live window buffer, single-pass lumped inter exchange). Two
    extra rows keep the comparison honest in one file: ``event-percycle``
    (superstep=False: the pre-refactor per-cycle window) and ``event-fused``
    (the fused Pallas superstep kernel; on CPU it runs in interpret mode, so
    that row measures semantics, not the kernel).
    """
    import jax
    import numpy as np

    from repro.core.engine import EngineConfig
    from repro.core.factory import make_simulation

    D = net.delay_ratio
    print(f"\n-- {name} / end-to-end engine ({windows} windows x D={D}) --")
    print(f"{'backend':14s} {'cycles/s':>12s} {'wall s':>9s} "
          f"{'vs onehot':>10s}")

    rows = [(b, dict(delivery_backend=b)) for b in BACKENDS]
    rows.append(("event-percycle", dict(delivery_backend="event",
                                        superstep=False)))
    rows.append(("event-fused", dict(delivery_backend="event",
                                     superstep_kernel=True)))
    ref_counts = None
    base = None
    for label, kw in rows:
        eng = make_simulation(spec, EngineConfig(
            neuron_model="ignore_and_fire", schedule="structure_aware",
            s_max_floor=4, **kw), net=net)
        st0 = eng.init()
        st, _ = eng.run(st0, windows)        # compile
        jax.block_until_ready(st.ring)
        wall = _time_best(
            lambda: jax.block_until_ready(eng.run(st0, windows)[0].ring))
        st, _ = eng.run(st0, windows)
        counts = np.asarray(st.spike_count)
        if ref_counts is None:
            ref_counts = counts
        else:
            assert np.array_equal(counts, ref_counts), (
                f"{label} diverged from the reference spike train")
        assert int(st.overflow) == 0, f"{label} dropped spikes"
        cps = windows * D / wall
        if base is None:
            base = cps
        speedup = cps / base
        print(f"{label:14s} {cps:12.1f} {wall:9.3f} {speedup:9.2f}x")
        results.append(dict(
            config=name, phase="engine", backend=label,
            cycles_per_s=round(cps, 2), wall_s=round(wall, 4),
            n_windows=windows, delay_ratio=D, n_neurons=spec.n_total,
            n_pad=net.n_pad, n_areas=spec.n_areas, k_total=spec.k_total,
            ring_len=net.ring_len, spikes=int(counts.sum()),
            speedup_vs_onehot=round(speedup, 3),
        ))


def bench_wire_volume(name, spec, net, results: list):
    """Dense-vs-routed wire bytes per window (static exchange accounting).

    Pure shape/adjacency arithmetic from ``repro.core.exchange`` -- the same
    counters the distributed engines report on ``Engine.wire_bytes`` -- for
    a modelled structure-aware mesh (``min(8, A)`` area groups x 2-device
    subgroups). Recorded for both wire formats: the event backend's id
    packets (routed vs dense is apples-to-apples: fewer rounds AND smaller
    per-edge packets) and the dense backends' bit-packed vectors (the
    routed global pathway always ships id packets, so at dense-graph tiny
    scales packed bits can win -- the table keeps that honest). On a
    sparse area graph the routed exchange must ship strictly fewer global
    bytes than the dense mesh collectives; asserted below for the
    ``*_sparse`` config.
    """
    from repro.core import exchange as exchange_lib
    from repro.core.connectivity import area_adjacency

    A = spec.n_areas
    n_groups = A if A <= 8 else 8
    gsz = 2
    adj = area_adjacency(net, spec)
    print(f"\n-- {name} / wire volume (bytes/window, mesh-total, "
          f"{n_groups} groups x {gsz} subgroup) --")
    print(f"{'backend':10s} {'exchange':10s} {'local':>12s} {'global':>12s} "
          f"{'total':>12s} {'rounds':>7s}")
    out = {}
    for backend in ("event", "scatter"):
        rep = exchange_lib.wire_report(
            net, adj, backend=backend, n_groups=n_groups, gsz=gsz,
            headroom=8.0, floor=4)
        for exch in ("dense", "routed"):
            r = rep[exch]
            rounds = r.get("rounds", max(n_groups - 1, 0))
            print(f"{backend:10s} {exch:10s} {r['local_bytes']:12,d} "
                  f"{r['global_bytes']:12,d} {r['total_bytes']:12,d} "
                  f"{rounds:7d}")
            results.append(dict(
                config=name, phase="wire", backend=backend, exchange=exch,
                local_bytes=r["local_bytes"], global_bytes=r["global_bytes"],
                total_bytes=r["total_bytes"], rounds=rounds,
                edges=r.get("edges"), n_groups=n_groups, gsz=gsz,
                n_areas=A, delay_ratio=net.delay_ratio,
            ))
        out[backend] = rep
    if name.endswith("_sparse"):
        ev = out["event"]
        assert (ev["routed"]["global_bytes"] < ev["dense"]["global_bytes"]), (
            "routed exchange must ship strictly fewer global bytes on a "
            "sparse area graph")
        assert ev["routed"]["rounds"] < ev["routed"]["dense_rounds"], (
            "routing must actually skip rounds on a sparse area graph")
    return out


def bench_adaptive_wire(name, spec, net, results, *, n_groups=None, gsz=2):
    """Static vs adaptive two-phase wire bytes per window (dense + routed).

    The adaptive tentpole's byte claim: phase 1 ships a tiny count
    collective, phase 2 payloads sized by the expectation rung of the
    bucket ladder instead of the static ``headroom x expectation`` bound
    (``exchange.adaptive_wire_bytes``, the same model the engines' runtime
    ``SimState.shipped_bytes`` constants mirror). Each row also prices the
    two-phase exchange with ``cost_model.exchange_time_s`` (alpha + bytes/
    beta per phase) so latency stays honest: the counts phase costs one
    extra dispatch. On the sparse routed config the adaptive payload must
    be measurably smaller than the static bound (asserted).
    """
    from repro.core import cost_model
    from repro.core import exchange as exchange_lib
    from repro.core.connectivity import area_adjacency

    A = spec.n_areas
    if n_groups is None:
        n_groups = A if A <= 8 else 8
    n_dev = n_groups * gsz
    adj = area_adjacency(net, spec)
    rep = exchange_lib.wire_report(
        net, adj, backend="event", n_groups=n_groups, gsz=gsz,
        headroom=8.0, floor=4)
    print(f"\n-- {name} / adaptive two-phase wire (bytes/window, "
          f"mesh-total, {n_groups} groups x {gsz} subgroup, event) --")
    print(f"{'exchange':10s} {'static':>12s} {'counts':>10s} "
          f"{'payload(exp)':>12s} {'worst':>12s} {'saved':>12s}")
    for exch in ("dense", "routed"):
        ad = rep[exch]["adaptive"]
        static = rep[exch]["total_bytes"]
        print(f"{exch:10s} {static:12,d} {ad['counts_bytes']:10,d} "
              f"{ad['payload_bytes_expected']:12,d} "
              f"{ad['payload_bytes_worst']:12,d} {ad['saved_bytes']:12,d}")
        results.append(dict(
            config=name, phase="adaptive", backend="event", exchange=exch,
            static_bytes=static,
            counts_bytes=ad["counts_bytes"],
            payload_bytes_expected=ad["payload_bytes_expected"],
            total_bytes_expected=ad["total_bytes_expected"],
            payload_bytes_worst=ad["payload_bytes_worst"],
            saved_bytes=ad["saved_bytes"],
            buckets=ad["buckets"],
            n_groups=n_groups, gsz=gsz, n_areas=A,
            delay_ratio=net.delay_ratio,
            static_time_s=cost_model.exchange_time_s(
                0, static, n_dev, cost_model.SUPERMUC_MPI),
            two_phase_time_s=cost_model.exchange_time_s(
                ad["counts_bytes"], ad["payload_bytes_expected"], n_dev,
                cost_model.SUPERMUC_MPI),
        ))
    if name.endswith("_sparse"):
        ad = rep["routed"]["adaptive"]
        assert (ad["total_bytes_expected"]
                < rep["routed"]["total_bytes"]), (
            "adaptive exchange must ship measurably fewer bytes than the "
            "static bound on the sparse routed config")
        assert ad["saved_bytes"] > 0, ad
    return rep


def bench_adaptive_wire_production(results):
    """Production-scale (MAM x1, 16x16 mesh) adaptive wire bytes from the
    dry-run's deterministic ShapeDtypeStruct bounds -- no allocation.

    At production scale the static event packets carry the full 8x
    headroom; the expectation-sized adaptive buckets drop most of it, and
    the phase-1 count bytes are noise next to the payload. Asserted so a
    ladder/accounting change can never silently lose the saving.
    """
    from repro.core import delivery
    from repro.core import exchange as exchange_lib
    from repro.core.areas import mam_spec
    from repro.core.connectivity import area_adjacency, network_sds

    spec = mam_spec(scale=1.0)
    n_groups, gsz = 16, 16
    sds = network_sds(spec, size_multiple=16, outgoing=True)
    adj = area_adjacency(sds, spec)
    routing = exchange_lib.build_routing(
        adj, n_groups,
        exp_area_spikes=delivery.expected_area_spikes(sds),
        headroom=8.0, floor=16)
    static = exchange_lib.dense_wire_bytes(
        sds, backend="event", schedule="structure_aware",
        n_groups=n_groups, gsz=gsz)
    rows = {
        "dense": exchange_lib.adaptive_wire_bytes(
            sds, backend="event", n_groups=n_groups, gsz=gsz),
        "routed": exchange_lib.adaptive_wire_bytes(
            sds, backend="event", n_groups=n_groups, gsz=gsz,
            routing=routing),
    }
    print(f"\n-- mam_x1 production / adaptive two-phase wire "
          f"({n_groups} groups x {gsz} subgroup, SDS bounds) --")
    for exch, ad in rows.items():
        print(f"{exch:10s} static {ad['static_total_bytes'] / 2**20:8.1f} "
              f"MiB/window -> adaptive {ad['total_bytes_expected'] / 2**20:8.1f} "
              f"MiB/window (counts {ad['counts_bytes'] / 2**10:.1f} KiB, "
              f"saved {ad['saved_bytes'] / 2**20:.1f} MiB)")
        assert ad["total_bytes_expected"] < ad["static_total_bytes"], (
            f"adaptive must beat the static bound at production scale "
            f"({exch})")
        results.append(dict(
            config="mam_x1_16x16", phase="adaptive", backend="event",
            exchange=exch,
            static_bytes=ad["static_total_bytes"],
            counts_bytes=ad["counts_bytes"],
            payload_bytes_expected=ad["payload_bytes_expected"],
            total_bytes_expected=ad["total_bytes_expected"],
            payload_bytes_worst=ad["payload_bytes_worst"],
            saved_bytes=ad["saved_bytes"],
            n_groups=n_groups, gsz=gsz, sds_bounds=True,
        ))


def bench_table_bytes(name, spec, net, results, *, n_groups=None, gsz=2):
    """Per-device inter receive-table bytes, replicated vs sharded.

    The tentpole's memory claim, measured on *instantiated* widths: the
    replicated outgoing tables put every inter synapse on every device;
    ``connectivity.shard_inter_tables`` re-cuts them into per-group inbound
    slices, dividing the per-device bytes (and the receive scatter's
    synapse touches -- priced with ``cost_model.receive_time_s``) by ~the
    group count. Recorded per exchange: the id volume a device receives
    differs between the dense all_gather and the routed ppermute rounds,
    the table it scatters through is the same.
    """
    from repro.core import cost_model, delivery
    from repro.core import exchange as exchange_lib
    from repro.core.connectivity import area_adjacency

    A = spec.n_areas
    if n_groups is None:
        n_groups = A if A <= 8 else 8
    if spec.k_inter == 0 or net.tgt_inter is None:
        return
    routing = exchange_lib.build_routing(
        area_adjacency(net, spec), n_groups,
        exp_area_spikes=delivery.expected_area_spikes(net),
        headroom=8.0, floor=4)
    rep = exchange_lib.priced_inter_table_report(
        net, n_groups=n_groups, gsz=gsz, headroom=8.0, floor=4,
        routing=routing)
    tb = rep["table_bytes"]
    print(f"\n-- {name} / inter receive tables (bytes/device, "
          f"{n_groups} groups x {gsz} subgroup) --")
    print(f"{'layout':11s} {'bytes/dev':>14s} {'K':>6s} "
          f"{'recv syn-touches/win (dense | routed)':>40s}")
    for layout, key in (("replicated", "replicated"), ("sharded", "sharded")):
        touches = {
            exch: rep["receive"][exch][f"syn_touches_{key}"]
            for exch in rep["receive"]
        }
        print(f"{layout:11s} {tb[key]:14,d} "
              f"{rep['k_out_replicated' if key == 'replicated' else 'k_in_sharded']:6d} "
              f"{touches.get('dense', 0):19,d} | {touches.get('routed', 0):,d}")
    print(f"reduction: {tb['reduction']:.1f}x over {rep['n_shards']} shards")
    for exch, recv in rep["receive"].items():
        results.append(dict(
            config=name, phase="table", backend="event", exchange=exch,
            table_bytes_per_device_replicated=tb["replicated"],
            table_bytes_per_device_sharded=tb["sharded"],
            reduction=round(tb["reduction"], 3),
            k_out_replicated=rep["k_out_replicated"],
            k_in_sharded=rep["k_in_sharded"],
            n_shards=rep["n_shards"], n_groups=n_groups, gsz=gsz,
            ids_per_window=recv["ids_per_window"],
            syn_touches_replicated=recv["syn_touches_replicated"],
            syn_touches_sharded=recv["syn_touches_sharded"],
            receive_s_replicated=cost_model.receive_time_s(
                recv["syn_touches_replicated"], cost_model.SUPERMUC),
            receive_s_sharded=cost_model.receive_time_s(
                recv["syn_touches_sharded"], cost_model.SUPERMUC),
        ))
    return rep


def bench_table_bytes_production(results):
    """Production-scale (MAM x1, 16x16 mesh) table bytes from the dry-run's
    deterministic ShapeDtypeStruct bounds -- no allocation.

    This is the number that makes multi-host runs possible at all: the
    replicated inter tables cost ~150 GiB/device at production scale (the
    ROADMAP's quantified scaling wall); the sharded inbound slices divide
    that by ~the 16-way group count. Asserted, so the benchmark fails if a
    table-layout change ever loses the reduction.
    """
    from repro.core import exchange as exchange_lib
    from repro.core.areas import mam_spec
    from repro.core.connectivity import network_sds

    spec = mam_spec(scale=1.0)
    n_groups, gsz = 16, 16
    sds_rep = network_sds(spec, size_multiple=16, outgoing=True)
    rep = exchange_lib.priced_inter_table_report(
        sds_rep, n_groups=n_groups, gsz=gsz)
    tb = rep["table_bytes"]
    print(f"\n-- mam_x1 production / inter receive tables "
          f"({n_groups} groups x {gsz} subgroup, SDS bounds) --")
    print(f"replicated {tb['replicated'] / 2**30:8.1f} GiB/dev "
          f"(K={rep['k_out_replicated']})")
    print(f"sharded    {tb['sharded'] / 2**30:8.1f} GiB/dev "
          f"(K={rep['k_in_sharded']}, {rep['n_shards']} shards) "
          f"-> {tb['reduction']:.1f}x")
    # ~the group count; the sharded width bound carries extra per-shard
    # slack (+6 sigma + 16 on a 16x smaller mean), so allow 0.6x of it.
    assert tb["reduction"] >= 0.6 * n_groups, (
        f"sharded inter tables must cut per-device bytes by ~the group "
        f"count ({n_groups}); got {tb['reduction']:.1f}x")
    results.append(dict(
        config="mam_x1_16x16", phase="table", backend="event",
        exchange="dense",
        table_bytes_per_device_replicated=tb["replicated"],
        table_bytes_per_device_sharded=tb["sharded"],
        reduction=round(tb["reduction"], 3),
        k_out_replicated=rep["k_out_replicated"],
        k_in_sharded=rep["k_in_sharded"],
        n_shards=rep["n_shards"], n_groups=n_groups, gsz=gsz,
        sds_bounds=True,
    ))


def bench_table_memory(name, spec, net, results, *, n_groups=None, gsz=2):
    """Per-device inter-table bytes across the three layouts
    (phase=memory): replicated outgoing, per-group inbound slices (PR 4),
    and inbound+subgroup slices (the memory-diet tentpole). The subgroup
    numbers come from actually cutting the instantiated tables, so the
    row prices real widths, not bounds."""
    from repro.core import exchange as exchange_lib

    if spec.k_inter == 0 or net.tgt_inter is None:
        return
    if net.n_pad % gsz != 0:
        # The subgroup cut needs the neuron window to tile the padded area
        # (odd n_pad configs exist in the full sweep); nothing to price.
        print(f"\n-- {name} / memory diet: skipped "
              f"(n_pad={net.n_pad} not divisible by subgroup={gsz})")
        return
    A = spec.n_areas
    if n_groups is None:
        n_groups = A if A <= 8 else 8
    rep_in = exchange_lib.priced_inter_table_report(
        net, n_groups=n_groups, gsz=gsz)
    rep_sub = exchange_lib.priced_inter_table_report(
        net, n_groups=n_groups, gsz=gsz, subgroup=gsz)
    b_rep = rep_in["table_bytes"]["replicated"]
    b_in = rep_in["table_bytes"]["sharded"]
    b_sub = rep_sub["table_bytes"]["sharded"]
    shrink = b_in / b_sub if b_sub else float("inf")
    print(f"\n-- {name} / memory diet (bytes/device, {n_groups} groups x "
          f"{gsz} subgroup, {net.bytes_per_synapse()} B/syn) --")
    print(f"{'replicated':16s} {b_rep:14,d}  K={rep_in['k_out_replicated']}")
    print(f"{'inbound':16s} {b_in:14,d}  K={rep_in['k_in_sharded']} "
          f"({rep_in['table_bytes']['reduction']:.1f}x)")
    print(f"{'inbound+subgroup':16s} {b_sub:14,d}  "
          f"K={rep_sub['k_in_sharded']} "
          f"({rep_sub['table_bytes']['reduction']:.1f}x, "
          f"{shrink:.1f}x vs inbound)")
    results.append(dict(
        config=name, phase="memory", backend="event", exchange="dense",
        bytes_per_device_replicated=b_rep,
        bytes_per_device_inbound=b_in,
        bytes_per_device_subgroup=b_sub,
        k_in_inbound=rep_in["k_in_sharded"],
        k_in_subgroup=rep_sub["k_in_sharded"],
        reduction_inbound=round(rep_in["table_bytes"]["reduction"], 3),
        reduction_subgroup=round(rep_sub["table_bytes"]["reduction"], 3),
        subgroup_slice_shrink=round(shrink, 3),
        bytes_per_synapse=net.bytes_per_synapse(),
        n_groups=n_groups, gsz=gsz,
    ))


def bench_table_memory_production(results):
    """Production memory-diet row (MAM x1, 16x16 mesh, SDS width bounds):
    the per-device inter slice must shrink by >= 4x going from the PR 4
    per-group inbound layout to the subgroup-sliced one (the acceptance
    bar of the 16 GiB diet; the ideal is gsz=16x, the bound's +6 sigma+16
    slack on a 256x smaller mean eats part of it). Asserted, so the
    benchmark fails if the slice ever fattens back up."""
    from repro.core import exchange as exchange_lib
    from repro.core.areas import mam_spec
    from repro.core.connectivity import network_sds

    spec = mam_spec(scale=1.0)
    n_groups, gsz = 16, 16
    sds_rep = network_sds(spec, size_multiple=16, outgoing=True)
    rep_in = exchange_lib.priced_inter_table_report(
        sds_rep, n_groups=n_groups, gsz=gsz)
    rep_sub = exchange_lib.priced_inter_table_report(
        sds_rep, n_groups=n_groups, gsz=gsz, subgroup=gsz)
    b_rep = rep_in["table_bytes"]["replicated"]
    b_in = rep_in["table_bytes"]["sharded"]
    b_sub = rep_sub["table_bytes"]["sharded"]
    shrink = b_in / b_sub
    print(f"\n-- mam_x1 production / memory diet ({n_groups} groups x "
          f"{gsz} subgroup, SDS bounds, "
          f"{sds_rep.bytes_per_synapse()} B/syn) --")
    print(f"replicated       {b_rep / 2**30:8.1f} GiB/dev")
    print(f"inbound          {b_in / 2**30:8.1f} GiB/dev "
          f"(K={rep_in['k_in_sharded']})")
    print(f"inbound+subgroup {b_sub / 2**30:8.1f} GiB/dev "
          f"(K={rep_sub['k_in_sharded']}, {shrink:.1f}x vs inbound)")
    assert shrink >= 4.0, (
        f"subgroup slicing must shrink the production inter slice >= 4x "
        f"over the per-group inbound layout; got {shrink:.1f}x")
    results.append(dict(
        config="mam_x1_16x16", phase="memory", backend="event",
        exchange="dense",
        bytes_per_device_replicated=b_rep,
        bytes_per_device_inbound=b_in,
        bytes_per_device_subgroup=b_sub,
        k_in_inbound=rep_in["k_in_sharded"],
        k_in_subgroup=rep_sub["k_in_sharded"],
        reduction_inbound=round(rep_in["table_bytes"]["reduction"], 3),
        reduction_subgroup=round(rep_sub["table_bytes"]["reduction"], 3),
        subgroup_slice_shrink=round(shrink, 3),
        bytes_per_synapse=sds_rep.bytes_per_synapse(),
        n_groups=n_groups, gsz=gsz, sds_bounds=True,
    ))


def bench_build(name, spec, results, *, n_shards=4, subgroup=2):
    """Construction wall + modelled host bytes, host vs sharded build
    (phase=build).

    Times the host path (``build_network(outgoing='intra')`` + both shard
    cuts -- what one process pays to construct every device's tables)
    against the sharded path (``sharded_build_plan`` + ONE shard-lane's
    ``build_shard_tables``/``build_lane_intra_tables`` -- what each device
    pays when all shards build their own tables concurrently). Shard 0's
    regenerated tables are asserted bitwise-equal to the host cut, so the
    benchmark is also an equivalence test. The modelled byte fields are
    pure width-bound arithmetic (``construction_cost_model``) and
    smoke-guarded against regression.
    """
    import numpy as np

    from repro.core.connectivity import (
        build_lane_intra_tables, build_network, build_shard_tables,
        construction_cost_model, shard_inter_tables, sharded_build_plan,
        slice_intra_tables)

    A = spec.n_areas
    S = min(n_shards, A)
    mult = 2 * subgroup  # even padded size so the subgroup windows tile

    def host():
        net = build_network(spec, seed=12, size_multiple=mult,
                            outgoing="intra")
        cut = shard_inter_tables(net, S, mode="group", subgroup=subgroup)
        return slice_intra_tables(cut, subgroup)

    def shard0():
        plan = sharded_build_plan(spec, 12, S, mode="group",
                                  subgroup=subgroup, size_multiple=mult)
        t, w, d = build_shard_tables(spec, 12, 0, plan=plan, lane=0)
        ti = build_lane_intra_tables(
            spec, 12, list(range(A // S)), 0, plan=plan)
        return t, w, d, ti

    wall_host = _time_best(host, repeats=2)
    wall_shard = _time_best(shard0, repeats=2)
    cut = host()
    t, w, d, ti = shard0()
    assert np.array_equal(np.asarray(cut.tgt_inter_in[0, 0]), t), (
        "sharded build diverged from the host-built inbound slice")
    assert np.array_equal(np.asarray(cut.wout_inter_in[0, 0]), w)
    assert np.array_equal(np.asarray(cut.dout_inter_in[0, 0]), d)
    assert np.array_equal(np.asarray(cut.tgt_intra[0][: A // S]), ti[0]), (
        "sharded build diverged from the host-built lane intra tables")
    cm = construction_cost_model(
        spec, n_shards=S, subgroup=subgroup, size_multiple=mult)
    print(f"\n-- {name} / construction ({S} shards x {subgroup} lanes) --")
    print(f"host build     {wall_host:8.3f} s  "
          f"(modelled {cm['build_bytes_host_modelled'] / 2**20:8.1f} MiB)")
    print(f"per-shard build{wall_shard:8.3f} s  "
          f"(modelled {cm['build_bytes_shard_modelled'] / 2**20:8.1f} MiB, "
          f"{cm['reduction']:.1f}x)")
    results.append(dict(
        config=name, phase="build", backend="event",
        wall_host_s=round(wall_host, 4),
        wall_shard_s=round(wall_shard, 4),
        build_bytes_host_modelled=cm["build_bytes_host_modelled"],
        build_bytes_shard_modelled=cm["build_bytes_shard_modelled"],
        reduction_modelled=round(cm["reduction"], 2),
        n_shards=S, subgroup=subgroup, n_areas=A,
        n_neurons=spec.n_total, k_total=spec.k_total,
    ))


def bench_build_production(results):
    """Production construction row (MAM x1, 16x16 mesh, width bounds):
    modelled host peak RSS of building the network, host path vs sharded.

    The host path materialises the global incoming tensors of ~2.4e12
    synapses plus all 256 inbound slices in one process -- construction,
    not simulation, becomes the scaling wall once the run itself fits in
    16 GiB devices. The sharded build's per-process peak (one shard-lane's
    draws + output slice + the planning counts) must come in >= 4x under
    it (the PR's acceptance bar; the real gap is ~65x). Asserted, so a
    builder change can never silently re-grow the host footprint.
    """
    from repro.core.areas import mam_spec
    from repro.core.connectivity import construction_cost_model

    spec = mam_spec(scale=1.0)
    cm = construction_cost_model(
        spec, n_shards=16, subgroup=16, size_multiple=16)
    print(f"\n-- mam_x1 production / construction (16 shards x 16 lanes, "
          f"width bounds) --")
    print(f"host build  {cm['build_bytes_host_modelled'] / 2**30:8.1f} GiB "
          f"peak RSS")
    print(f"sharded     {cm['build_bytes_shard_modelled'] / 2**30:8.1f} GiB "
          f"peak RSS/process -> {cm['reduction']:.1f}x")
    assert cm["reduction"] >= 4.0, (
        f"sharded build must cut the production construction host RSS "
        f">= 4x; got {cm['reduction']:.1f}x")
    results.append(dict(
        config="mam_x1_16x16", phase="build", backend="event",
        build_bytes_host_modelled=cm["build_bytes_host_modelled"],
        build_bytes_shard_modelled=cm["build_bytes_shard_modelled"],
        reduction_modelled=round(cm["reduction"], 2),
        n_shards=16, subgroup=16, sds_bounds=True,
    ))


def bench_resilience(name, spec, net, results, *, windows=300, cadence=50):
    """Checkpoint overhead + fault harness, end to end (phase=resilience).

    Three legs on the quickstart event engine through the resilient run
    loop (``schedule.run_windows``, one dispatch per window):

    * **overhead** -- best-of-3 wall with window-boundary checkpoints at the
      every-``cadence`` cadence vs the same loop bare. The async writer
      serialises off-thread, so the paid cost is one ``device_get`` per
      checkpoint; asserted < 5% (the tentpole's overhead budget).
    * **transient I/O** -- the first 2 checkpoint writes fail (injected
      ``OSError``); the run must complete with exactly 2 writer retries and
      a readable latest checkpoint.
    * **jitter** -- per-device compute jitter from the paper's §2.2
      cycle-time model; the injected per-window straggler time must match
      the order-statistics prediction (Blom) within 10%, tying the fault
      harness to ``repro.core.sync_model``.
    """
    import shutil
    import tempfile

    import jax
    import numpy as np

    from repro.checkpoint import manager as ckpt_manager
    from repro.core import faults as faults_lib
    from repro.core import schedule as schedule_lib
    from repro.core.engine import EngineConfig
    from repro.core.factory import make_simulation

    eng = make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", schedule="structure_aware",
        delivery_backend="event", s_max_floor=4), net=net)
    st0 = eng.init()
    jax.block_until_ready(eng.window(st0)[0].ring)  # compile

    def run(ckpt_dir=None, injector=None, onpath=None):
        ckpt = None
        if ckpt_dir is not None:
            ckpt = schedule_lib.SimCheckpointer(
                ckpt_dir, eng, net, every=cadence, injector=injector)
            if onpath is not None:
                # Attribute the synchronous cost a checkpoint adds to the
                # run loop (device_get + queue handoff; serialisation is
                # off-thread) by timing the cadence hook in place.
                inner = ckpt.maybe_save

                def timed_maybe_save(st, window=None):
                    t0 = time.perf_counter()
                    out = inner(st, window=window)
                    onpath.append(time.perf_counter() - t0)
                    return out

                ckpt.maybe_save = timed_maybe_save
        res = schedule_lib.run_windows(
            eng, st0, windows, checkpointer=ckpt, faults=injector)
        if ckpt is not None:
            ckpt.close()
        return res, ckpt

    tmp = tempfile.mkdtemp(prefix="bench_resilience_")
    try:
        # Interleaved bare/checkpointed pairs; minima over pairs reject the
        # positive-only OS noise. At this scale (ms windows) run-to-run
        # drift can still exceed the true per-checkpoint cost, so the
        # <5% wall budget is asserted only when the measured bare-run
        # spread says the box can resolve it; the synchronous on-path cost
        # (timed at the cadence hook) is asserted unconditionally.
        bare_walls, ckpt_walls, n_ckpts = [], [], 0
        onpath: list = []
        for _ in range(5):
            bare_walls.append(float(run()[0].window_times_s.sum()))
            res, ckpt = run(ckpt_dir=tmp, onpath=onpath)
            ckpt_walls.append(float(res.window_times_s.sum()))
            n_ckpts = len(ckpt.saved_windows)
        base_wall = min(bare_walls)
        ckpt_wall = min(ckpt_walls)
        noise_frac = max(bare_walls) / base_wall - 1.0
        onpath_frac = sum(onpath) / len(ckpt_walls) / base_wall

        # Transient-write leg: first 2 saves fail, the run must shrug.
        shutil.rmtree(tmp, ignore_errors=True)
        inj = faults_lib.FaultInjector(
            faults_lib.FaultConfig(ckpt_write_failures=2, seed=7),
            n_devices=jax.device_count(), delay_ratio=net.delay_ratio)
        _, ckpt = run(ckpt_dir=tmp, injector=inj)
        retries = ckpt.retry_count
        assert retries == 2, (
            f"expected exactly 2 transient-write retries, got {retries}")
        assert ckpt_manager.latest_step(tmp) is not None, (
            "no readable checkpoint after the transient-failure leg")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    # Jitter leg: injected straggler time vs the sync model's prediction.
    jinj = faults_lib.FaultInjector(
        faults_lib.FaultConfig(
            jitter_mu_ms=0.5, jitter_sigma_ms=0.1, jitter_devices=8, seed=3),
        n_devices=jax.device_count(), delay_ratio=net.delay_ratio)
    jres, _ = run(injector=jinj)
    predicted_s = jinj.predicted_jitter_s()
    injected_s = jres.injected_sleep_s / windows
    wall_infl_s = float(jres.window_times_s.mean()) - base_wall / windows
    assert abs(injected_s / predicted_s - 1) < 0.10, (
        f"injected jitter {injected_s * 1e3:.3f} ms/window strays from the "
        f"sync-model prediction {predicted_s * 1e3:.3f} ms/window")

    overhead = ckpt_wall / base_wall - 1.0
    print(f"\n-- {name} / resilience ({windows} windows, checkpoint every "
          f"{cadence}) --")
    print(f"bare loop      {base_wall:8.3f} s  (run-to-run noise "
          f"{noise_frac * 100:+.2f}%)")
    print(f"checkpointed   {ckpt_wall:8.3f} s  ({n_ckpts} checkpoints, "
          f"overhead {overhead * 100:+.2f}%, on-path "
          f"{onpath_frac * 100:.3f}%)")
    print(f"transient I/O  {retries} injected write failures retried, "
          f"run completed")
    print(f"jitter         injected {injected_s * 1e3:.2f} ms/window vs "
          f"predicted {predicted_s * 1e3:.2f} (wall inflation "
          f"{wall_infl_s * 1e3:.2f})")
    # The synchronous cost the cadence hook adds to the loop is pure
    # device_get + queue handoff -- deterministic, so asserted tight.
    assert onpath_frac < 0.01, (
        f"checkpoint on-path cost {onpath_frac * 100:.2f}% -- the submit "
        f"path should be microseconds, something is blocking the loop")
    if noise_frac < 0.04:
        assert overhead < 0.05, (
            f"checkpoint overhead {overhead * 100:.1f}% breaches the 5% "
            f"budget at the every-{cadence}-windows cadence (measured "
            f"noise floor {noise_frac * 100:.1f}%)")
    else:
        print(f"(wall-clock 5% guard skipped: bare-run noise "
              f"{noise_frac * 100:.1f}% cannot resolve it; on-path guard "
              f"still enforced)")
    results.append(dict(
        config=name, phase="resilience", backend="event",
        n_windows=windows, cadence=cadence, n_checkpoints=n_ckpts,
        wall_base_s=round(base_wall, 4), wall_ckpt_s=round(ckpt_wall, 4),
        overhead_frac=round(overhead, 4),
        onpath_frac=round(onpath_frac, 6),
        noise_frac=round(noise_frac, 4), ckpt_retries=retries,
        jitter_predicted_s=round(predicted_s, 6),
        jitter_injected_s=round(injected_s, 6),
        jitter_wall_inflation_s=round(wall_infl_s, 6),
        delay_ratio=net.delay_ratio, n_neurons=spec.n_total,
    ))


def bench_overlap(name, spec, net, results, *, windows=40):
    """Sequential vs double-buffered overlapped window pipeline
    (phase=overlap).

    Two legs on the quickstart event engine:

    * **bit-identity + raw wall** -- ``Engine.run`` with and without
      ``overlap_exchange``: identical spikes/rings/shipped_bytes (asserted),
      best-of-3 walls recorded. On one CPU host there is no communication to
      hide, so the walls are reported, not compared.
    * **jitter absorption** -- both engines through the resilient loop under
      the paper's injected compute + exchange stragglers. The sequential
      loop's injected wall realizes ``sum(compute_w + comm_w)``; the
      pipelined loop realizes ``comp_1 + sum(max(comp_w, comm_{w-1})) +
      comm_n`` -- strictly smaller, and both within 15% of the extended
      sync model (``sync_model.expected_wall_overlapped``, Clark's E[max]).
      Asserted; the injected walls are pure functions of (seed, window), so
      the recorded row is deterministic and smoke-guarded against any
      shrink in what the overlap hides.

    ``windows`` is fixed (not scaled down by --smoke) so the smoke run's
    rows stay comparable to the recorded baseline.
    """
    import math

    import jax
    import numpy as np

    from repro.core import faults as faults_lib
    from repro.core import schedule as schedule_lib
    from repro.core import sync_model
    from repro.core.engine import EngineConfig
    from repro.core.factory import make_simulation

    kw = dict(neuron_model="ignore_and_fire", schedule="structure_aware",
              delivery_backend="event", s_max_floor=4)
    seq = make_simulation(spec, EngineConfig(**kw), net=net)
    ovl = make_simulation(spec, EngineConfig(overlap_exchange=True, **kw), net=net)
    st0 = seq.init()
    jax.block_until_ready(seq.run(st0, windows)[0].ring)  # compile
    jax.block_until_ready(ovl.run(st0, windows)[0].ring)
    wall_seq = _time_best(
        lambda: jax.block_until_ready(seq.run(st0, windows)[0].ring))
    wall_ovl = _time_best(
        lambda: jax.block_until_ready(ovl.run(st0, windows)[0].ring))
    a, b = seq.run(st0, windows)[0], ovl.run(st0, windows)[0]
    assert np.array_equal(np.asarray(a.spike_count),
                          np.asarray(b.spike_count)), (
        "overlapped pipeline diverged from the sequential spike train")
    assert np.array_equal(np.asarray(a.ring), np.asarray(b.ring))
    assert float(a.shipped_bytes) == float(b.shipped_bytes)
    assert int(b.overflow) == 0, "overlapped pipeline dropped spikes"

    fcfg = faults_lib.FaultConfig(
        jitter_mu_ms=0.5, jitter_sigma_ms=0.1, jitter_devices=8,
        comm_mu_ms=6.0, comm_sigma_ms=0.5, seed=3)

    def injector():
        return faults_lib.FaultInjector(
            fcfg, n_devices=jax.device_count(), delay_ratio=net.delay_ratio)

    res_seq = schedule_lib.run_windows(seq, st0, windows, faults=injector())
    res_ovl = schedule_lib.run_windows(ovl, st0, windows, faults=injector())
    assert res_ovl.overlapped and res_ovl.drains == 1
    assert np.array_equal(res_ovl.spikes_per_window,
                          res_seq.spikes_per_window)
    inj = injector()
    mu_c, mu_x = inj.predicted_jitter_s(), inj.predicted_comm_s()
    pred_seq = windows * (mu_c + mu_x)
    pred_ovl = sync_model.expected_wall_overlapped(
        windows, mu_c, math.sqrt(net.delay_ratio) * inj.model.sigma,
        mu_x, fcfg.comm_sigma_ms * 1e-3)
    hidden = 1 - res_ovl.injected_sleep_s / res_seq.injected_sleep_s
    assert res_ovl.injected_sleep_s < res_seq.injected_sleep_s, (
        "pipelined injected wall failed to beat the sequential sum")
    assert abs(res_seq.injected_sleep_s / pred_seq - 1) < 0.15, (
        f"sequential injected wall {res_seq.injected_sleep_s:.3f} s strays "
        f"from the sum prediction {pred_seq:.3f} s")
    assert abs(res_ovl.injected_sleep_s / pred_ovl - 1) < 0.15, (
        f"pipelined injected wall {res_ovl.injected_sleep_s:.3f} s strays "
        f"from the E[max] prediction {pred_ovl:.3f} s")

    print(f"\n-- {name} / overlapped exchange ({windows} windows, "
          f"injected comm {fcfg.comm_mu_ms} ms/window) --")
    print(f"raw wall       sequential {wall_seq:8.3f} s vs overlapped "
          f"{wall_ovl:8.3f} s (single host: nothing to hide)")
    print(f"injected wall  sequential {res_seq.injected_sleep_s:8.3f} s "
          f"(sum; predicted {pred_seq:.3f}) vs overlapped "
          f"{res_ovl.injected_sleep_s:8.3f} s (max; predicted "
          f"{pred_ovl:.3f}) -> {hidden * 100:.1f}% hidden")
    results.append(dict(
        config=name, phase="overlap", backend="event", exchange="local",
        n_windows=windows,
        wall_sequential_s=round(wall_seq, 4),
        wall_overlap_s=round(wall_ovl, 4),
        injected_sequential_s=round(res_seq.injected_sleep_s, 6),
        injected_overlap_s=round(res_ovl.injected_sleep_s, 6),
        predicted_sequential_s=round(pred_seq, 6),
        predicted_overlap_s=round(pred_ovl, 6),
        hidden_frac=round(hidden, 4), drains=res_ovl.drains,
        comm_mu_ms=fcfg.comm_mu_ms, jitter_mu_ms=fcfg.jitter_mu_ms,
        delay_ratio=net.delay_ratio, n_neurons=spec.n_total,
    ))


def bench_serve(name, spec, results, *, trials=16, windows=4, batch=8,
                assert_speedup=False):
    """Multi-tenant serving throughput (phase=serve): folded batch vs two
    sequential-loop baselines.

    Three runs over the SAME request list:

    * ``batched`` -- the server with ``max_batch=batch``: folds up to
      ``batch`` trials into one block-diagonal dispatch against the
      startup-warmed executable.
    * ``sequential`` -- the server with ``max_batch=1``: identical
      machinery and warm executable, no folding (one dispatch per trial).
      Isolates the fold's per-window overhead amortisation, which on a
      1-core CPU host is small (per-neuron compute dominates the window,
      and that scales with the fold) -- reported as ``speedup_warm``, not
      asserted.
    * ``cold`` -- the sequential-loop baseline *without* the serving
      layer: what each tenant paid before serve.py existed, one process
      per trial building its own engine and jit-compiling its own window
      (process startup and imports generously excluded; ``clear_caches``
      between trials stands in for process isolation). The server's
      startup warm amortises exactly this cost across every trial it
      ever serves, and ``assert_speedup`` requires the batched server to
      clear 2x this baseline's throughput.

    Asserted always: every batched trial's spike train is bitwise
    identical to the warm sequential server's, with overflow 0 (the
    fold's exactness condition). ``total_spikes``/``overflow`` are
    deterministic (counter-based drive), so the smoke run guards them
    against the recorded baseline: a change means served trajectories
    moved, which bitwise serving must never do.
    """
    import jax
    import numpy as np

    from repro.core.engine import EngineConfig
    from repro.core.factory import make_simulation
    from repro.core.neuron import LIFParams
    from repro.launch.serve import SimServer, TrialRequest

    # Spiking regime for the short horizon (see launch/serve.py --selftest):
    # lowered threshold, population-hard per-area packet floor.
    cfg = EngineConfig(
        delivery_backend="event", lif=LIFParams(v_th_mv=2.0),
        s_max_floor=max(16, spec.padded_area_size(1)))
    rng = np.random.default_rng(0)
    reqs = [
        TrialRequest(seed=int(rng.integers(1, 2**31)),
                     stim=float(rng.uniform(0.9, 1.1)), windows=windows)
        for _ in range(trials)
    ]

    runs = {}
    for label, B in (("batched", batch), ("sequential", 1)):
        with SimServer(spec, cfg, max_batch=B, max_windows=windows) as srv:
            t0 = time.perf_counter()
            handles = [srv.submit(r) for r in reqs]
            res = [h.result(timeout=1200) for h in handles]
            wall = time.perf_counter() - t0
        runs[label] = (res, wall, srv.stats())

    res_b, wall_b, stats_b = runs["batched"]
    res_s, wall_s, stats_s = runs["sequential"]
    for rb, rs in zip(res_b, res_s):
        assert rb.overflow == 0 and rs.overflow == 0, (
            "serve bench overflowed; the fold's exactness condition broke")
        assert np.array_equal(rb.spikes, rs.spikes), (
            f"seed={rb.request.seed}: batched spike train diverged from "
            "the sequential-loop baseline")
    total_spikes = int(sum(int(r.spikes.sum()) for r in res_b))
    speedup_warm = wall_s / wall_b

    # The cold baseline: each trial as its own client, paying engine build
    # + jit compile itself. Timed over a few trials -- the rate is honest
    # (measured, not extrapolated); each extra trial would cost the same.
    n_cold = min(3, trials)
    t0 = time.perf_counter()
    for r in reqs[:n_cold]:
        jax.clear_caches()
        eng = make_simulation(spec, cfg)
        st = eng.init(seed=r.seed, stim=r.stim)
        for _ in range(r.windows):
            st, blk = eng.window(st)
        jax.block_until_ready(blk)
    wall_cold_per_trial = (time.perf_counter() - t0) / n_cold
    wall_cold = wall_cold_per_trial * trials
    speedup = wall_cold / wall_b

    print(f"\n-- {name} / serving ({trials} trials x {windows} windows, "
          f"batch {batch} vs 1) --")
    print(f"batched    {trials / wall_b:8.2f} trials/s  "
          f"(p50 {stats_b['p50_ms']:8.1f} ms, p99 {stats_b['p99_ms']:8.1f} "
          f"ms)")
    print(f"sequential {trials / wall_s:8.2f} trials/s  "
          f"(p50 {stats_s['p50_ms']:8.1f} ms, p99 {stats_s['p99_ms']:8.1f} "
          f"ms)")
    print(f"cold       {1 / wall_cold_per_trial:8.2f} trials/s  "
          f"(per-trial engine build + compile, {n_cold} measured)")
    print(f"speedup    {speedup:8.2f}x vs cold clients, "
          f"{speedup_warm:.2f}x vs the warm loop  ({total_spikes} spikes, "
          f"bitwise identical, overflow 0)")
    if assert_speedup:
        assert speedup >= 2.0, (
            f"batched serving speedup {speedup:.2f}x < 2x the per-trial "
            "cold-client baseline")

    results.append(dict(
        config=name, phase="serve", backend="event", exchange="local",
        max_batch=batch, n_trials=trials, n_windows=windows,
        trials_per_s=round(trials / wall_b, 4),
        trials_per_s_sequential=round(trials / wall_s, 4),
        trials_per_s_cold=round(1 / wall_cold_per_trial, 4),
        p50_ms=round(stats_b["p50_ms"], 2),
        p99_ms=round(stats_b["p99_ms"], 2),
        p50_ms_sequential=round(stats_s["p50_ms"], 2),
        p99_ms_sequential=round(stats_s["p99_ms"], 2),
        speedup=round(speedup, 3),
        speedup_warm=round(speedup_warm, 3),
        overflow=0, total_spikes=total_spikes,
        delay_ratio=spec.delay_ratio, n_neurons=spec.n_total,
    ))


# Static (deterministic) per-row byte fields the smoke run guards against
# regressions: any increase vs the recorded BENCH_delivery.json baseline
# fails CI -- wire bytes and table bytes are pure shape arithmetic, so an
# increase is a real regression, never noise.
_STATIC_GUARDED = {
    "wire": ("local_bytes", "global_bytes", "total_bytes"),
    "table": ("table_bytes_per_device_sharded",
              "table_bytes_per_device_replicated"),
    # Memory-diet rows: the three per-device table layouts are pure shape
    # arithmetic (instantiated widths on laptop configs, SDS bounds at
    # production scale) -- any byte increase is a layout regression.
    "memory": ("bytes_per_device_replicated", "bytes_per_device_inbound",
               "bytes_per_device_subgroup"),
    # Adaptive two-phase rows: count-collective overhead, expectation-
    # window total, and the hard-cap worst case are all pure shape
    # arithmetic -- any increase vs the recorded baseline is a regression
    # of the adaptive path's byte model, never noise.
    "adaptive": ("counts_bytes", "total_bytes_expected",
                 "payload_bytes_worst"),
    # Overlap rows: the injected walls are pure functions of the fault
    # seed and window count (fixed, --smoke included), so any increase is
    # a real loss of pipelining/absorption, never noise.
    "overlap": ("injected_overlap_s", "injected_sequential_s"),
    # Construction rows: both modelled peaks are pure width-bound
    # arithmetic -- a host-bytes increase means a builder re-grew what one
    # process materialises; a shard-bytes increase means the per-device
    # build lost its diet.
    "build": ("build_bytes_host_modelled", "build_bytes_shard_modelled"),
    # Serving rows: the counter-based drive makes every served spike train
    # deterministic, so total spikes and overflow are exact -- any growth
    # means the batched fold changed a trajectory (or started clipping),
    # which bitwise serving must never do.
    "serve": ("overflow", "total_spikes"),
}


def _check_static_regression(results, baseline_path):
    """Fail if a static wire/table byte counter grew vs the recorded file."""
    if not os.path.exists(baseline_path):
        print(f"(no baseline at {baseline_path}; regression check skipped)")
        return
    with open(baseline_path) as f:
        base_rows = json.load(f).get("results", [])
    key = lambda r: (r["config"], r["phase"], r["backend"], r.get("exchange"))
    base = {key(r): r for r in base_rows if r["phase"] in _STATIC_GUARDED}
    checked, failures = 0, []
    for r in results:
        if r["phase"] not in _STATIC_GUARDED:
            continue
        b = base.get(key(r))
        if b is None:
            continue
        for field in _STATIC_GUARDED[r["phase"]]:
            if field not in r or field not in b:
                continue
            checked += 1
            if r[field] > b[field]:
                failures.append(
                    f"{key(r)} {field}: {r[field]:,} > baseline {b[field]:,}")
    if failures:
        raise SystemExit(
            "static byte regression vs BENCH_delivery.json:\n  "
            + "\n  ".join(failures))
    print(f"static wire/table bytes: {checked} fields checked against "
          f"baseline, no regression")


def _representative_spikes(spec, net):
    """A real spike raster cycle from a warmed-up reference run."""
    import numpy as np

    from repro.core.engine import EngineConfig
    from repro.core.factory import make_simulation

    eng = make_simulation(spec, EngineConfig(
        neuron_model="ignore_and_fire", schedule="structure_aware"), net=net)
    st = eng.init()
    st, blk = eng.window(st)
    blk = np.asarray(blk)
    # pick the window cycle with the median activity
    per_cycle = blk.reshape(blk.shape[0], -1).sum(axis=1)
    return blk[int(np.argsort(per_cycle)[len(per_cycle) // 2])]


def main(argv=None) -> None:
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
    ap = argparse.ArgumentParser()
    ap.add_argument("--windows", type=int, default=10,
                    help="timed windows (of D cycles each) per backend")
    ap.add_argument("--cycles", type=int, default=100,
                    help="deliver-phase scan length per timing")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(__file__), "..", "BENCH_delivery.json"))
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke mode: quickstart config only, tiny cycle "
                         "counts, results NOT written to --out. Exercises "
                         "every backend row (incl. the superstep and fused-"
                         "kernel engine paths) plus the bit-exactness and "
                         "overflow assertions, so the benchmark cannot rot.")
    args = ap.parse_args(argv)
    if args.smoke:
        args.windows = min(args.windows, 3)
        args.cycles = min(args.cycles, 20)
    if args.windows < 1 or args.cycles < 1:
        ap.error("--windows and --cycles must be >= 1")

    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.core.areas import (
        mam_benchmark_spec, mam_spec, ring_area_adjacency)
    from repro.core.connectivity import build_network
    from repro.kernels.ops import default_interpret

    enable_compile_cache()

    results: list[dict] = []
    configs = [
        # The quickstart network (examples/quickstart.py), where dense
        # delivery is at its most wasteful: K=64 synapses over a 101-slot
        # ring with ~0.025%-scale per-cycle firing.
        ("quickstart", mam_benchmark_spec(
            n_areas=4, n_per_area=256, k_intra=32, k_inter=32)),
        # Laptop-scale 32-area MAM: heterogeneous sizes/rates, D=10.
        ("mam_x0.001", mam_spec(scale=0.001)),
        # A deliberately sparse area graph (directed ring, width 2 of 8
        # areas): the connectivity-routed exchange must skip rounds and
        # ship strictly fewer global bytes here (asserted).
        ("quickstart_sparse", mam_benchmark_spec(
            n_areas=8, n_per_area=256, k_intra=32, k_inter=32,
            area_adjacency=ring_area_adjacency(8, width=2))),
    ]
    if args.smoke:
        configs = [configs[0], configs[2]]
    for name, spec in configs:
        net = build_network(spec, seed=12, outgoing=True)
        print(f"\n== {name}: {spec.n_areas} areas x {net.n_pad} pad "
              f"({spec.n_total} live), K={spec.k_total}, "
              f"D={net.delay_ratio}, ring={net.ring_len} ==")
        if not name.endswith("_sparse"):
            spikes = _representative_spikes(spec, net)
            bench_deliver_phase(name, spec, net, spikes, args.cycles, results)
            bench_engine(name, spec, net, args.windows, results)
        bench_wire_volume(name, spec, net, results)
        bench_adaptive_wire(name, spec, net, results)
        bench_table_bytes(name, spec, net, results)
        bench_table_memory(name, spec, net, results)
        bench_build(name, spec, results)
        if name == "quickstart":
            bench_resilience(name, spec, net, results)
            bench_overlap(name, spec, net, results)
            # Fixed trial mix (not scaled by --smoke) so the smoke run's
            # guarded total_spikes/overflow are comparable to the baseline.
            bench_serve(name, spec, results, trials=8, windows=3, batch=4)
        if name == "mam_x0.001":
            # The acceptance claim: batched serving beats the per-trial
            # cold-client loop >= 2x on the laptop config (full runs only;
            # the smoke config list drops this entry).
            bench_serve(name, spec, results, trials=16, windows=4, batch=8,
                        assert_speedup=True)
    bench_table_bytes_production(results)
    bench_table_memory_production(results)
    bench_adaptive_wire_production(results)
    bench_build_production(results)

    payload = dict(
        benchmark="delivery_backends",
        backend=jax.default_backend(),
        pallas_interpret=default_interpret(),
        platform=platform.platform(),
        jax_version=jax.__version__,
        results=results,
    )
    if args.smoke:
        _check_static_regression(results, os.path.abspath(args.out))
        print("\n--smoke: results not written (CI smoke run)")
    else:
        out = os.path.abspath(args.out)
        with open(out, "w") as f:
            json.dump(payload, f, indent=2)
            f.write("\n")
        print(f"\nwrote {out}")

    by = {(r["config"], r["phase"], r["backend"]): r for r in results
          if r["phase"] != "wire"}
    ev = by[("quickstart", "deliver", "event")]["speedup_vs_onehot"]
    ee = by[("quickstart", "engine", "event")]["speedup_vs_onehot"]
    print(f"quickstart event vs onehot: {ev:.1f}x (deliver phase), "
          f"{ee:.1f}x (end-to-end)")
    pc = by[("quickstart", "engine", "event-percycle")]["cycles_per_s"]
    ss = by[("quickstart", "engine", "event")]["cycles_per_s"]
    print(f"quickstart event superstep vs per-cycle window: {ss / pc:.2f}x")
    wire = {(r["config"], r["backend"], r["exchange"]): r for r in results
            if r["phase"] == "wire"}
    dn = wire[("quickstart_sparse", "event", "dense")]["global_bytes"]
    rt = wire[("quickstart_sparse", "event", "routed")]["global_bytes"]
    print(f"quickstart_sparse routed vs dense global wire: "
          f"{rt:,} vs {dn:,} B/window ({dn / rt:.2f}x fewer)")
    adapt = {(r["config"], r["exchange"]): r for r in results
             if r["phase"] == "adaptive"}
    a = adapt[("quickstart_sparse", "routed")]
    print(f"quickstart_sparse routed adaptive vs static: "
          f"{a['total_bytes_expected']:,} vs {a['static_bytes']:,} B/window "
          f"({a['static_bytes'] / a['total_bytes_expected']:.2f}x fewer, "
          f"incl. {a['counts_bytes']:,} B phase-1 counts)")
    for r in (r for r in results if r["phase"] == "resilience"):
        print(f"{r['config']} checkpoint overhead @ every-{r['cadence']} "
              f"windows: {r['overhead_frac'] * 100:+.2f}% (budget 5.00%), "
              f"{r['ckpt_retries']} transient writes retried")
    bld = next(r for r in results if r["phase"] == "build"
               and r.get("sds_bounds"))
    print(f"mam_x1 construction host peak RSS: "
          f"{bld['build_bytes_host_modelled'] / 2**30:.0f} GiB -> "
          f"{bld['build_bytes_shard_modelled'] / 2**30:.1f} GiB/process "
          f"sharded ({bld['reduction_modelled']:.0f}x, modelled)")
    for r in (r for r in results if r["phase"] == "overlap"):
        print(f"{r['config']} overlapped exchange hides "
              f"{r['hidden_frac'] * 100:.1f}% of the injected jitter wall "
              f"({r['injected_sequential_s']:.3f} -> "
              f"{r['injected_overlap_s']:.3f} s over {r['n_windows']} "
              f"windows; bit-identical spikes)")


if __name__ == "__main__":
    main()
