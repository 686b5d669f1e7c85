#!/usr/bin/env python3
"""Chip smoke test: the simulator's main path on a TPU, checked bitwise.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: the distributed legs only

One chip, in one process:

1. **Full width.** The homogeneous MAM benchmark (paper §4.2) at its
   published in-degrees (K = 3000 intra + 3000 inter per neuron), dt 0.1 ms,
   D = 10, LIF neurons with their calibrated Poisson drive, cut to one
   chip's share: 8 areas x 4096 neurons (32,768 neurons, ~197M synapses).
   It runs through ``make_simulation`` (event delivery, structure-aware
   schedule) and the windowed ``run_windows`` loop past the LIF onset
   transient; overflow must be 0 and the network must spike.
2. **Bitwise reference at full width.** The plain reference -- the
   single-host engine on the conventional per-cycle schedule -- reruns the
   same windows; its spike raster must equal step 1's bit for bit. The
   scatter backend (no event compaction) reruns the last windows from
   step 1's state and must match too.
3. **Every backend at the quickstart size.** onehot, scatter, pallas (the
   Pallas delivery and LIF kernels, compiled for the chip) and event, on
   both schedules, against the scatter/conventional reference.
4. **Serving.** ``SimServer`` folds 4 trials into batches of 2; every
   served raster must equal its sequential run. Published in-degrees, 512
   neurons per area (a folded full-width batch would not fit beside its
   reference).

Four chips (``--chips 4``): on the ``(data, model)`` mesh that
``launch/simulate`` picks, the structure-aware legs with the dense and the
routed exchange and one host-free sharded-build leg must equal the
conventional schedule bitwise, with state and tables spread over all four
devices.

Timings and memory are printed for information only; they are not
benchmark results. The last line of output is the JSON verdict, and only a
run in which every phase passed prints it. Without a TPU the script exits
non-zero before any phase.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 12           # connectivity seed (paper seeds: 12, 654, 91856)
K_PUBLISHED = 3000  # in-degree per pathway, paper §4.2
# Static event-packet floor: the onset transient fires in near-synchronous
# volleys, far above the 2.5 Hz bound the default floor prices; overflow is
# asserted 0. (The adaptive ladder's top rung needs more than the chip's HBM
# at full width.)
S_MAX_FLOOR = 1024
# The serving phase's 512-neuron areas peak at ~60 spikes per area-cycle.
S_MAX_FLOOR_SERVING = 128


def _log(msg: str) -> None:
    print(msg, flush=True)


def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _run(engine, state, n_windows: int, keep: int | None = None):
    """``run_windows`` collecting the host raster ``[n_windows * D, A,
    n_pad]``; also returns the state after window ``keep`` (or None)."""
    from repro.core import schedule

    blocks, kept = [], []

    def on_window(w, st):
        if w == keep:
            kept.append(st)

    res = schedule.run_windows(
        engine, state, n_windows, on_window=on_window,
        on_block=lambda w, blk: blocks.append(np.asarray(blk)))
    return res, np.concatenate(blocks), (kept[0] if kept else None)


def _check(ok: bool, what) -> None:
    """Fail the run (also under ``python -O``, which strips asserts)."""
    if not ok:
        raise RuntimeError(f"chip smoke check failed: {what}")


def _bitwise(name: str, got: np.ndarray, want: np.ndarray) -> None:
    same = got.shape == want.shape and np.array_equal(got, want)
    _log(f"  bitwise {name}: {same} ({int(want.sum()):,} spikes)")
    _check(same, f"{name}: spike raster differs from the reference")


def full_width(n_per_area: int, windows: int, scatter_windows: int) -> None:
    """Phases 1 and 2 at the published in-degrees."""
    import jax

    from repro.core import EngineConfig, build_network, make_simulation
    from repro.core.areas import mam_benchmark_spec
    from repro.core.delivery import event_bounds

    spec = mam_benchmark_spec(
        n_areas=8, n_per_area=n_per_area,
        k_intra=K_PUBLISHED, k_inter=K_PUBLISHED)
    t0 = time.perf_counter()
    net = build_network(spec, seed=SEED, outgoing=True)
    jax.block_until_ready(net.tgt_inter)
    _log(f"full width: {spec.n_areas} areas x {n_per_area} neurons, "
         f"K={spec.k_intra}+{spec.k_inter}, D={spec.delay_ratio}, "
         f"{net.synapse_count():,} synapses, host build "
         f"{time.perf_counter() - t0:.1f} s")

    def engine(backend: str, schedule: str):
        return make_simulation(spec, EngineConfig(
            delivery_backend=backend, schedule=schedule,
            s_max_floor=S_MAX_FLOOR), net=net)

    w0 = windows - scatter_windows
    eng = engine("event", "structure_aware")
    res, raster, state_w0 = _run(eng, eng.init(), windows, keep=w0)
    times = res.window_times_s
    overflow = int(res.state.overflow)
    spikes = int(raster.sum())
    _log(f"  event/structure_aware: {windows} windows, first window "
         f"(compile + run) {times[0]:.2f} s, then "
         f"{np.median(times[1:]) * 1e3:.2f} ms/window median, "
         f"{spikes:,} spikes, overflow {overflow}, peak device bytes "
         f"{_peak_bytes(jax.devices()[0]):,}")
    per_area = raster.sum(axis=2)                     # [cycles, A]
    s_max_area, s_max_all = event_bounds(
        net, headroom=eng.config.s_max_headroom, floor=S_MAX_FLOOR)
    _log(f"  packet peaks: {int(per_area.max()):,} spikes per area-cycle "
         f"(bound {s_max_area:,}), {int(per_area.sum(axis=1).max()):,} per "
         f"cycle (bound {s_max_all:,})")
    _check(overflow == 0, "event packets overflowed: the run is not exact")
    _check(spikes > 0, "the network never spiked")

    ref = engine("event", "conventional")
    res_ref, raster_ref, _ = _run(ref, ref.init(), windows)
    _check(int(res_ref.state.overflow) == 0, "reference packets overflowed")
    _bitwise("event/structure_aware == event/conventional", raster,
             raster_ref)

    # The dense scatter path gathers and scatters all ~197M synapses every
    # cycle, so it reruns only the last windows, from step 1's state there.
    sc = engine("scatter", "conventional")
    res_sc, raster_sc, _ = _run(sc, state_w0, scatter_windows)
    d = spec.delay_ratio
    _bitwise(f"scatter/conventional (windows {w0}..{windows - 1})",
             raster_sc, raster[w0 * d:])
    _log(f"  scatter/conventional: "
         f"{np.median(res_sc.window_times_s) * 1e3:.1f} ms/window median")


def backends_small(windows: int) -> None:
    """Phase 3: every delivery backend at the quickstart size."""
    from repro.core import EngineConfig, build_network, make_simulation
    from repro.core.areas import mam_benchmark_spec

    spec = mam_benchmark_spec(n_areas=4, n_per_area=256, k_intra=32,
                              k_inter=32)
    net = build_network(spec, seed=SEED, outgoing=True)
    _log(f"quickstart size: {spec.n_areas} areas x 256 neurons, "
         f"K={spec.k_intra}+{spec.k_inter}")
    rasters, states = {}, {}
    for backend in ("scatter", "onehot", "pallas", "event"):
        for schedule in ("conventional", "structure_aware"):
            eng = make_simulation(spec, EngineConfig(
                delivery_backend=backend, schedule=schedule,
                s_max_floor=256), net=net)
            res, rasters[backend, schedule], _ = _run(
                eng, eng.init(), windows)
            states[backend, schedule] = res.state.neuron
            _check(int(res.state.overflow) == 0,
                   f"{backend}/{schedule} packets overflowed")
    want = rasters["scatter", "conventional"]
    _check(want.sum() > 0, "the quickstart network never spiked")
    for (backend, schedule), got in rasters.items():
        _bitwise(f"{backend}/{schedule} == scatter/conventional", got, want)
    # The pallas legs update through the fused LIF kernel, the others
    # through the jnp chain: the final neuron state must agree bit for bit.
    ref = states["scatter", "conventional"]
    for key, st in states.items():
        same = all(np.array_equal(np.asarray(getattr(st, f)),
                                  np.asarray(getattr(ref, f)))
                   for f in ("v", "i_syn", "refrac"))
        _log(f"  bitwise v/i_syn/refrac {key[0]}/{key[1]} == "
             f"scatter/conventional: {same}")
        _check(same, f"{key}: neuron state differs from the reference")


def serving(n_per_area: int, windows: int) -> None:
    """Phase 4: served trials against their sequential runs."""
    from repro.core import EngineConfig, make_simulation
    from repro.core.areas import mam_benchmark_spec
    from repro.launch.serve import SimServer, TrialRequest

    spec = mam_benchmark_spec(
        n_areas=8, n_per_area=n_per_area,
        k_intra=K_PUBLISHED, k_inter=K_PUBLISHED)
    cfg = EngineConfig(delivery_backend="event",
                       s_max_floor=S_MAX_FLOOR_SERVING)
    requests = [TrialRequest(seed=s, stim=st, windows=windows)
                for s, st in ((7, 1.0), (8, 1.1), (9, 0.9), (10, 1.2))]
    _log(f"serving: {len(requests)} trials x {windows} windows, "
         f"max_batch=2, {spec.n_areas} areas x {n_per_area} neurons, "
         f"K={spec.k_intra}+{spec.k_inter}")
    with SimServer(spec, cfg, max_batch=2, max_windows=windows,
                   build_seed=SEED) as server:
        results = [h.result(timeout=900)
                   for h in [server.submit(r) for r in requests]]
    stats = server.stats()
    _log(f"  served {stats['trials']} trials, {stats['trials_per_s']:.2f} "
         f"trials/s, p50 {stats['p50_ms']:.0f} ms")
    ref = make_simulation(spec, cfg, build_seed=SEED)
    for r in results:
        _check(r.overflow == 0, f"served trial overflowed: {r.request}")
        st = ref.init(seed=r.request.seed, stim=r.request.stim)
        blocks = []
        for _ in range(r.request.windows):
            st, blk = ref.window(st)
            blocks.append(np.asarray(blk))
        _bitwise(f"served trial seed={r.request.seed} == sequential",
                 r.spikes, np.concatenate(blocks))
    _check(sum(int(r.spikes.sum()) for r in results) > 0,
           "no served trial spiked")


def _spread(name: str, arr, n_dev: int) -> None:
    """``arr`` is split over ``n_dev`` devices, one distinct shard each."""
    devices = arr.sharding.device_set
    shards = {s.device: s.data.shape for s in arr.addressable_shards}
    ok = (len(devices) == n_dev and not arr.sharding.is_fully_replicated
          and len(shards) == n_dev)
    _log(f"  placement {name} {tuple(arr.shape)}: {len(devices)} devices, "
         f"shard {next(iter(shards.values()))}: {ok}")
    _check(ok, f"{name} is not spread over {n_dev} devices")


def four_chips(n_per_area: int, windows: int) -> None:
    """The distributed legs against the conventional schedule."""
    import jax

    from repro.core import EngineConfig, build_network, make_simulation
    from repro.core.areas import mam_benchmark_spec
    from repro.launch.simulate import _pick_mesh

    spec = mam_benchmark_spec(
        n_areas=8, n_per_area=n_per_area,
        k_intra=K_PUBLISHED, k_inter=K_PUBLISHED)
    n_dev = jax.device_count()
    shape = _pick_mesh(n_dev, spec.n_areas, spec.padded_area_size(1))
    mesh = jax.make_mesh(shape, ("data", "model"))
    t0 = time.perf_counter()
    net = build_network(spec, seed=SEED, outgoing=True)
    _log(f"four chips: mesh {shape[0]} area groups x {shape[1]} subgroup, "
         f"{spec.n_areas} areas x {n_per_area} neurons, "
         f"K={spec.k_intra}+{spec.k_inter}, {net.synapse_count():,} "
         f"synapses, host build {time.perf_counter() - t0:.1f} s")
    base = EngineConfig(delivery_backend="event", s_max_floor=S_MAX_FLOOR)
    # The reference leg delivers through the dense scatter backend: the
    # conventional event window compiles for minutes at this packet floor
    # (142-285 s in a compile rehearsal), the scatter window in ~35 s.
    legs = {
        "conventional/dense": dict(schedule="conventional",
                                   exchange="dense",
                                   delivery_backend="scatter"),
        "structure_aware/dense": dict(exchange="dense"),
        "structure_aware/routed": dict(exchange="routed"),
        "structure_aware/dense/sharded_build": dict(exchange="dense",
                                                    sharded_build=True),
    }
    counts, rasters = {}, {}
    for name, kw in legs.items():
        cfg = dataclasses.replace(base, **kw)
        t0 = time.perf_counter()
        eng = make_simulation(
            spec, cfg, net=None if cfg.sharded_build else net, mesh=mesh,
            build_seed=SEED)
        t_build = time.perf_counter() - t0
        res, rasters[name], _ = _run(eng, eng.init(), windows)
        st = res.state
        counts[name] = np.asarray(st.spike_count)
        _log(f"  {name}: engine build {t_build:.1f} s, first window "
             f"{res.window_times_s[0]:.2f} s, then "
             f"{np.median(res.window_times_s[1:]) * 1e3:.2f} ms/window, "
             f"{int(counts[name].sum()):,} spikes, overflow "
             f"{int(st.overflow)}")
        _check(int(st.overflow) == 0, f"{name} packets overflowed")
        if cfg.schedule == "structure_aware":
            _spread("ring", st.ring, n_dev)
            _spread("v", st.neuron.v, n_dev)
            _spread("tgt_intra", eng.net.tgt_intra, n_dev)
            _spread("tgt_inter_in", eng.net.tgt_inter_in, n_dev)
    want = counts["conventional/dense"]
    _check(want.sum() > 0, "the network never spiked")
    for name in legs:
        same = np.array_equal(counts[name], want)
        _log(f"  spike counts {name} == conventional/dense: {same}")
        _check(same, name)
        _bitwise(f"raster {name} == conventional/dense", rasters[name],
                 rasters["conventional/dense"])
    peaks = [_peak_bytes(d) for d in jax.devices()]
    in_use = [int((d.memory_stats() or {}).get("bytes_in_use", 0))
              for d in jax.devices()]
    _log(f"  peak device bytes {peaks}, in use {in_use}")
    _check(all(b > 0 for b in in_use), "a device holds nothing")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the distributed legs, on four chips")
    args = ap.parse_args(argv)

    import jax

    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX sees "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        return 1

    _log(f"device: {devices[0].device_kind} x {len(devices)}, jax "
         f"{jax.__version__}, compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(n_per_area=1024, windows=30)
    else:
        full_width(n_per_area=4096, windows=40, scatter_windows=2)
        backends_small(windows=100)
        serving(n_per_area=512, windows=32)
    _log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
