"""One run of one benchmark cell, driven by the data files beside it.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; each is a file found by its name:

* ``configs/<config>.json``: the network (areas, in-degrees, delays,
  weights, drive), the neuron parameters and the engine settings;
* ``traffic/<mix>.json``: the stimulus scale and the warm-up;
* ``metrics/<metric>.py``: a reader ``read(ctx) -> float | None`` for each
  per-layer metric;
* ``peaks.json``: the device peaks, keyed by ``device_kind``.

A run builds the network from the seed, warms the window up, times the
window loop for the requested seconds while every window's spike block is
copied to the host, and then checks the whole raster against the plain
reference (``reference.py``), after the device memory peak has been read
and the program's state freed.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
GIB = float(1 << 30)


class NoDevice(RuntimeError):
    """The machine lacks the chips the cell asks for."""


# ---------------------------------------------------------------------------
# Discovery by name
# ---------------------------------------------------------------------------


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def find_workload(bench: dict, name: str) -> dict:
    for wl in bench["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def load_config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    """A configuration file, with a homogeneous area list spelled out."""
    cfg = json.loads((Path(bench_dir) / "configs" / f"{name}.json")
                     .read_text())
    if "areas" not in cfg:
        cfg["areas"] = [
            {"name": f"A{i:02d}", "n_neurons": cfg["n_per_area"],
             "rate_hz": cfg["rate_hz"]}
            for i in range(cfg["n_areas"])]
    return cfg


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return json.loads((Path(bench_dir) / "traffic" / f"{name}.json")
                      .read_text())


def load_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(kind: str, bench_dir: Path = BENCH_DIR) -> dict:
    table = json.loads((Path(bench_dir) / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]


def cell_metrics(bench: dict, workload: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in bench[kind]
            if workload in m.get("workloads", [workload])]


# ---------------------------------------------------------------------------
# The system under test
# ---------------------------------------------------------------------------


def build_spec(cfg: dict):
    from repro.core.areas import AreaSpec, MultiAreaSpec

    areas = tuple(AreaSpec(name=a["name"], n_neurons=int(a["n_neurons"]),
                           rate_hz=float(a["rate_hz"]))
                  for a in cfg["areas"])
    return MultiAreaSpec(areas=areas, **cfg["network"])


def engine_config(cfg: dict):
    from repro.core import EngineConfig
    from repro.core.neuron import LIFParams

    return EngineConfig(lif=LIFParams(**cfg["lif"]), **cfg["engine"])


def out_degree_bounds(cfg: dict) -> dict[str, tuple[int, int]]:
    """Per pathway, the mean and a seed-independent width of the outgoing
    table (``(mean, width)``; ``(0, 0)`` for a pathway with no synapses).

    ``build_network`` sizes each outgoing table to its widest row, which
    changes with the seed and would make every new seed compile the window
    again. Every target row, ghost rows included, draws ``k`` sources, so a
    source in area ``a`` of ``n_a`` live neurons has a mean out-degree of
    ``n_pad * k_intra / n_a`` (intra) and ``n_pad * k_inter / n_a`` (inter,
    all to all); the width is the mean + 6 sd + 8 of the widest area, and
    its mean is the least width a table sized by its widest row can have.
    """
    sizes = np.asarray([a["n_neurons"] for a in cfg["areas"]], np.float64)
    n_pad = sizes.max()
    net = cfg["network"]

    def bound(k):
        if k == 0:
            return 0, 0
        m = float((n_pad * k / sizes).max())
        return int(m), int(math.ceil(m + 6 * math.sqrt(m))) + 8

    return {"intra": bound(net["k_intra"]),
            "inter": bound(net["k_inter"] if len(sizes) > 1 else 0)}


def pad_outgoing(net, bounds: dict[str, tuple[int, int]], log):
    """Widen each outgoing table that is sized by its widest row to the
    seed-independent width of ``bounds``, with the program's own empty
    entries (target -1, weight 0, delay 1), one table at a time.

    A table of any other width (wider than its widest row, or narrower than
    the mean out-degree, as a changed layout would be) is left as built.
    Returns the net and ``{pathway: [built width, width run]}``.
    """
    import jax
    import jax.numpy as jnp

    fills = {"tgt": -1, "wout": 0.0, "dout": 1}
    widths = {}
    for pathway, (mean, width) in bounds.items():
        have = getattr(net, f"tgt_{pathway}")
        if have is None:
            continue
        built = int(have.shape[-1])
        widest = int(jax.jit(lambda t: (t >= 0).sum(-1).max())(have))
        widths[pathway] = [built, built]
        if built == width:
            continue
        if built != widest or not mean <= built < width:
            log(f"outgoing {pathway} table is {built} wide (widest row "
                f"{widest}, mean out-degree {mean}, bound {width}): left as "
                f"built")
            continue
        log(f"outgoing {pathway} table widened from {built} to {width}")
        widths[pathway][1] = width
        for prefix, fill in fills.items():
            name = f"{prefix}_{pathway}"
            x = getattr(net, name)
            pad = [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])]
            x = jax.jit(lambda a: jnp.pad(a, pad, constant_values=fill))(x)
            net = dataclasses.replace(net, **{name: x})
            x = None
            gc.collect()
    return net, widths


# ---------------------------------------------------------------------------
# A run
# ---------------------------------------------------------------------------


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class _CompileCounter:
    """Counts backend compilations (for 'nothing compiles in the window')."""

    _instance = None

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls) -> "_CompileCounter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on(self, event: str, duration: float, **_) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and (platform != "tpu" or len(devices) < chips):
        raise NoDevice(f"the cell needs {chips} TPU chip(s); JAX sees "
                       f"{len(devices)} {platform} device(s)")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def initial_v(traffic: dict, cfg: dict, seed: int) -> np.ndarray:
    """Initial membrane potentials ``[A, n_pad]`` (float32) drawn from the
    seed: uniform in ``traffic["v0"]`` ``[low, high)`` mV for live neurons
    (0 without the key), 0 in ghost rows."""
    sizes = [int(a["n_neurons"]) for a in cfg["areas"]]
    v0 = np.zeros((len(sizes), max(sizes)), np.float32)
    if "v0" in traffic:
        rng = np.random.default_rng(seed)
        draw = rng.uniform(traffic["v0"]["low"], traffic["v0"]["high"],
                           v0.shape).astype(np.float32)
        for a, n in enumerate(sizes):
            v0[a, :n] = draw[a, :n]
    return v0


def packet_counts(per_area: np.ndarray, bounds: tuple[int, int],
                  intra: bool, inter: bool) -> dict:
    """Spikes that entered the event packets, and the packet slots paid.

    ``per_area`` is ``[cycles, A]`` spike counts; ``bounds`` the static
    ``(per area-cycle, per cycle)`` packet sizes. A spike beyond a bound is
    dropped, so it did not enter a packet.
    """
    s_area, s_all = bounds
    cycles, a = per_area.shape
    entered = slots = 0
    if intra:
        entered += int(np.minimum(per_area, s_area).sum())
        slots += cycles * a * s_area
    if inter:
        entered += int(np.minimum(per_area.sum(axis=1), s_all).sum())
        slots += cycles * s_all
    return {"entered": entered, "slots": slots}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench_dir: Path = BENCH_DIR, root: Path = ROOT,
             require_tpu: bool = True, control: bool = False,
             cache: bool = True, log=_log) -> dict:
    """Run one cell once and return its result line (a dict)."""
    bench = load_benchmark(root)
    wl = find_workload(bench, workload)
    cfg = load_config(wl["config"], bench_dir)
    traffic = load_traffic(wl["traffic"], bench_dir)
    dev = device_info(int(wl["chips"]), require_tpu)

    import jax
    import jax.numpy as jnp

    from jax.profiler import TraceAnnotation
    from repro.core import build_network, make_simulation
    from repro.core.delivery import event_bounds
    from repro.core.schedule import run_windows

    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache() if cache else "off"
    compiles = _CompileCounter.get()
    log(f"cell {workload}: config {wl['config']}, traffic {wl['traffic']}, "
        f"seed {seed}, device {dev['kind']} x {dev['count']}, cache "
        f"{cache_dir}")

    t_setup = time.perf_counter()
    spec = build_spec(cfg)
    ecfg = engine_config(cfg)
    with TraceAnnotation("bench.build_network"):
        net = build_network(spec, seed=int(seed),
                            outgoing=ecfg.backend == "event")
        jax.block_until_ready(net)
    build_s = time.perf_counter() - t_setup
    net, widths = pad_outgoing(net, out_degree_bounds(cfg), log)
    engine = make_simulation(spec, ecfg, net=net)
    stim = float(traffic.get("stim", 1.0))
    drive_seed = int(seed) % (1 << 32)
    state = engine.init(seed=drive_seed, stim=None if stim == 1.0 else stim)
    v0 = initial_v(traffic, cfg, int(seed))
    state = dataclasses.replace(
        state, neuron=state.neuron._replace(v=jnp.asarray(v0)))
    D = int(engine.delay_ratio)
    blocks: list[np.ndarray] = []

    def on_block(w, block):
        with TraceAnnotation("bench.on_block"):
            blocks.append(np.asarray(block))

    def on_window(w, st):
        # The hook a user hangs checkpoints on; here it only holds a span,
        # so that the trace shows what the host does between windows.
        with TraceAnnotation("bench.on_window"):
            pass

    n_warm = int(traffic["warmup_windows"])
    warm = run_windows(engine, state, n_warm, on_block=on_block,
                       on_window=on_window)
    per_window = float(warm.window_times_s[-1])
    n_windows = max(2, math.ceil(float(seconds) / per_window))
    # The recorded run covers a whole turn of the ring buffer and three
    # windows more, so the timed windows read slots that were read, cleared
    # and filled again: warm-up runs on until it does.
    ring_len = int(warm.state.ring.shape[-1])
    extra = max(0, -(-(ring_len + 3 * D) // D) - n_warm - n_windows)
    if extra:
        warm = run_windows(engine, warm.state, extra, on_block=on_block,
                           on_window=on_window)
        n_warm += extra
    state = warm.state
    setup_s = time.perf_counter() - t_setup
    log(f"set-up {setup_s:.3f} s (build {build_s:.3f} s), warm window "
        f"{per_window * 1e3:.3f} ms, {n_warm} warm-up windows, timing "
        f"{n_windows} windows (ring of {ring_len} cycles)")

    trace_dir = root / ".bench_trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(str(trace_dir))
    compiles_before = compiles.count
    t0 = time.perf_counter()
    with TraceAnnotation("bench.run_windows"):
        res = run_windows(engine, state, n_windows, on_block=on_block,
                          on_window=on_window)
    wall = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    in_window_compiles = compiles.count - compiles_before
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    state = res.state
    overflow = int(state.overflow)
    final = {k: np.asarray(getattr(state.neuron, k))
             for k in ("v", "i_syn", "refrac")}
    bounds = event_bounds(engine.net, headroom=ecfg.s_max_headroom,
                          floor=ecfg.s_max_floor,
                          burst_factor=ecfg.s_max_burst)
    k_intra, k_inter = net.k_intra, net.k_inter
    del engine, state, net, res, warm
    gc.collect()

    raster = np.concatenate(blocks)                      # [T, A, n_pad]
    n_warm_cycles = n_warm * D
    bio_s = n_windows * D * spec.dt_ms * 1e-3
    log(f"window: {n_windows} windows, {bio_s * 1e3:.3f} ms of biology in "
        f"{wall:.6f} s, {int(raster[n_warm_cycles:].sum())} spikes, "
        f"overflow {overflow}, compiles in window {in_window_compiles}, "
        f"peak device bytes {peak}")
    per_area = raster.sum(axis=2)
    log(f"packet peaks: {int(per_area.max())} spikes per area-cycle "
        f"(bound {bounds[0]}), {int(per_area.sum(axis=1).max())} per cycle "
        f"(bound {bounds[1]})")

    # --- the reference, after the peak has been read and the state freed
    from bench import reference, work

    t_ref = time.perf_counter()
    p = reference.params_from_config(cfg, stim)
    tables = reference.build_tables(p, int(seed))
    flat = raster.reshape(raster.shape[0], -1)
    v0 = v0.reshape(-1)
    verdict = reference.check(p, tables, int(seed), flat, final, v0=v0)
    ref_s = time.perf_counter() - t_ref
    log(f"reference: {ref_s:.3f} s, {tables.n_fixed} delays recomputed in "
        f"float64, gather width {tables.k_out}, "
        f"{verdict['reference_spikes']} spikes; program: raster_mismatch "
        f"{verdict['raster_mismatch']}, state_mismatch "
        f"{verdict['state_mismatch']}")
    if control:
        # The control: the reference in the program's place, one precision
        # lower, judged by the same checks and limits.
        verdict = reference.check(p, tables, int(seed), flat, final, v0=v0,
                                  dtype="bfloat16")
        log("control bfloat16 in the program's place")

    miss_w = verdict["mismatch_per_cycle"].reshape(-1, D).sum(axis=1)
    checks = {
        "raster_mismatch": {"value": verdict["raster_mismatch"], "limit": 0},
        "state_mismatch": {"value": verdict["state_mismatch"], "limit": 0},
        "overflow": {"value": overflow, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    timed = flat[n_warm_cycles:]
    ctx = {
        "build_s": build_s,
        "setup_s": setup_s,
        "window_wall_s": wall,
        "windows": n_windows,
        "cycles": timed.shape[0],
        "device_kind": dev["kind"],
        "packets": packet_counts(per_area[n_warm_cycles:], bounds,
                                 k_intra > 0, k_inter > 0),
        "work": work.necessary_work(timed, tables.counts,
                                    len(p.alive_rows())),
        "trace": None,
        "bench_dir": bench_dir,
    }
    result: dict = {"correct": correct, "attempted": int(len(miss_w)),
                    "failed": int((miss_w > 0).sum())}
    device = dict(dev, memory_peak_bytes=peak)
    if trace:
        from bench import trace as trace_lib

        path = trace_lib.find_xplane(str(trace_dir))
        events = trace_lib.load(path)
        window = trace_lib.span_window(events, "bench.run_windows")
        summary = trace_lib.reduce(events, window)
        log(f"trace planes: {events['planes']}")
        for plane, evs in events["devices"].items():
            if evs:
                log(f"trace {plane}: {len(evs)} ops from {evs[0][1]:.0f} "
                    f"to {evs[-1][1] + evs[-1][2]:.0f} ns; window {window}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx["trace"] = dict(summary, windows=n_windows)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        log(f"trace: busy {summary['busy_s']:.6f} s of "
            f"{summary['window_s']:.6f} s on {summary['n_devices']} device(s)")
        metrics = {}
        for m in cell_metrics(bench, workload, "per_layer"):
            value = load_reader(m["name"], bench_dir)(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        e2e = {"bio_s_per_wall_s": bio_s / wall, "peak_hbm_gib": peak / GIB,
               "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in cell_metrics(bench, workload, "end_to_end")}
        result["device"] = device
    result["packet_peaks"] = {
        "per_area_cycle": int(per_area.max()), "bound_area": bounds[0],
        "per_cycle": int(per_area.sum(axis=1).max()), "bound_all": bounds[1]}
    result["outgoing_widths"] = widths
    result["cycles"] = {"recorded": int(flat.shape[0]), "ring": ring_len}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return result
