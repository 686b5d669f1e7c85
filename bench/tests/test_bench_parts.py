"""The benchmark's parts on their own: the trace reduction, the
necessary-work count, the packet fill, discovery by name, and the refusal
to run without a chip."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT, tiny_config


# --- trace reduction --------------------------------------------------------

def _synthetic_trace():
    ms = 1_000_000.0
    return {
        "devices": {
            "/device:TPU:0": [("scatter.1", 0 * ms, 4 * ms),
                              ("fusion.2", 3 * ms, 2 * ms),   # overlaps
                              ("scatter.1", 8 * ms, 1 * ms),
                              ("fusion.9", 12 * ms, 3 * ms)],
            "/device:TPU:1": [("scatter.1", 0 * ms, 10 * ms)],
        },
        "spans": [("bench.run_windows", 0.0, 20 * ms),
                  ("bench.on_block", 5 * ms, 2.5 * ms),
                  ("bench.on_window", 15 * ms, 5 * ms),
                  ("other", 5 * ms, 3 * ms)],
    }


def test_trace_reduce_busy_union_and_gaps():
    from bench import trace

    ev = _synthetic_trace()
    out = trace.reduce(ev, trace.span_window(ev, "bench.run_windows"))
    # device 0: [0, 5) + [8, 9) + [12, 15) = 9 ms; device 1: 10 ms.
    assert out["busy_s"] == pytest.approx((9e-3 + 10e-3) / 2)
    assert out["window_s"] == pytest.approx(20e-3)
    assert out["n_devices"] == 2
    names = dict(out["device_ops"])
    assert names["scatter.1"] == pytest.approx((5e-3 + 10e-3) / 2)
    assert list(names)[0] == "scatter.1"
    gaps = out["idle_gaps"]
    assert gaps[0][1] == pytest.approx(10e-3)        # device 1, [10, 20)
    assert sorted(g[1] for g in gaps) == pytest.approx(
        sorted([3e-3, 3e-3, 5e-3, 10e-3]))
    by_len = {round(g[1] * 1e3): g[0] for g in gaps if g[0] != "bench.run_windows"}
    assert by_len[5] == "bench.on_window"            # device 0, [15, 20)
    assert "other" not in [g[0] for g in gaps]       # not a bench span


def test_trace_reduce_clips_to_window_and_handles_no_device():
    from bench import trace

    ev = _synthetic_trace()
    out = trace.reduce(ev, (1e6, 2e6))
    assert out["busy_s"] == pytest.approx(1e-3)
    empty = trace.reduce({"devices": {}, "spans": []})
    assert empty["busy_s"] == 0.0 and empty["device_ops"] == []


def test_trace_load_reads_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from bench import trace

    f = jax.jit(lambda x: jnp.sin(x) * 2)
    x = jnp.ones((64,))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.run_windows"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.load(trace.find_xplane(str(tmp_path)))
    assert trace.span_window(ev, "bench.run_windows") is not None
    assert ev["devices"] == {}  # a CPU has no device plane


# --- necessary work -------------------------------------------------------

def test_necessary_work_matches_a_hand_count():
    """Quickstart size (4 areas x 256, K = 32 + 32): three spikes and the
    neuron-cycles, counted by hand from the program's own tables."""
    from bench import reference, work
    from repro.core import build_network
    from repro.core.areas import mam_benchmark_spec

    spec = mam_benchmark_spec(n_areas=4, n_per_area=256, k_intra=32,
                              k_inter=32)
    seed = 12
    cfg = {"areas": [{"n_neurons": 256, "rate_hz": 2.5}] * 4,
           "network": dict(tiny_config("mam_bench_8x3072_k3000")["network"],
                           k_intra=32, k_inter=32),
           "lif": tiny_config("mam_bench_8x3072_k3000")["lif"]}
    p = reference.params_from_config(cfg)
    tables = reference.build_tables(p, seed)
    net = build_network(spec, seed=seed)
    src_intra = np.asarray(net.src_intra)
    src_inter = np.asarray(net.src_inter).reshape(-1)
    # out-degree of gid 5 (area 0) and gid 700 (area 2), by hand
    def out_degree(g):
        a, i = divmod(g, 256)
        return int((src_intra[a] == i).sum() + (src_inter == g).sum())
    for g in (5, 700):
        assert tables.counts[g] == out_degree(g)
    raster = np.zeros((3, 1024), bool)
    raster[0, 5] = raster[2, 5] = raster[1, 700] = True
    w = work.necessary_work(raster, tables.counts, 1024)
    syn = 2 * out_degree(5) + out_degree(700)
    assert w["synapses"] == syn
    assert w["neuron_cycles"] == 3 * 1024
    assert w["bytes"] == syn * (4 + 4 + 1 + 4 + 4) + 3 * 1024 * (24 + 8 + 1)
    t, bound = work.least_time_s(w, {"hbm_bytes_per_s": 819e9,
                                     "flops_per_s": 197e12})
    assert bound == "hbm" and t == pytest.approx(w["bytes"] / 819e9)


# --- packet fill ------------------------------------------------------------

def test_packet_fill_counts_against_event_bounds():
    from bench import harness
    from repro.core import EngineConfig, build_network
    from repro.core.areas import mam_benchmark_spec
    from repro.core.delivery import event_bounds

    spec = mam_benchmark_spec(n_areas=4, n_per_area=64, k_intra=8,
                              k_inter=8)
    net = build_network(spec, seed=3)
    cfg = EngineConfig(delivery_backend="event", s_max_floor=4)
    bounds = event_bounds(net, headroom=cfg.s_max_headroom,
                          floor=cfg.s_max_floor)
    s_area, s_all = bounds
    per_area = np.array([[0, 1, 2, 0], [s_area + 3, 0, 0, 1]])
    got = harness.packet_counts(per_area, bounds, True, True)
    entered_intra = 1 + 2 + s_area + 1                 # the excess dropped
    entered_inter = 3 + min(s_area + 4, s_all)
    assert got["entered"] == entered_intra + entered_inter
    assert got["slots"] == 2 * 4 * s_area + 2 * s_all
    assert harness.packet_counts(per_area, bounds, True, False)["slots"] \
        == 2 * 4 * s_area
    read = harness.load_reader("event_packet_fill", BENCH)
    assert read({"packets": got}) == pytest.approx(
        100.0 * got["entered"] / got["slots"])
    assert read({"packets": None}) is None


# --- the outgoing tables' width --------------------------------------------

@pytest.mark.parametrize("layout", ["widest_row", "wider", "narrower"])
def test_pad_outgoing_widens_only_tables_sized_by_their_widest_row(layout):
    """A table sized by its widest row (the width that changes with the
    seed) is widened to the seed-independent bound; a table of any other
    width, as a changed layout would give, is left as built."""
    import dataclasses

    import jax.numpy as jnp

    from bench import harness
    from repro.core import build_network

    cfg = tiny_config("mam_bench_8x3072_k3000")
    net = build_network(harness.build_spec(cfg), seed=3, outgoing=True)
    bounds = harness.out_degree_bounds(cfg)
    fills = {"tgt": -1, "wout": 0.0, "dout": 1}
    for pw in ("intra", "inter"):
        for prefix, fill in fills.items():
            x = getattr(net, f"{prefix}_{pw}")
            if layout == "wider":
                x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, 2)],
                            constant_values=fill)
            elif layout == "narrower":
                x = x[..., :bounds[pw][0] - 1]
            net = dataclasses.replace(net, **{f"{prefix}_{pw}": x})
    built = {pw: getattr(net, f"tgt_{pw}").shape[-1]
             for pw in ("intra", "inter")}
    padded, widths = harness.pad_outgoing(net, bounds, lambda m: None)
    for pw, (mean, width) in bounds.items():
        assert mean < width
        want = width if layout == "widest_row" else built[pw]
        assert widths[pw] == [built[pw], want]
        for prefix, fill in fills.items():
            x = np.asarray(getattr(padded, f"{prefix}_{pw}"))
            assert x.shape[-1] == want
            if layout == "widest_row":
                assert (x[..., built[pw]:] == fill).all()


# --- discovery by name ------------------------------------------------------

def test_new_config_traffic_and_metric_are_found_by_name(tiny_bench):
    """Files added beside the existing ones are found with no edit to any
    existing file: a configuration, a traffic mix and a metric reader."""
    from bench import harness

    root, bench_dir = tiny_bench
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    cfg = tiny_config("mam_bench_8x3072_k3000")
    cfg["network"]["k_inter"] = 2
    (bench_dir / "configs" / "added_cfg.json").write_text(json.dumps(cfg))
    (bench_dir / "traffic" / "added_mix.json").write_text(
        json.dumps({"stim": 1.5, "warmup_windows": 2}))
    (bench_dir / "metrics" / "added_metric.py").write_text(
        "def read(ctx):\n    return ctx['windows'] * 1.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "added.cell", "config": "added_cfg",
                               "traffic": "added_mix", "chips": 1,
                               "why": "t"})
    bench["per_layer"].append({"name": "added_metric", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "window loop",
                               "moves": "bio_s_per_wall_s",
                               "workloads": ["added.cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    peaks = json.loads((bench_dir / "peaks.json").read_text())
    peaks["devices"]["cpu"] = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    (bench_dir / "peaks.json").write_text(json.dumps(peaks))
    res = harness.run_cell("added.cell", 5, 0.05, True, bench_dir=bench_dir,
                           root=root, require_tpu=False, cache=False,
                           log=lambda m: None)
    assert res["correct"], res["checks"]
    assert res["metrics"]["added_metric"]["value"] >= 2
    for p, data in before.items():
        if p.name != "peaks.json":
            assert p.read_bytes() == data


def test_unknown_device_kind_is_an_error():
    from bench import harness

    with pytest.raises(KeyError):
        harness.load_peaks("no such chip", BENCH)
    assert harness.load_peaks("TPU v5 lite", BENCH)["hbm_bytes_per_s"] == 819e9


def test_benchmark_json_names_existing_files():
    from bench import harness

    bench = harness.load_benchmark(ROOT)
    for cfg in bench["configs"]:
        assert (ROOT / cfg["file"]).is_file()
    for wl in bench["workloads"]:
        assert harness.load_config(wl["config"])["areas"]
        assert "warmup_windows" in harness.load_traffic(wl["traffic"])
    for m in bench["per_layer"]:
        assert callable(harness.load_reader(m["name"]))


# --- no chip, no result -----------------------------------------------------

@pytest.mark.parametrize("where", ["checkout", "bench_only"])
def test_run_exits_nonzero_without_a_tpu(tmp_path, where):
    """On a CPU (and in a directory with nothing but the benchmark) the
    command fails before it builds anything and prints no result."""
    import shutil

    if where == "checkout":
        cwd = ROOT
    else:
        cwd = tmp_path
        shutil.copytree(BENCH, cwd / "bench",
                        ignore=shutil.ignore_patterns("__pycache__", "tests"))
        shutil.copy(ROOT / "BENCHMARK.json", cwd / "BENCHMARK.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "mam_bench.ground",
         "--seed", "3000000019", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
