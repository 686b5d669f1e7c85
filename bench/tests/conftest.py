"""Fixtures of the benchmark's CPU tests: a tiny benchmark beside the real
one, built from the real data files with the sizes cut."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_CELLS = {
    "tiny_bench.ground": ("mam_bench_8x3072_k3000", "ground"),
    "tiny_ragged.ground": ("tiny_ragged", "ground"),
}
# Areas of unequal sizes (a test layout, not a deployment): the padded rows
# of the smaller areas are ghost rows.
RAGGED_SIZES = (64, 56, 48, 40, 40, 32, 24, 16)


def tiny_config(name: str) -> dict:
    """A real configuration at 1/64 of the neurons and 1/100 of K; the
    ``tiny_ragged`` layout is the benchmark network with ragged areas."""
    from bench import harness

    ragged = name == "tiny_ragged"
    cfg = harness.load_config("mam_bench_8x3072_k3000" if ragged else name)
    for i, a in enumerate(cfg["areas"]):
        a["n_neurons"] = (RAGGED_SIZES[i] if ragged else
                          max(8, a["n_neurons"] // 64 // 8 * 8))
    net = cfg["network"]
    net["k_intra"] = max(1, net["k_intra"] // 100)
    net["k_inter"] = max(1, net["k_inter"] // 100)
    cfg["engine"]["s_max_floor"] = 64
    return cfg


@pytest.fixture
def tiny_bench(tmp_path):
    """``(root, bench_dir)`` of a benchmark with the tiny cells."""
    bench_dir = tmp_path / "bench"
    shutil.copytree(BENCH / "metrics", bench_dir / "metrics")
    shutil.copytree(BENCH / "traffic", bench_dir / "traffic")
    shutil.copy(BENCH / "peaks.json", bench_dir / "peaks.json")
    (bench_dir / "configs").mkdir()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    workloads = []
    for cell, (config, traffic) in TINY_CELLS.items():
        (bench_dir / "configs" / f"{config}.json").write_text(
            json.dumps(tiny_config(config)))
        workloads.append({"name": cell, "config": config,
                          "traffic": traffic, "chips": 1, "why": "test"})
    bench["workloads"] = workloads
    for m in bench["per_layer"]:
        m["workloads"] = list(TINY_CELLS)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path, bench_dir
