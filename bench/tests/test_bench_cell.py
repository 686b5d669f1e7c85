"""A whole run of a cell on the CPU at a tiny size: the harness past its
look for a chip, the reference check, the control, and planted faults."""

from __future__ import annotations

import json

import numpy as np
import pytest

from conftest import TINY_CELLS

SEED = 2_718_281_829  # above 2**31: seeds of any size up to 2**32 must work


def _run(tiny_bench, cell, *, seconds=0.3, trace=False, control=False):
    from bench import harness

    root, bench_dir = tiny_bench
    peaks = json.loads((bench_dir / "peaks.json").read_text())
    # A CPU stands in for the chip here; its "peaks" only exercise the
    # reader, and no CPU number is ever reported as a device metric.
    peaks["devices"]["cpu"] = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    (bench_dir / "peaks.json").write_text(json.dumps(peaks))
    return harness.run_cell(cell, SEED, seconds, trace, bench_dir=bench_dir,
                            root=root, require_tpu=False, control=control,
                            cache=False, log=lambda m: None)


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_cell_is_correct(tiny_bench, cell):
    res = _run(tiny_bench, cell)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 4
    assert set(res["metrics"]) == {"bio_s_per_wall_s", "peak_hbm_gib",
                                   "setup_s"}
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert res["packet_peaks"]["per_area_cycle"] > 0  # the network spiked
    # The recorded run covers a turn of the ring and three windows more.
    assert res["cycles"]["recorded"] >= res["cycles"]["ring"] + 30


def test_traced_run_reports_per_layer_metrics(tiny_bench):
    res = _run(tiny_bench, "tiny_bench.ground", trace=True)
    assert res["correct"]
    m = res["metrics"]
    # No device plane on a CPU: the trace readers find nothing and stay
    # silent; the others read the harness's own numbers.
    assert {"build_s", "event_packet_fill", "window_mfu"} <= set(m)
    assert "device_idle_share" not in m and "window_device_ms" not in m
    assert 0 < m["event_packet_fill"]["value"] < 100
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_control_is_not_correct(tiny_bench):
    """The reference one precision lower (bfloat16), put in the program's
    place, fails the checks and limits that the program passes."""
    res = _run(tiny_bench, "tiny_bench.ground", control=True)
    assert not res["correct"]
    assert res["checks"]["raster_mismatch"]["value"] > 0
    assert res["checks"]["state_mismatch"]["value"] > 0


def _break_window(monkeypatch, fault):
    """Wrap the engine's window (the timed path) with ``fault``."""
    from repro.core import engine as engine_lib

    real = engine_lib._make_engine

    def make(*a, **k):
        eng = real(*a, **k)
        return eng._replace(window=fault(eng.window))

    monkeypatch.setattr(engine_lib, "_make_engine", make)
    from repro.core import factory
    monkeypatch.setattr(factory, "_make_engine", make)


def _state_unchanged(window):
    def broken(state):
        _, block = window(state)
        return state, block
    return broken


def _half_areas_dropped(window):
    def broken(state):
        state, block = window(state)
        a = block.shape[1]
        return state, block.at[:, a // 2:].set(False)
    return broken


def _spike_altered(window):
    calls = []

    def broken(state):
        state, block = window(state)
        calls.append(1)
        if len(calls) == 3:
            block = block.at[0, 0, 0].set(~block[0, 0, 0])
        return state, block
    return broken


@pytest.mark.parametrize("fault", [_state_unchanged, _half_areas_dropped,
                                   _spike_altered])
def test_planted_fault_is_not_correct(tiny_bench, monkeypatch, fault):
    _break_window(monkeypatch, fault)
    res = _run(tiny_bench, "tiny_bench.ground")
    assert not res["correct"]
    assert res["checks"]["raster_mismatch"]["value"] > 0
    assert res["failed"] > 0


def _ring_not_cleared(ring_buffer, monkeypatch):
    real = ring_buffer.open_window

    def open_window(ring, t0, d, w):
        fut, _ = real(ring, t0, d, w)
        return fut, ring
    monkeypatch.setattr(ring_buffer, "open_window", open_window)


def _ring_read_never_wraps(ring_buffer, monkeypatch):
    import jax

    def read_and_clear_block(ring, t0, d):
        start = jax.numpy.minimum(t0, ring.shape[-1] - d)
        blk = jax.lax.dynamic_slice_in_dim(ring, start, d, axis=-1)
        return blk, jax.lax.dynamic_update_slice_in_dim(
            ring, jax.numpy.zeros_like(blk), start, axis=-1)
    monkeypatch.setattr(ring_buffer, "read_and_clear_block",
                        read_and_clear_block)


@pytest.mark.parametrize("fault", [_ring_not_cleared,
                                   _ring_read_never_wraps])
def test_ring_fault_is_not_correct(tiny_bench, monkeypatch, fault):
    """The ring buffer's slots left uncleared after their read, or its read
    index clamped at the ring's end instead of wrapping: the recorded run,
    as long as on the chip, turns the ring and shows either."""
    from repro.core import ring_buffer

    fault(ring_buffer, monkeypatch)
    # Two timed windows: warm-up runs on until the run is as long as the
    # chip's (two warm-up, eight more, four timed windows).
    res = _run(tiny_bench, "tiny_bench.ground", seconds=1e-6)
    assert res["cycles"]["recorded"] == res["cycles"]["ring"] + 30
    assert not res["correct"]
    assert (res["checks"]["raster_mismatch"]["value"]
            + res["checks"]["state_mismatch"]["value"]) > 0


def test_exchange_left_out_is_not_correct(tiny_bench, monkeypatch):
    """The window-end exchange between areas skipped: inter-area spikes
    never arrive."""
    from repro.core import exchange

    def window_end(self, ring, block, t0, net, gids, *, blocked):
        return ring, np.int32(0), np.float32(0)

    monkeypatch.setattr(exchange.LocalExchange, "window_end", window_end)
    res = _run(tiny_bench, "tiny_bench.ground")
    assert not res["correct"]
    assert res["checks"]["raster_mismatch"]["value"] > 0
