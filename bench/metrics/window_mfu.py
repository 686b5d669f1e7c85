"""The whole window's share of the chip's peak, in percent: the least time
the chip needs for the window's necessary work (``work.py``: bytes at the
HBM peak or operations at the compute peak, whichever is longer) over the
window's wall time."""

from bench import harness, work


def read(ctx):
    w, wall = ctx.get("work"), ctx.get("window_wall_s")
    if not w or not wall or w["bytes"] <= 0:
        return None
    peak = harness.load_peaks(ctx["device_kind"], ctx["bench_dir"])
    least, _ = work.least_time_s(w, peak)
    return 100.0 * least / wall
