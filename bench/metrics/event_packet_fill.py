"""Percent of the event-packet slots that carried a spike: spikes that
entered the packets over the slots the static bounds allocate (per
area-cycle for intra delivery, per cycle for the window-end inter
delivery), summed over the timed window."""


def read(ctx):
    p = ctx.get("packets")
    if not p or p["slots"] <= 0:
        return None
    return 100.0 * p["entered"] / p["slots"]
