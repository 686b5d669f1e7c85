"""Device busy milliseconds per window: the union of device op intervals
in the traced window over the number of windows it held."""


def read(ctx):
    tr = ctx.get("trace")
    if not tr or tr["busy_s"] <= 0 or not tr.get("windows"):
        return None
    return tr["busy_s"] * 1e3 / tr["windows"]
