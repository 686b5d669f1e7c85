"""Host seconds of ``build_network`` (the connectivity build), clocked by
the harness around the call."""


def read(ctx):
    return ctx.get("build_s")
