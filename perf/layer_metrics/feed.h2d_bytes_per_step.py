"""Host-to-device bytes per env step trained, all paths of
``telemetry.metrics.h2d_bytes_by_path()`` over the window. The fused
lanes ship only PRNG keys and coefficients (about 0); an actor-fed
lane ships every batch."""


def read(ctx):
    win = ctx.window
    steps = win.delta("sampled")
    if not steps:
        return None
    return win.h2d_delta() / steps
