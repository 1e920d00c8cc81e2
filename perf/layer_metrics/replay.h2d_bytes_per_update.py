"""Host-to-device bytes the replay plane ships per update: the
``replay_sample`` path (0 under the device tree) plus ``replay_rng``
(the generator's raw uniform stream), over the window's updates."""


def read(ctx):
    win = ctx.window
    updates = win.updates()
    if not updates:
        return None
    return (win.h2d_delta("replay_sample") + win.h2d_delta("replay_rng")) / updates
