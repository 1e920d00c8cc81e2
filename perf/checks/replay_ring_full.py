"""Before the window the replay ring is full, on the device, and drawn
from through the device tree: the window measures the steady state the
traffic mix describes, not a ring still filling or one spilled to the
host."""

STAGE = "after_warmup"
LIMITS = ()


def run(state):
    buf = state.algo.local_replay_buffer.buffers["default_policy"]
    state.checks.true(
        "replay_ring_full_on_device_before_window",
        not buf.spilled and buf.tree_plane == "device"
        and len(buf) == buf.capacity,
        f"{len(buf)} of {buf.capacity} rows, {buf.storage_bytes} B, "
        f"tree plane {buf.tree_plane}",
    )
