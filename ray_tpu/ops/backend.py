"""The one rule for "does a Pallas kernel's TPU lowering exist"."""

import jax


def is_tpu() -> bool:
    """The process's default backend is a TPU. Every op that picks
    between a kernel and its ``jax.numpy`` text asks here. Not the
    platform a computation is lowered for: a compile for a described
    TPU from a CPU host (the tests' ``v5e_mesh``, a memory budget taken
    ahead of time) sees the text, and has to call the kernel's own entry
    to see the kernel."""
    return jax.default_backend() == "tpu"
