"""How every Pallas entry point of ``ray_tpu.ops`` resolves its
``use_pallas`` knob — one rule, decided by what the code can observe,
never by a caught compile failure."""

from __future__ import annotations

import jax


def kernel_selected(
    use_pallas, interpret: bool, *, compiles_on_tpu: bool
) -> bool:
    """An explicit ``use_pallas`` bool forces that path;
    ``interpret=True`` (tests) runs the kernel through the Pallas
    interpreter on any backend. ``use_pallas=None`` (auto) means the
    kernel on the TPU backend IF it compiles there
    (``compiles_on_tpu`` — a per-kernel constant established on the
    chip, with Mosaic's refusal quoted beside it when False) and the
    XLA reference everywhere else. There is no lowering probe: a
    forced kernel that Mosaic refuses raises with Mosaic's message."""
    if use_pallas is not None:
        return bool(use_pallas)
    if interpret:
        return True
    return compiles_on_tpu and jax.default_backend() == "tpu"
