"""The largest scratch allocation of any program the cell ran, in GB
(1e9) per chip: XLA's own estimate (``memory_analysis()
.temp_size_in_bytes``, recorded by the program's device ledger as each
program first compiles), NOT a measurement, and not part of
``memory_peak_bytes``. What the chip must hold beside the live buffers
while that program runs."""


def read(ctx):
    if not ctx.program_temp_bytes:
        return None
    return max(ctx.program_temp_bytes.values()) / 1e9
