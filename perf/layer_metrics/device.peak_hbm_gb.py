"""Peak device memory on the fullest chip in GB (1e9), as the runtime
measured it (``memory_stats()["peak_bytes_in_use"]``) and as the
result line's ``memory_peak_bytes`` has it. On this runtime that is
live buffers; a running program's scratch is not in it (see
``device.program_scratch_gb``)."""


def read(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return ctx.memory_peak_bytes / 1e9
