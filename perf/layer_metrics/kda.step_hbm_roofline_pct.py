"""Share of its HBM roofline the one-token delta rule with a decay a key
channel reaches, a call: the bytes ONE call (one layer, one token of
every stream) must move (``perf/kda_latent_model.kda_step_bytes``: the
layer's float32 matrices once in and once out, 8 bytes an element, plus
its ``q``, ``k``, decay, ``v``, ``beta`` and ``o`` rows) over the chip's
peak bandwidth (perf/peaks.json), over the device time of a call of the
step kernel: the ``gated_delta_step`` custom-call events of the traced
span by their NAME, and their own count as the calls, so the bytes and
the time are of the same kernels and of nothing else (every KDA layer's
call a step of the fragment and the one tail forward a fragment). The
scope the kernel stands in (``kda/rule``) also holds the L2 norms of
``q`` and ``k``, the decay's ``exp`` and two broadcasts: a reader of the
scope's time divides the kernel's bytes by more than the kernel.

The mean hides two populations in this cell (PERF.md section 5): four
layers' calls reach 79% and two layers' calls take half the time, less
than HBM needs for their bytes. ``None`` for a configuration that is not
``model_type: bailing_hybrid`` or a program whose one-token rule is not
that kernel."""

from perf import flops, kda_latent_model, program_trace, sequence_model

KERNEL = "gated_delta_step"


def kernel_seconds_and_calls(rep, kernel: str = KERNEL):
    """``(device seconds, events)`` of the custom-call events named
    ``kernel`` that begin inside the traced span; ``None`` where there
    is none."""
    if rep is None or not rep.op_scopes:
        return None
    lo, hi = rep.trace.span_ns()
    total, calls = 0.0, 0
    for _, start, duration, name in rep.op_scopes:
        if (name.lstrip("%").startswith(kernel) and " custom-call " in name
                and lo <= start < hi):
            total += (min(start + duration, hi) - start) / 1e9
            calls += 1
    return (total, calls) if calls else None


def read(ctx):
    config = ctx.cell.config
    if not kda_latent_model.is_kda_latent(config):
        return None
    got = kernel_seconds_and_calls(program_trace.report(ctx))
    if got is None or not got[0]:
        return None
    seconds, calls = got
    need = kda_latent_model.kda_step_bytes(config, sequence_model.envs(ctx))
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / (seconds / calls)
