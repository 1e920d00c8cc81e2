"""Share of the HBM roofline one decode step of a policy of Kimi Delta
Attention beside latent attention reaches: the bytes a step MUST move
(``perf/kda_latent_model.decode_step_bytes``: product weights once at 2
bytes, every held expert's among them, the others at 4; each KDA
matrix and its convolutions' inputs once in and once out; the latent
rows of the mean depth once and one row written; no matrix read twice,
no masked row, no expanded key or value) over the chip's peak bandwidth
(perf/peaks.json), over the device time of a step AS THE CHIP LIVES IT:
the leaf operations under ``rollout/act`` + ``rollout/env_step`` +
``rollout/state_reset`` AND the exposed waits of the lane's loop
(``perf/async_waits.py``: the ``*-done`` operations the compiler's
asynchronous copies end in, which carry no scope). In this cell the
compiler loads a third of a step's weights through such copies (0.79 ms
of waits beside 2.12 ms of scoped time a step): the scoped time alone
leaves out part of the work and reads 117. Bound by bytes: a step of 16
streams is 0.03 TFLOP. ``None`` for a configuration that is not
``model_type: bailing_hybrid`` or a program without scopes."""

from perf import async_waits, flops, kda_latent_model, program_trace, sequence_model


def read(ctx):
    if not kda_latent_model.is_kda_latent(ctx.cell.config):
        return None
    rep = program_trace.report(ctx)
    seconds = sequence_model.decode_seconds(rep)
    waits = async_waits.waits(ctx)
    if seconds is None or waits is None or not rep.iterations:
        return None
    seconds += waits.exposed_ns("rollout") / 1e9
    step = seconds / (rep.iterations * sequence_model.fragment_steps(ctx))
    need = kda_latent_model.decode_step_bytes(
        ctx.cell.config, ctx.num_actions, sequence_model.envs(ctx))
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / step
