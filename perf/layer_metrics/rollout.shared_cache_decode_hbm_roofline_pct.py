"""Share of the HBM roofline one decode step of a SambaY policy reaches:
the bytes a step MUST move (``perf/sambay_model.decode_step_bytes``:
product weights once at 2 bytes, the others at 4; the full layer's rows
below the position at the mean depth once PER READING LAYER, the layer
itself and every cross layer; a ring's rows inside the window; one row
written a cache; each scan's matrix and convolution inputs read and
written) over the chip's peak bandwidth (perf/peaks.json), over the
device time of a step AS THE CHIP LIVES IT: the step's operations
(``rollout/act`` + ``rollout/env_step`` + ``rollout/state_reset``) and
the time the loop waits for the compiler's asynchronous copies
(``perf/async_waits``, the ``rollout`` layer: the weights' slices are
fetched by ``*-start`` / ``*-done`` pairs whose waits lie under no
scope; without them the operations alone read 103% here, PR 57's first
chip run). Bound by bytes: a step of 16 streams is 0.03 TFLOP. ``None``
for a configuration that is not ``model_type: phi4flash`` or a program
without the scopes or the waits' table."""

from perf import async_waits, flops, program_trace, sambay_model, sequence_model


def read(ctx):
    if not sambay_model.is_sambay(ctx.cell.config):
        return None
    rep = program_trace.report(ctx)
    seconds = sequence_model.decode_seconds(rep)
    waits = async_waits.waits(ctx)
    if seconds is None or not rep.iterations or waits is None:
        return None
    seconds += waits.exposed_ns("rollout") / 1e9  # of the same traced span
    step = seconds / (rep.iterations * sequence_model.fragment_steps(ctx))
    need = sambay_model.decode_step_bytes(
        ctx.cell.config, ctx.num_actions, sequence_model.envs(ctx))
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / step
