"""Share of its HBM roofline the fragment-form selective scan reaches, a
layer and update: the bytes the scan of ONE layer must move for the
update's fragments, forward and backward
(``perf/sambay_model.scan_fragment_bytes``: a token's ``u``, ``dt``,
``B``, ``C`` in and ``y`` out; ``dy`` and the four inputs in and their
cotangents out; the matrix once in and once out each way) over the
chip's peak bandwidth (perf/peaks.json), over the device time of the
leaf operations under the model's ``learn/scan/step`` scope (forward,
the recomputation and the backward pass of the recurrence and the skip
``D u``), per update and scan layer. The scan is VECTOR-bound, not
byte-bound, once its matrix stays on the chip (an ``exp`` and eight more
operations a (token, channel, state)): perf/peaks.json has no vector
peak, so the share is of HBM, and a scan whose carry lives in HBM moves
the matrix once a token and reads far below 100. ``None`` for a
configuration that is not ``model_type: phi4flash`` or a program without
the scope."""

from perf import flops, program_trace, sambay_model, sequence_model


def read(ctx):
    config = ctx.cell.config
    if not sambay_model.is_sambay(config):
        return None
    rep = program_trace.report(ctx)
    got = sequence_model.seconds_under(rep, "learn/scan/step")
    layers = sambay_model.kinds(config).count(sambay_model.SCAN)
    if got is None or not rep.updates or not layers:
        return None
    need = sambay_model.scan_fragment_bytes(
        config, sequence_model.envs(ctx), sequence_model.fragment_steps(ctx))
    peak = flops.load_peaks(ctx.device_kind)["hbm_bytes_per_s"]
    return 100.0 * need / peak / (got / (rep.updates * layers))
