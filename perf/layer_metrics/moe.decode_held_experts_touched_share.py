"""Of a layer's held experts, the share (%) that at least one stream's
token reached in a decode step, a mean over the expert layers, the steps
and the updates of the process: the program's counter
``ray_tpu_moe_decode_held_experts_touched_total``, fed from the learn
program's own routing of the fragments the lane generated (a fragment is
one stream's rollout, so a place in it is a decode step). The dense
one-token experts' product reads every held expert's weights; this is
the share of them some token chose. ``None`` for a program without the
counter or a model that reports none."""


def read(ctx):
    from ray_tpu.telemetry import metrics

    totals = getattr(metrics, "decode_held_experts_touched", lambda: {})()
    if not totals.get("updates"):
        return None
    return 100.0 * totals["share"] / totals["updates"]
