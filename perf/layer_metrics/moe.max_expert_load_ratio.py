"""How uneven the held experts' load is: the largest count of tokens
one held expert of a layer saw in an update over the mean count of a
held expert, both summed over the updates of the process (the
program's counter ``ray_tpu_moe_held_expert_tokens_total``, fed from
the learn program's own routing). 1.0 is even; ``None`` for a model
that reports no expert load."""


def read(ctx):
    from ray_tpu.telemetry import metrics

    totals = getattr(metrics, "expert_load_totals", lambda: {})()
    if not totals.get("mean"):
        return None
    return totals["max"] / totals["mean"]
