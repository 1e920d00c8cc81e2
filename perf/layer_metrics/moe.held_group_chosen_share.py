"""Of an update's tokens, the share (%) whose chosen GROUPS of experts
hold one of this chip's experts, a mean over the expert layers and the
updates of the process: the program's counter
``ray_tpu_moe_held_group_chosen_total``, fed from the learn program's
own group-limited routing (``ops/moe.chosen_groups``: a token keeps
``topk_group`` of ``n_group`` groups before its top-k). The tokens
outside it send nothing to this chip by the group choice alone; with the
held experts in ONE of 8 groups of which 4 are kept it is near 50 by
construction. ``None`` for a program without the counter or a router
that chooses no groups."""


def read(ctx):
    from ray_tpu.telemetry import metrics

    totals = getattr(metrics, "held_group_chosen", lambda: {})()
    if not totals.get("updates"):
        return None
    return 100.0 * totals["share"] / totals["updates"]
