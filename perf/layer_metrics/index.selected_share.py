"""Of the rows a query may see, the share its index lets it attend to:
the learn program's ``index_selected_share_mean`` (a mean over the
update's queries and the layers of ``selected / seen``, the selected
COUNTED from the choice itself), summed over the updates of the process
by the program's counter ``ray_tpu_attention_index_selection_total`` and
divided by the updates counted. At episodes of 8,192 with ``topk`` 2,048
and streams 512 apart the depths alone give 0.59-0.61 (0.597 at depths
drawn evenly; a stream below 2,048 reads 1; at episodes of 16,384:
0.385); a reading near 1 says that the program selects nothing. ``None`` for a program without
the counter or a model without an index."""


def read(ctx):
    from ray_tpu.telemetry import metrics

    totals = getattr(metrics, "index_selection", lambda: {})()
    if not totals.get("updates"):
        return None
    return totals["selected_share_mean"] / totals["updates"]
