"""Model operations one env step (one token) costs a PPO over the SDAR
block stack on the fused lane, by what the MATHEMATICS of block
diffusion needs a token: ``S + 1`` forward passes to generate it (``S``
denoise forwards and the commit forward of its block, each over all
``B`` tokens of the block) and, for each of ``num_sgd_iter`` trainings,
``S + 1`` passes forward and backward (the clean pass and the ``S``
noisy ones of the trace-level update; forward + backward = 3 x forward).
A multiply-add counts as two operations. Counted: a token-pass pays for
attention's projections, for a score and a value product of the layer's
query heads over the keys inside the block-causal mask at the mean depth
of an episode (``perf/block_diffusion_model.mean_rows_seen``: its own
block's rows and every earlier one's, not the slots a masked product
also multiplies), for the router, for the experts it is routed to AND
that are held here (``top_k x held / router_outputs``: one of its eight)
and, in the passes whose logits are read, for the head. Recomputed
operations are not counted.

``learner.mfu_pct`` therefore reads, here, the share of the chip's
bfloat16 peak that the ``3 (S + 1) + (S + 1)`` token-passes a token of
THIS algorithm needs fill: a token of an autoregressive policy of the
same widths needs ``3 + 1`` of them, so at the same share this cell
yields ``1 / (S + 1)`` of that policy's tokens a second. It is not a
share of what an autoregressive policy would need."""

from perf import block_diffusion_model as model


def forward_flops_per_token_pass(config, num_actions: int) -> float:
    c = config
    d, dh = int(c["hidden_size"]), int(c["head_dim"])
    routed = (
        int(c["num_experts_per_tok"]) * int(c["num_experts"])
        / float(c.get("router_outputs", c["num_experts"]))
    )
    p = model.layer_param_counts(config)
    layer = (p["attention"] + p["router"] + routed * p["one_expert"]
             + int(c["num_attention_heads"]) * model.mean_rows_seen(config) * 2 * dh)
    macs = int(c["num_hidden_layers"]) * layer + d * num_actions + d
    return 2.0 * macs


def train_flops_per_env_step(config, num_actions: int) -> float:
    passes = model.generation(config)["steps"] + 1
    fwd = passes * forward_flops_per_token_pass(config, num_actions)
    return fwd * (1.0 + 3.0 * int(config["algo_config"].get("num_sgd_iter", 1)))
