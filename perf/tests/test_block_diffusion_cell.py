"""The block-diffusion cell's files, rehearsed on the CPU at a small
size: the committed configuration, traffic mix, reference, checks, FLOP
rule and readers of ``sdar_ppo.fused_blocks.1chip`` with only the sizes
rewritten (hidden 32, two layers of 4 query heads of 16 over 2 KV heads,
a router over 8 experts of which 4 are held, top-2, a vocabulary of 64
whose last row is ``[MASK]``, episodes of 32, 4 streams x 16 tokens: four
blocks of 4 a stream and fragment)."""

import json
import os

import pytest

from perf import block_diffusion_model as model
from perf import manifest as manifest_lib
from perf import run as run_lib
from perf.tests.conftest import _rewrite

CELL = "sdar_ppo.fused_blocks.1chip"
CONFIG = "sdar_30b_a3b_ppo"
TRAFFIC = "fused_blocks_v18992_e4096_b4s2"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SMALL = {
    "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 4,
    "router_outputs": 8, "experts_held": [0, 4], "num_experts_per_tok": 2,
    "moe_intermediate_size": 16, "max_position_embeddings": 32,
    "mask_token_id": 63, "vocab_size": 64,
}
NEW_METRICS = (
    "diffusion.denoise_device_ms_per_block", "diffusion.commit_device_ms_per_block",
    "diffusion.noisy_pass_device_ms_per_update",
    "diffusion.clean_pass_device_ms_per_update",
    "diffusion.rollout_passes_per_token", "rollout.block_decode_hbm_roofline_pct",
)


@pytest.fixture()
def small_root(tiny_root):
    perf = os.path.join(tiny_root, "perf")

    def shrink_config(c):
        for target in (c, c["algo_config"]["model"]["sequence_lm"]):
            target.update({k: v for k, v in SMALL.items()
                           if k != "vocab_size" or target is c})
        c["algo_config"]["model"]["dtype"] = "float32"
        c["algo_config"]["model"]["max_seq_len"] = 16
        c["algo_config"]["lr"] = 1e-4

    def shrink_traffic(t):
        t["algo_config"].update(
            num_envs_per_worker=4, rollout_fragment_length=16,
            train_batch_size=64, sgd_minibatch_size=64,
            env_config={"vocab_size": 64, "episode_length": 32, "phase_stride": 8},
        )
        t["trace_iterations"] = 2

    def loosen(limits):
        # CPU float32 against a float32 reference: the chip's limits
        # are far above anything read here
        for entry in limits["limits"].values():
            entry["limit"] = max(entry["limit"], 0.05) if entry["limit"] else 0

    _rewrite(os.path.join(perf, "configs", CONFIG + ".json"), shrink_config)
    _rewrite(os.path.join(perf, "traffic", TRAFFIC + ".json"), shrink_traffic)
    _rewrite(os.path.join(perf, "limits", CONFIG + ".json"), loosen)
    return tiny_root


def test_the_committed_files_agree_with_each_other_and_with_the_catalogue():
    cell = manifest_lib.load_cell(CELL)
    c, t = cell.config, cell.traffic["algo_config"]
    lm = c["algo_config"]["model"]["sequence_lm"]
    for key, value in lm.items():
        assert c[key] == value, key  # one architecture, stated twice
    assert (lm["block_length"], lm["denoising_steps"]) == (4, 2)
    assert lm["mask_token_id"] == c["vocab_size"] - 1 == 18991
    assert "dtype" not in c["algo_config"]["model"]  # the shipped bfloat16
    assert t["env_config"]["vocab_size"] == c["vocab_size"] == 18992 == 151936 // 8
    assert t["env_config"]["episode_length"] == c["max_position_embeddings"] == 4096
    assert t["rollout_fragment_length"] == c["algo_config"]["model"]["max_seq_len"] == 256
    assert (t["num_envs_per_worker"] * t["rollout_fragment_length"]
            == t["train_batch_size"] == t["sgd_minibatch_size"] == 4096)
    assert t["env_config"]["phase_stride"] * t["num_envs_per_worker"] == 4096
    # every length the lane counts is whole blocks
    for n in (t["rollout_fragment_length"], t["env_config"]["episode_length"],
              t["env_config"]["phase_stride"]):
        assert n % lm["block_length"] == 0
    assert c["experts_held"] == [0, c["num_experts"]] == [0, 16]
    assert c["router_outputs"] == c["published"]["num_experts"] == 128
    assert set(c["reduced"]) == set(c["published"]) - {"deployment", "mask_token_id"}
    assert set(c["reduced"]) == set(c["reduced_why"]) == set(cell.config_entry["reduced"])
    assert "8 chips share each layer" in c["published"]["deployment"]
    for key in ("block_length", "denoising_steps", "sampling", "mask_token",
                "qk_norm", "attention_mask", "objective", "gae_order",
                "tail_bootstrap", "no_diffusion_term", "value_head", "ppo",
                "weights", "superstep"):
        assert key in c["assumed"], key
    assert cell.config_entry["source"] == c["source"]
    assert cell.chips == 1 and "16 streams x 256" in cell.why
    assert cell.traffic["checks"] == ["token_streams_at_phase", "block_rollout_fragment"]
    assert c["checks"] == ["block_fused_dispatch"]
    # the traffic mix is the Laguna cell's but for the vocabulary and
    # the checks that know a block a step
    with open(os.path.join(
            manifest_lib.PERF_DIR, "traffic", "fused_tokens_v12544_e4096.json")) as f:
        base = json.load(f)
    base.update(name=cell.traffic["name"], what=cell.traffic["what"],
                checks=cell.traffic["checks"])
    base["algo_config"]["env_config"]["vocab_size"] = 18992
    assert base == cell.traffic
    # every number of the catalogue's entry under its own key, but for
    # the reduced keys: no width differs from the source
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalogue beside the guide here")
    with open(CATALOG) as f:
        entry = next(r for r in map(json.loads, f) if r["name"] == "SDAR-30B-A3B-Chat")
    assert entry["source_url"] == c["source"]
    for key, value in entry["config"].items():
        if key in c["reduced"]:
            assert c["published"][key] == value and c[key] < value, key
        else:
            assert c[key] == value, key


def test_parameter_and_byte_arithmetic_at_the_published_widths():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    v = c["vocab_size"]
    layer = model.layer_param_counts(c)
    assert layer["attention"] == 18_874_368 and layer["router"] == 262_144
    assert layer["one_expert"] == 4_718_592 and layer["experts_held"] == 75_497_472
    assert layer["all"] == 94_638_336
    assert model.param_count(c, v) == c["parameters_held"] == 645_625_345
    # the permitted departure's four layers
    assert model.param_count(dict(c, num_hidden_layers=4), v) == 456_348_673
    # the same sum over all 48 layers, 128 experts and the whole
    # vocabulary is the published size
    uncut = dict(c, num_hidden_layers=48, num_experts=128)
    assert model.param_count(uncut, 151936) == 48 * 623_120_640 + 622_329_856 + 4_097
    assert round(model.param_count(uncut, 151936) / 1e9, 2) == 30.53
    # a block forward of 16 streams: product weights at 2 bytes, the
    # rows below the block at the mean depth
    assert round(2 * model.product_weight_count(c, v) / 1e9, 2) == 1.21
    assert model.cache_row_bytes(c) * 6 == 12_288
    assert model.mean_rows_seen(c) == 2050.0
    assert round(16 * 12_288 * 2046 / 1e9, 2) == 0.40
    forward = model.block_forward_bytes(c, v, 16)
    assert round(forward / 1e9, 2) == 1.63
    # the commit forward leaves rows and nothing else: not the last
    # layer's queries, cache rows, W_o, router and experts, not the head
    commit = model.commit_forward_bytes(c, v, 16)
    assert round((forward - commit) / 1e9, 3) == round(
        (2 * (75_497_472 + 2 * 8_388_608) + 4 * 262_144 + 16 * 2_048 * 2_046
         + 2 * 2048 * 18992 + 4 * 4_097 + 4 * 64 * 18992) / 1e9, 3) == 0.335
    assert model.block_step_bytes(c, v, 16) == 2 * forward + commit


def test_flop_rule_counts_the_passes():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    rule = cell._module("flop_rules", "sdar_ppo")
    one = rule.forward_flops_per_token_pass(c, c["vocab_size"])
    # 3 passes to generate a token, 3 passes forward and backward to train it
    assert rule.train_flops_per_env_step(c, c["vocab_size"]) == 3 * one * 4
    # by hand: one routed-and-held expert a token and layer (8 x 16 /
    # 128), 32 heads over the rows of its own and the earlier blocks
    products = 2 * (2048 * 18992 + 2048 + 6 * (18_874_368 + 262_144 + 4_718_592))
    scores = 2 * 6 * 32 * 2050.0 * 2 * 128
    assert abs(one - (products + scores)) < 1.0
    assert 0.56e9 < one < 0.57e9


def test_the_cell_runs_end_to_end_at_a_small_size(small_root):
    from ray_tpu.telemetry import metrics

    cell = manifest_lib.load_cell(CELL, small_root)
    before = dict(metrics.diffusion_token_passes())
    out = run_lib.run_cell(cell, 2**31 + 5, 1.0, False, require_tpu=False)
    by_name = {r["check"]: r for r in out["checks"]}
    assert out["correct"], [r for r in out["checks"] if not r["ok"]]
    assert out["failed"] == 0 and out["attempted"] >= 1
    for name in ("streams_off_phase", "grad_rel_l2", "grad_leaf_rel_l2_max",
                 "loss_rel", "update_rel_l2", "adam_step_rel_l2",
                 "dispatch_rows_wrong", "rollout_logit_rel_l2",
                 "rollout_value_rel_l2", "rollout_state_rel_l2",
                 "route_top_k_mismatch_share", "forms_logit_rel_l2",
                 "rollout_advantage_rel_l2", "iteration_adds_up",
                 "dispatch_program_traced_once", "params_replicated_on_every_chip"):
        assert by_name[name]["ok"], by_name[name]
    assert by_name["rollout_positions_wrong"]["value"] == 0
    assert "depths 0-24, 4 distinct" in by_name["streams_off_phase"]["note"]
    # two of a block's four tokens in each pass
    assert "committed by pass [32, 32]" in by_name["rollout_logit_rel_l2"]["note"]
    # float32 on the CPU takes the reference's gradient and its routes
    assert by_name["grad_rel_l2"]["value"] < 1e-3
    assert by_name["forms_logit_rel_l2"]["value"] < 1e-4
    assert by_name["route_top_k_mismatch_share"]["value"] == 0
    assert by_name["rollout_logit_rel_l2"]["value"] < 1e-3
    assert by_name["rollout_state_rel_l2"]["value"] < 1e-3
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    # the counters: 3 forwards a block of 4, 3 token-passes a trained token
    after = metrics.diffusion_token_passes()
    grown = {k: after[k] - before.get(k, 0.0) for k in after}
    assert grown["denoise"] == 2 * grown["committed"] == 2 * grown["commit"] > 0
    assert grown["noisy"] == 2 * grown["clean"] > 0
    ctx = run_lib.Context(cell, None, None, 1, "cpu", 64)
    assert cell.reader("diffusion.rollout_passes_per_token")(ctx) == 0.75
    assert cell.reader("moe.max_expert_load_ratio")(ctx) >= 1.0
    # a reader of the device trace finds nothing without one and says so
    for name in NEW_METRICS:
        if name != "diffusion.rollout_passes_per_token":
            assert cell.reader(name)(ctx) is None, name
    taken = {m["name"] for m in cell.per_layer}
    assert set(cell.chosen_metrics) <= taken and set(NEW_METRICS) <= taken
    assert not {name for name in taken if "decode_hbm_roofline" in name} - {
        "rollout.block_decode_hbm_roofline_pct"}


def test_controls_come_out_worse_than_the_system(small_root):
    from perf import control

    cell = manifest_lib.load_cell(CELL, small_root)
    (row,) = control.readings(cell, [2**31 + 11], require_tpu=False)
    for name in ("grad_rel_l2", "rollout_logit_rel_l2", "rollout_value_rel_l2",
                 "rollout_state_rel_l2"):
        for precision in ("int8", "fp8"):
            assert row[precision][name] > 10 * row["system"][name], (name, row)


def test_the_readers_return_nothing_for_another_cell_and_match_scopes():
    import types

    other = manifest_lib.load_cell("laguna_ppo.fused_tokens.1chip")
    cell = manifest_lib.load_cell(CELL)
    ctx = run_lib.Context(other, None, None, 1, "cpu", 64)
    for name in NEW_METRICS[:4] + NEW_METRICS[5:]:
        assert cell.reader(name)(ctx) is None, name
    from perf import sequence_model

    act = "jit(rollout_superstep)/while/body/closed_call/rollout/act/"
    learn = "jit(rollout_superstep)/sgd_nest/while/body/learn/loss_grad/"
    ops = [
        [act + "denoise/attn/scores/step_attention", 0, 1000],
        [act + "denoise/moe/experts/dot_general", 1000, 200],
        [act + "commit/attn/out/dot_general", 2000, 500],
        [learn + "learn/clean/learn/attn/scores/fragment_attention_fwd", 3000, 700],
        [learn + "transpose(jvp(learn/noisy))/learn/moe/experts/dot_general", 4000, 300],
    ]
    rep = types.SimpleNamespace(
        op_scopes=ops, trace=types.SimpleNamespace(bounds=None))
    under = lambda needle: sequence_model.seconds_under(rep, needle) * 1e9
    assert abs(under("rollout/act/denoise") - 1200) < 1e-6
    assert abs(under("rollout/act/commit") - 500) < 1e-6
    assert abs(under("learn/clean") - 700) < 1e-6
    assert abs(under("learn/noisy") - 300) < 1e-6
    # the kinds' own scopes nest inside the passes' as they do today
    assert abs(under("learn/attn") - 700) < 1e-6
