"""The mixed-geometry attention cell's files, rehearsed on the CPU at a
small size: the committed configuration, traffic mix, reference, checks,
FLOP rule and readers of ``laguna_ppo.fused_tokens.1chip`` with only the
sizes rewritten (hidden 32, the leading dense layer and one period
window, window, window, full; 4 / 6 query heads of 16 over 2 KV heads, a
window of 8 in episodes of 32, a router over 8 experts of which 2 are
held, top-3, a vocabulary of 64, 8 streams x 8 tokens: six of the
streams past the window)."""

import json
import os

import pytest

from perf import manifest as manifest_lib
from perf import mixed_attention_model as model
from perf import run as run_lib
from perf.tests.conftest import _rewrite

CELL = "laguna_ppo.fused_tokens.1chip"
CONFIG = "laguna_xs2_33b_a3b_ppo"
TRAFFIC = "fused_tokens_v12544_e4096"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
SMALL = {
    "hidden_size": 32, "intermediate_size": 48, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 8,
    "num_experts": 2, "router_outputs": 8, "experts_held": [0, 2],
    "num_experts_per_tok": 3, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "max_position_embeddings": 32,
    "vocab_size": 64,
}


@pytest.fixture()
def small_root(tiny_root):
    perf = os.path.join(tiny_root, "perf")

    def shrink_config(c):
        for target in (c, c["algo_config"]["model"]["sequence_lm"]):
            target.update({k: v for k, v in SMALL.items()
                           if k != "vocab_size" or target is c})
            target["num_attention_heads_per_layer"] = [
                4 if h == 48 else 6 for h in target["num_attention_heads_per_layer"]]
            target["rope_parameters"]["full_attention"].update(
                original_max_position_embeddings=8, factor=4, beta_fast=2,
                beta_slow=0.25, rope_theta=100)
        c["algo_config"]["model"]["dtype"] = "float32"
        c["algo_config"]["model"]["max_seq_len"] = 8
        c["algo_config"]["lr"] = 1e-4

    def shrink_traffic(t):
        t["algo_config"].update(
            num_envs_per_worker=8, rollout_fragment_length=8,
            train_batch_size=64, sgd_minibatch_size=64,
            env_config={"vocab_size": 64, "episode_length": 32, "phase_stride": 4},
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
        if key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
            # published whole; the first five are run
            assert c[key][:5] == value and len(c[key]) == 40, key
        else:
            assert c[key] == value, key  # one architecture, stated twice
    assert lm["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3 + [
        "full_attention"]
    assert lm["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert lm["num_attention_heads_per_layer"] == [48, 64, 64, 64, 48]
    assert "dtype" not in c["algo_config"]["model"]  # the shipped bfloat16
    assert t["env_config"]["vocab_size"] == c["vocab_size"] == 12544 == 100352 // 8
    assert t["env_config"]["episode_length"] == c["max_position_embeddings"] == 4096
    assert t["rollout_fragment_length"] == c["algo_config"]["model"]["max_seq_len"] == 256
    assert (t["num_envs_per_worker"] * t["rollout_fragment_length"]
            == t["train_batch_size"] == t["sgd_minibatch_size"] == 4096)
    assert t["env_config"]["phase_stride"] * t["num_envs_per_worker"] == 4096
    assert c["experts_held"] == [0, c["num_experts"]] == [0, 32]
    assert c["router_outputs"] == c["published"]["num_experts"] == 256
    assert set(c["reduced"]) == set(c["published"]) - {"deployment"}
    assert set(c["reduced"]) == set(c["reduced_why"]) == set(cell.config_entry["reduced"])
    assert "8 chips share each layer" in c["published"]["deployment"]
    for key in ("qk_norm", "gate", "window_convention", "rope", "router",
                "shared_expert", "value_head", "ppo", "weights", "router_aux_loss"):
        assert key in c["assumed"], key
    assert cell.config_entry["source"] == c["source"]
    assert cell.chips == 1 and "16 streams x 256" in cell.why
    # the traffic mix is ``fused_tokens_v12544`` but for the episode
    with open(os.path.join(
            manifest_lib.PERF_DIR, "traffic", "fused_tokens_v12544.json")) as f:
        base = json.load(f)
    base["name"], base["what"] = cell.traffic["name"], cell.traffic["what"]
    base["algo_config"]["env_config"].update(episode_length=4096, phase_stride=256)
    assert base == cell.traffic
    # every number of the catalogue's entry under its own key, but for
    # the reduced keys: no width differs from the source
    if not os.path.isfile(CATALOG):
        pytest.skip("no catalogue beside the guide here")
    with open(CATALOG) as f:
        entry = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
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
    full, window = (model.layer_param_counts(c, l) for l in model.layers(c)[:2])
    assert full["attention"] == 29_458_432 and window["attention"] == 37_879_808
    assert full["dense"] == 50_331_648 and window["router"] == 524_288
    assert window["experts_held"] == 32 * 3_145_728 and window["shared"] == 3_145_728
    assert model.param_count(c, v) == c["parameters_held"] == 691_627_265
    # the same sum over all 40 layers, 256 experts and the whole
    # vocabulary is the published size
    uncut = dict(c, num_hidden_layers=40, num_experts=256)
    assert round(model.param_count(uncut, 100352) / 1e9, 2) == 33.44
    # an element-wise gate in the head-wise one's place
    wider = sum(2048 * h * 127 for h in c["num_attention_heads_per_layer"])
    assert round((model.param_count(uncut, 100352) + wider) / 1e9, 2) == 34.07
    assert model.cache_bytes(c) == [16_777_216] + [2_097_152] * 3 + [16_777_216]
    assert sum(model.cache_bytes(c)) == 39_845_888
    # a decode step of 16 streams: product weights at 2 bytes, the rows
    # inside the masks at the mean depth
    assert round(2 * model.product_weight_count(c, v) / 1e9, 2) == 1.33
    assert round(2 * 4 * window["experts_held"] / 1e9, 2) == 0.81
    seen = model.mean_rows_seen(c)
    assert seen == {"full": 2048.5, "window": 480.0625}
    assert round(16 * 4096 * 2 * seen["full"] / 1e9, 2) == 0.27
    assert round(16 * 4096 * 3 * seen["window"] / 1e9, 2) == 0.09
    assert round(model.decode_step_bytes(c, v, 16) / 1e9, 2) == 1.70


def test_flop_rule_counts_rollout_and_update():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    rule = cell._module("flop_rules", "laguna_ppo")
    fwd = rule.forward_flops_per_token(c, c["vocab_size"])
    assert rule.train_flops_per_env_step(c, c["vocab_size"]) == 4 * fwd
    # by hand: one routed-and-held expert a token and layer (8 x 32 /
    # 256) and the shared one; 48 or 64 heads over the rows seen
    products = 2 * (2048 * 12544 + 2048 + 2 * 29_458_432 + 3 * 37_879_808
                    + 50_331_648 + 4 * (524_288 + 2 * 3_145_728))
    scores = 2 * 2 * 128 * (2 * 48 * 2048.5 + 3 * 64 * 480.0625)
    assert abs(fwd - (products + scores)) < 1.0
    assert 0.69e9 < fwd < 0.71e9


def test_the_cell_runs_end_to_end_at_a_small_size(small_root):
    from ray_tpu.telemetry import metrics

    cell = manifest_lib.load_cell(CELL, small_root)
    before = dict(metrics.attention_layer_lowerings())
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
    assert "depths 0-28, 8 distinct" in by_name["streams_off_phase"]["note"]
    # float32 on the CPU takes the reference's gradient and its routes
    assert by_name["grad_rel_l2"]["value"] < 1e-3
    assert by_name["forms_logit_rel_l2"]["value"] < 1e-4
    assert by_name["route_top_k_mismatch_share"]["value"] == 0
    assert by_name["rollout_logit_rel_l2"]["value"] < 1e-3
    assert by_name["rollout_state_rel_l2"]["value"] < 1e-3
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    # the programs traced both geometries in both forms
    after = metrics.attention_layer_lowerings()
    for geometry in ("full_attention/4/yarn", "sliding_attention/6/default"):
        assert after.get(geometry, 0) >= before.get(geometry, 0) + 3, geometry
    # the learn program's routing fed the rollout's statistic
    ctx = run_lib.Context(cell, out and None, None, 1, "cpu", 64)
    share = cell.reader("moe.decode_held_experts_touched_share")(ctx)
    assert 0.0 < share <= 100.0
    # a reader of the device trace finds nothing without one and says so
    for name in ("attn_mix.scope_device_ms_per_update",
                 "attn_mix.decode_scope_device_ms_per_step",
                 "attn_mix.cache_bytes_per_stream",
                 "rollout.mixed_decode_hbm_roofline_pct",
                 "rollout.decode_device_ms_per_step",
                 "attn.scope_device_ms_per_update"):
        assert cell.reader(name)(ctx) is None, name
    taken = {m["name"] for m in cell.per_layer}
    assert set(cell.chosen_metrics) <= taken
    assert not {"rollout.decode_hbm_roofline_pct", "swa.scope_device_ms_per_update",
                "rollout.window_decode_hbm_roofline_pct"} & taken
    assert {"attn_mix.scope_device_ms_per_update", "attn_mix.cache_bytes_per_stream",
            "attn_mix.decode_scope_device_ms_per_step",
            "moe.decode_held_experts_touched_share",
            "rollout.mixed_decode_hbm_roofline_pct", "learner.mfu_pct"} <= taken


def test_controls_come_out_worse_than_the_system(small_root):
    from perf import control

    cell = manifest_lib.load_cell(CELL, small_root)
    (row,) = control.readings(cell, [2**31 + 11], require_tpu=False)
    for name in ("grad_rel_l2", "rollout_logit_rel_l2", "rollout_value_rel_l2",
                 "rollout_state_rel_l2"):
        for precision in ("int8", "fp8"):
            assert row[precision][name] > 10 * row["system"][name], (name, row)


def test_the_readers_return_nothing_for_another_cell_and_match_scopes_in_order():
    import types

    other = manifest_lib.load_cell("granite4h_ppo.fused_tokens.1chip")
    cell = manifest_lib.load_cell(CELL)
    ctx = run_lib.Context(other, None, None, 1, "cpu", 64)
    for name in ("attn_mix.scope_device_ms_per_update",
                 "attn_mix.decode_scope_device_ms_per_step",
                 "rollout.mixed_decode_hbm_roofline_pct",
                 "attn_mix.cache_bytes_per_stream"):
        assert cell.reader(name)(ctx) is None, name
    seconds = cell._module(
        "layer_metrics", "attn_mix.decode_scope_device_ms_per_step").seconds
    act = "jit(rollout_superstep)/while/body/closed_call/rollout/act/"
    ops = [
        [act + "swa/scores/dot_general", 0, 1000],
        [act + "swa/gate/mul", 1000, 200],
        [act + "attn/out/dot_general", 2000, 500],
        ["jit(rollout_superstep)/sgd_nest/while/body/learn/loss_grad/learn/attn/out/mul",
         3000, 700],
        [act + "moe/experts/dot_general", 4000, 300],
    ]
    rep = lambda ops: types.SimpleNamespace(
        op_scopes=ops, trace=types.SimpleNamespace(bounds=None))
    assert abs(seconds(rep(ops)) - 1700 / 1e9) < 1e-15
    assert seconds(rep(ops[3:])) is None
    assert seconds(None) is None
