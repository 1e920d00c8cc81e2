"""The Nemotron-H cell's files, rehearsed on the CPU at a small size:
the committed configuration, traffic mix, reference, limits, FLOP rule
and the three new readers of ``nemotron3nano_ppo.fused_tokens.1chip``
with only the sizes rewritten (hidden 48, the pattern's first nine
characters ``MEMEM*EME``; 8 state-space heads of 8 over a state of 16 in
2 groups, chunks of 8; top-3 of 16 router outputs with 4 experts of 24
held, a shared expert of 40; 4 query heads on 2 KV heads of 16; a
vocabulary of 64, 8 streams x 16 tokens: two chunks a fragment)."""

import os
import types

import numpy as np
import pytest

from perf import manifest as manifest_lib
from perf import run as run_lib
from perf import ssm_moe_model
from perf.tests.conftest import _rewrite

CELL = "nemotron3nano_ppo.fused_tokens.1chip"
CONFIG = "nemotron3_nano_30b_a3b_ppo"
TRAFFIC = "fused_tokens_v16384_f256"
SMALL = {
    "hidden_size": 48, "mamba_num_heads": 8, "mamba_head_dim": 8,
    "ssm_state_size": 16, "n_groups": 2, "chunk_size": 8,
    "n_routed_experts": 4, "experts_held": [0, 4], "router_outputs": 16,
    "num_experts_per_tok": 3, "moe_intermediate_size": 24,
    "moe_shared_expert_intermediate_size": 40,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 32, "vocab_size": 64,
}


@pytest.fixture()
def small_root(tiny_root):
    perf = os.path.join(tiny_root, "perf")

    def shrink_config(c):
        c.update(SMALL)
        lm = c["algo_config"]["model"]["sequence_lm"]
        lm.update({k: v for k, v in SMALL.items() if k != "vocab_size"})
        c["algo_config"]["model"]["dtype"] = "float32"
        c["algo_config"]["model"]["max_seq_len"] = 16
        c["algo_config"]["lr"] = 1e-4

    def shrink_traffic(t):
        t["algo_config"].update(
            num_envs_per_worker=8, rollout_fragment_length=16,
            train_batch_size=128, sgd_minibatch_size=128,
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


def test_the_committed_files_agree_with_each_other():
    cell = manifest_lib.load_cell(CELL)
    c, t = cell.config, cell.traffic["algo_config"]
    lm = c["algo_config"]["model"]["sequence_lm"]
    for key, value in lm.items():
        assert c[key] == value, key  # one architecture, stated twice
    # the pattern is published whole; its first nine characters are run
    assert len(c["hybrid_override_pattern"]) == c["published"]["num_hidden_layers"] == 52
    assert c["hybrid_override_pattern"][: c["num_hidden_layers"]] == "MEMEM*EME"
    assert ssm_moe_model.kinds(c) == list("MEMEM*EME")
    assert "dtype" not in c["algo_config"]["model"]  # the shipped bfloat16
    assert t["env_config"]["vocab_size"] == c["vocab_size"] == 16384 == 131072 // 8
    assert t["env_config"]["episode_length"] == c["max_position_embeddings"] == 2048
    assert t["rollout_fragment_length"] == c["algo_config"]["model"]["max_seq_len"]
    # a fragment is TWO published chunks
    assert t["rollout_fragment_length"] == 2 * c["chunk_size"] == 256
    assert t["num_envs_per_worker"] == 32
    assert (t["num_envs_per_worker"] * t["rollout_fragment_length"]
            == t["train_batch_size"] == t["sgd_minibatch_size"] == 8192)
    # depths cover the episode: 32 streams, 64 tokens apart
    assert t["env_config"]["phase_stride"] * t["num_envs_per_worker"] == 2048
    assert c["experts_held"] == [0, 8] and c["n_routed_experts"] == 8
    assert c["router_outputs"] == c["published"]["n_routed_experts"] == 128
    assert set(c["reduced"]) == set(c["published"]) - {"deployment"}
    assert set(c["reduced"]) == set(c["reduced_why"]) == set(cell.config_entry["reduced"])
    assert cell.config_entry["source"] == c["source"]
    assert cell.chips == 1 and "32 streams x 256" in cell.why
    # the traffic mix is ``fused_tokens_v16384`` but for its geometry
    base = manifest_lib._load_json(
        os.path.join(manifest_lib.PERF_DIR, "traffic", "fused_tokens_v16384.json"))
    base["name"], base["what"] = cell.traffic["name"], cell.traffic["what"]
    base["algo_config"].update(
        rollout_fragment_length=256, train_batch_size=8192, sgd_minibatch_size=8192)
    assert base == cell.traffic
    # every number of the catalogue's entry but the reduced keys: no
    # width differs from the source
    for key, value in {
        "hidden_size": 2688, "intermediate_size": 1856, "head_dim": 128,
        "num_attention_heads": 32, "num_key_value_heads": 2,
        "mamba_num_heads": 64, "mamba_head_dim": 64, "ssm_state_size": 128,
        "n_groups": 8, "conv_kernel": 4, "chunk_size": 128, "expand": 2,
        "use_conv_bias": True, "mamba_proj_bias": False, "mamba_hidden_act": "silu",
        "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
        "n_shared_experts": 1, "num_experts_per_tok": 6, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "n_group": 1, "topk_group": 1,
        "mlp_hidden_act": "relu2", "mlp_bias": False, "attention_bias": False,
        "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5, "rope_theta": 10000,
        "partial_rotary_factor": 1, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
        "model_type": "nemotron_h",
    }.items():
        assert c[key] == value, key


def test_parameter_and_byte_arithmetic_at_the_published_widths():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    v = c["vocab_size"]
    p = ssm_moe_model.layer_param_counts(c, v)
    assert p["ssm_products"] == 27_697_152 + 11_010_048
    assert p["ssm_others"] == 30_720 + 192 + 4_096
    assert p["router"] == 344_064 + 128 and p["shared"] == 19_955_712
    assert p["one_expert"] == 9_977_856 and p["held"] == 8
    assert p["attention_products"] + p["norm"] == 23_399_040
    assert p["embedding"] + p["head"] == 88_080_384
    assert p["value_and_final_norm"] == 5_377
    assert ssm_moe_model.param_count(c, v) == 666_966_145  # x 16 B = 10.67e9
    # the policy's own shapes add up to the same count
    from ray_tpu.models.sequence_lm import SequenceLM

    shapes = SequenceLM(v, c["algo_config"]["model"]["sequence_lm"]).param_shapes()
    assert sorted(shapes) == [
        "embed", "final_norm", "head", "layer_1", "layer_3", "layer_5", "layer_6",
        "layer_8", "layers_0_0", "layers_2_2", "layers_4_4", "layers_7_7", "value"]
    count = lambda g: sum(int(np.prod(s)) for s in shapes[g].values())
    assert count("layers_0_0") == 38_744_896 and count("layer_1") == 100_125_440
    assert count("layer_5") == 23_399_040
    assert sum(count(g) for g in shapes) == 666_966_145
    # the reference's own shapes too
    assert cell.reference().param_shapes(c, v) == shapes
    # the same sum over all 52 blocks, 128 experts and the whole
    # vocabulary is the published size
    full = dict(c, num_hidden_layers=52, experts_held=[0, 128])
    assert round(ssm_moe_model.param_count(full, 131072) / 1e9, 2) == 31.58
    # 13 blocks and 16 held experts, the two cuts that do not fit
    assert round(16 * ssm_moe_model.param_count(
        dict(c, num_hidden_layers=13), v) / 1e9, 2) == 13.89
    assert round(16 * ssm_moe_model.param_count(
        dict(c, experts_held=[0, 16]), v) / 1e9, 2) == 15.78
    # a decode step of 32 streams: product weights at 2 bytes (half of
    # them the 4 x 8 held experts' two matrices), four matrices and tails
    # in and out, half an episode of keys and values
    products = ssm_moe_model.product_weight_count(c, v)
    assert round(2 * products / 1e9, 2) == 1.24
    assert round(2 * 4 * 8 * p["one_expert"] / 1e9, 2) == 0.64
    s = ssm_moe_model.state_bytes(c)
    assert s["ssm_layer"] == 4 * (64 * 64 * 128 + 3 * 6144) == 2_170_880
    state = 4 * 32 * 2 * s["ssm_layer"]
    assert round(state / 1e9, 2) == 0.56
    cache = 32 * 1024 * 1025
    need = ssm_moe_model.decode_step_bytes(c, v, 32)
    assert abs(need - (2 * products + state + cache)) < 0.01e9
    assert round(need / 1e9, 2) == 1.84
    # one call of the step kernel: 8 bytes an element of 32 streams'
    # matrices, and the rows
    call = ssm_moe_model.ssm_step_bytes(c, 32)
    assert call == 32 * (8 * 64 * 64 * 128 + 4 * (2 * 4096 + 64 + 2 * 8 * 128))
    assert round(call / 1e6, 1) == 135.5


def test_flop_rule_counts_rollout_and_update():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    rule = cell._module("flop_rules", "nemotron_h_ppo")
    fwd = rule.forward_flops_per_token(c, c["vocab_size"])
    assert rule.train_flops_per_env_step(c, c["vocab_size"]) == 4 * fwd
    assert cell.flop_rule()(c, c["vocab_size"]) == 4 * fwd
    # below the products of every HELD expert (a token pays 0.375 of
    # one), above the mixers', the shared experts' and the head's alone
    p = ssm_moe_model.layer_param_counts(c, c["vocab_size"])
    held = 2 * ssm_moe_model.product_weight_count(c, c["vocab_size"])
    assert held - 2 * 4 * 8 * p["one_expert"] < fwd < held
    assert round(8192 * 4 * fwd / 1e12, 1) == 21.8  # an iteration's TFLOP


def test_the_limits_lie_between_their_two_readings():
    cell = manifest_lib.load_cell(CELL)
    limits = cell.limits.entries
    assert "PLACEHOLDER" not in manifest_lib._load_json(cell.limits.path)["read"]
    separating = 0
    for name, entry in limits.items():
        assert "PLACEHOLDER" not in entry["read"], name
        assert entry["sound_max"] <= entry["limit"], name
        if entry["separates"]:
            separating += 1
            assert entry["sound_max"] < entry["limit"] < min(
                entry["control_min"].values()), name
    assert separating >= 5
    for check in ("fused_dispatch", "token_streams_at_phase", "rollout_fragment"):
        for name in cell._module("checks", check).LIMITS:
            assert name in limits, name


def test_the_cell_runs_end_to_end_at_a_small_size(small_root):
    from ray_tpu.telemetry import metrics

    cell = manifest_lib.load_cell(CELL, small_root)
    before = metrics.ssm_step_lowerings().get("xla", 0)
    out = run_lib.run_cell(cell, 2**31 + 5, 1.0, False, require_tpu=False)
    by_name = {r["check"]: r for r in out["checks"]}
    assert out["correct"], [r for r in out["checks"] if not r["ok"]]
    assert out["failed"] == 0 and out["attempted"] >= 1
    for name in ("streams_off_phase", "grad_rel_l2", "grad_leaf_rel_l2_max",
                 "loss_rel", "update_rel_l2", "adam_step_rel_l2",
                 "dispatch_rows_wrong",
                 "rollout_logit_rel_l2", "rollout_value_rel_l2",
                 "rollout_state_rel_l2", "route_top_k_mismatch_share",
                 "forms_logit_rel_l2", "rollout_advantage_rel_l2",
                 "iteration_adds_up", "dispatch_program_traced_once",
                 "env_carry_split_over_every_chip",
                 "params_replicated_on_every_chip"):
        assert by_name[name]["ok"], by_name[name]
    assert by_name["rollout_positions_wrong"]["value"] == 0
    assert "depths 0-28, 8 distinct" in by_name["streams_off_phase"]["note"]
    # float32 on the CPU takes the reference's gradient, and Adam's step
    # on it is the program's
    assert by_name["grad_rel_l2"]["value"] < 2e-3
    assert by_name["adam_step_rel_l2"]["value"] < 1e-3
    assert by_name["forms_logit_rel_l2"]["value"] < 1e-4
    assert by_name["rollout_logit_rel_l2"]["value"] < 1e-3
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    # the rollout's programs traced the one-token step, once a block
    assert metrics.ssm_step_lowerings().get("xla", 0) >= before + 4
    # a reader of the device trace finds nothing without one and says so
    ctx = run_lib.Context(cell, None, None, 1, "cpu", 64)
    for name in ("rollout.decode_device_ms_per_step",
                 "rollout.ssm_moe_decode_hbm_roofline_pct",
                 "ssm.step_hbm_roofline_pct",
                 "moe.decode_scope_device_ms_per_step",
                 "ssm.scope_device_ms_per_update",
                 "ssm.decode_scope_device_ms_per_step",
                 "moe.scope_device_ms_per_update",
                 "attn.scope_device_ms_per_update",
                 "learner.scope_device_ms_per_update"):
        assert cell.reader(name)(ctx) is None, name
    # the expert blocks fed the program's counters
    assert cell.reader("moe.max_expert_load_ratio")(ctx) >= 1.0
    assert 0.0 < cell.reader("moe.decode_held_experts_touched_share")(ctx) <= 100.0
    taken = {m["name"] for m in cell.per_layer}
    assert set(cell.chosen_metrics) <= taken
    assert not {"rollout.decode_hbm_roofline_pct", "rollout.ssm_decode_hbm_roofline_pct",
                "ssm.state_bytes_per_stream", "mla.scope_device_ms_per_update",
                "linear_attn.scope_device_ms_per_update"} & taken
    assert {"rollout.ssm_moe_decode_hbm_roofline_pct", "ssm.step_hbm_roofline_pct",
            "moe.decode_scope_device_ms_per_step", "ssm.scope_device_ms_per_update",
            "moe.scope_device_ms_per_update", "learner.mfu_pct"} <= taken


def test_controls_come_out_worse_than_the_system(small_root):
    """The reference with int8 and float8 operands in the system's
    place reads further from the float32 reference than the system
    (float32 on the CPU) on every number that is a precision's."""
    from perf import control

    cell = manifest_lib.load_cell(CELL, small_root)
    (row,) = control.readings(cell, [2**31 + 11], require_tpu=False)
    for name in ("grad_rel_l2", "rollout_logit_rel_l2", "rollout_value_rel_l2",
                 "rollout_state_rel_l2"):
        for precision in ("int8", "fp8"):
            assert row[precision][name] > 10 * row["system"][name], (name, row)


def test_the_new_readers_match_their_scopes_in_order():
    """A run of one layer is still a scan: the loop's frames stand
    between the lane's ``rollout/act`` and the model's scopes on an
    operation's path. The learn program's scopes are not the rollout's;
    the step's reader takes ``ssm/step`` and not the mixer's other
    parts, the experts' reader every ``moe`` scope and no mixer's."""
    cell = manifest_lib.load_cell(CELL)
    step = cell._module("layer_metrics", "ssm.step_hbm_roofline_pct").seconds
    moe = cell._module("layer_metrics", "moe.decode_scope_device_ms_per_step").seconds
    act = "jit(rollout_superstep)/while/body/closed_call/rollout/act/"
    ops = [
        [act + "while/body/closed_call/ssm/step/ssd_step", 0, 1000],
        [act + "while/body/closed_call/ssm/in/dot_general", 1000, 500],
        [act + "moe/experts/dot_general", 2000, 400],
        [act + "moe/route/top_k", 2500, 100],
        ["jit(rollout_superstep)/sgd_nest/while/body/learn/loss_grad/learn/ssm/step/mul",
         3000, 700],
        ["jit(rollout_superstep)/sgd_nest/while/body/learn/loss_grad/learn/moe/experts/dot",
         4000, 700],
        [act + "attn/dot_general", 5000, 300],
    ]
    rep = lambda ops: types.SimpleNamespace(
        op_scopes=ops, trace=types.SimpleNamespace(bounds=None))
    assert step(rep(ops)) == 1000 / 1e9
    assert moe(rep(ops)) == 500 / 1e9
    assert step(rep(ops[1:])) is None and moe(rep(ops[:2])) is None
    assert step(None) is None and moe(None) is None
    # on a configuration of another family the two shares say nothing
    other = manifest_lib.load_cell("granite4h_ppo.fused_tokens.1chip")
    ctx = types.SimpleNamespace(cell=other)
    for name in ("rollout.ssm_moe_decode_hbm_roofline_pct", "ssm.step_hbm_roofline_pct"):
        assert cell.reader(name)(ctx) is None
