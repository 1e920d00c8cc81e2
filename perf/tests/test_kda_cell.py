"""The Kimi-Delta-Attention cell's files, rehearsed on the CPU at a small
size: the committed configuration, traffic mix, reference, checks, FLOP
rule and readers of ``ling3flash_ppo.fused_tokens.1chip`` with only the
sizes rewritten (hidden 32, 4 heads of 16, latent 24 with head parts 16 /
8 / 16, 16 router outputs in 4 groups of which 2 are kept and 4 experts
of one group held, a vocabulary of 64, 8 streams x 16 tokens)."""

import os

import numpy as np
import pytest

from perf import kda_latent_model as m
from perf import manifest as manifest_lib
from perf import run as run_lib
from perf.tests.conftest import _rewrite

CELL = "ling3flash_ppo.fused_tokens.1chip"
CONFIG = "ling_3_0_flash_125b_a5b_ppo"
TRAFFIC = "fused_tokens_v19648_e4096"
SMALL = {
    "hidden_size": 32, "num_attention_heads": 4, "head_dim": 16,
    "kv_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "intermediate_size": 48, "moe_intermediate_size": 16,
    "moe_shared_expert_intermediate_size": 16, "num_experts": 4,
    "router_outputs": 16, "experts_held": [0, 4], "n_group": 4, "topk_group": 2,
    "num_experts_per_tok": 3, "max_position_embeddings": 32, "vocab_size": 64,
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
    import json

    cell = manifest_lib.load_cell(CELL)
    c, t = cell.config, cell.traffic["algo_config"]
    lm = c["algo_config"]["model"]["sequence_lm"]
    for key, value in lm.items():
        assert c[key] == value, key  # one architecture, stated twice
    assert "dtype" not in c["algo_config"]["model"]  # the shipped bfloat16
    assert t["env_config"]["vocab_size"] == c["vocab_size"] == 19648 == 157184 // 8
    assert t["env_config"]["episode_length"] == c["max_position_embeddings"] == 4096
    assert t["rollout_fragment_length"] == c["algo_config"]["model"]["max_seq_len"]
    assert (t["num_envs_per_worker"] * t["rollout_fragment_length"]
            == t["train_batch_size"] == t["sgd_minibatch_size"] == 4096)
    assert c["experts_held"] == [0, c["num_experts"]] == [0, 8]
    assert c["published"]["num_experts"] == c["router_outputs"] == 512
    # the held eight are an eighth of ONE of the router's 8 groups of 64
    assert c["router_outputs"] // c["n_group"] == 64 and c["topk_group"] == 4
    assert c["layer_indices"] == [0, 2, 3, 4, 5, 6, 7]
    assert len(c["layer_indices"]) == c["num_hidden_layers"]
    assert c["published_num_hidden_layers"] == c["published"]["num_hidden_layers"] == 42
    assert set(c["reduced"]) == set(c["published"]) - {"deployment"}
    assert set(c["reduced"]) == set(c["reduced_why"]) == set(cell.config_entry["reduced"])
    assert "64 chips" in c["published"]["deployment"]
    # the traffic mix is the Laguna cell's at this vocabulary
    with open(os.path.join(manifest_lib.PERF_DIR, "traffic",
                           "fused_tokens_v12544_e4096.json")) as f:
        base = json.load(f)
    base["name"], base["what"] = cell.traffic["name"], cell.traffic["what"]
    base["algo_config"]["env_config"]["vocab_size"] = 19648
    assert base == cell.traffic
    assert "1/64" in cell.why and "mixers" in cell.why
    # every number of the catalogue's entry but the reduced keys: no
    # width is cut
    for key, value in {
        "hidden_size": 2560, "num_attention_heads": 32, "num_key_value_heads": 32,
        "head_dim": 128, "intermediate_size": 6144, "moe_intermediate_size": 768,
        "moe_shared_expert_intermediate_size": 768, "num_shared_experts": 1,
        "num_experts_per_tok": 8, "n_group": 8, "topk_group": 4,
        "routed_scaling_factor": 2.5, "kv_lora_rank": 512, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "qk_head_dim": 192,
        "v_head_dim": 128, "rope_theta": 6000000, "rope_scaling": None,
        "rope_interleave": True, "partial_rotary_factor": 0.5, "rotary_dim": 64,
        "layer_group_size": 6, "short_conv_kernel_size": 4, "kda_lower_bound": -5,
        "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
        "gated_attention_proj_granularity_type": "head_wise", "group_norm_size": 1,
        "num_kv_heads_for_linear_attn": 0, "linear_silu": True, "use_qk_norm": True,
        "value_norm": False, "score_function": "sigmoid", "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "moe_router_enable_expert_bias": True,
        "norm_topk_prob": True, "num_nextn_predict_layers": 1, "max_window_layers": 20,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "use_bias": False,
        "use_qkv_bias": False, "model_type": "bailing_hybrid",
    }.items():
        assert c[key] == value, key
    # the clamps are 0 in every published layer held
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert len(c[key]) == 42 and not any(c[key][i] for i in c["layer_indices"])


def test_parameter_and_byte_arithmetic_at_the_published_widths():
    c = manifest_lib.load_cell(CELL).config
    v = c["vocab_size"]
    p = m.layer_param_counts(c, v)
    total = lambda part: sum(p[part].values())
    assert total(m.KDA) == 52_646_048 and total(m.LATENT) == 31_965_696
    assert total("dense") == 47_185_920 and total("experts") == 54_395_392
    assert p["experts"]["others"] == 1_311_232  # the router and its bias
    assert m.kinds(c) == [m.KDA] * 4 + [m.LATENT] + [m.KDA] * 2
    assert m.param_count(c, v) == 822_038_977  # x 16 B = 13.15e9
    # the policy's own shapes add up to the same count
    from ray_tpu.models.sequence_lm import SequenceLM

    shapes = SequenceLM(v, c["algo_config"]["model"]["sequence_lm"]).param_shapes()
    assert sum(int(np.prod(s)) for g in shapes.values() for s in g.values()) == 822_038_977
    # the same sum over the 42 published layers (35 KDA to 7 latent), 512
    # experts and the whole vocabulary is the published "~125B"
    assert m.kinds(c, range(42)).count(m.KDA) == 35
    assert round(m.published_param_count(c) / 1e9, 2) == 124.05
    # a stream: six matrices with their tails, one layer's latent rows
    state = m.state_bytes(c)
    assert state == {"kda": 6 * (2_097_152 + 3 * 3 * 4096 * 4), "latent": 4096 * 1152}
    assert sum(state.values()) == 18_186_240
    # a decode step of 16 streams: product weights at 2 bytes (every held
    # expert's), six matrices in and out, half an episode of latent rows
    in_products = m.product_weight_count(c, v)
    need = m.decode_step_bytes(c, v, 16)
    matrices = 16 * 6 * 2 * (2_097_152 + 147_456)
    assert round(2 * in_products / 1e9, 2) == 1.53 and round(matrices / 1e9, 2) == 0.43
    assert abs(need - (2 * in_products + matrices)) < 0.08e9
    assert round(need / 1e9, 2) == 2.03
    # one call of the step kernel: the matrices in and out and the rows
    assert m.kda_step_bytes(c, 16) == 16 * (8 * 32 * 128 * 128 + 4 * (5 * 4096 + 32))


def test_carried_state_bytes_read_the_leaves():
    import jax.numpy as jnp

    kda = [jnp.zeros((2, 32, 128, 128), jnp.float32)] + [
        jnp.zeros((2, 3, 4096), jnp.float32)] * 3
    latent = [jnp.zeros((2, 4096, 576), jnp.bfloat16)]
    state = kda * 4 + latent + kda * 2 + [jnp.zeros((2,), jnp.int32)]
    assert m.carried_kda_bytes_per_stream(state) == 13_467_648
    assert m.carried_kda_bytes_per_stream(latent + state[-1:]) is None


def test_flop_rule_counts_rollout_and_update():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    rule = cell._module("flop_rules", "ling3_ppo")
    fwd = rule.forward_flops_per_token(c, c["vocab_size"])
    assert rule.train_flops_per_env_step(c, c["vocab_size"]) == 4 * fwd
    p = m.layer_param_counts(c, c["vocab_size"])
    # the dense parts alone: six KDA mixers and the latent one, the dense
    # layer, six shared experts, the head
    floor = 2 * (6 * p[m.KDA]["products"] + p[m.LATENT]["products"]
                 + p["dense"]["products"] + 6 * 3 * 2560 * 768 + p["ends"]["products"])
    assert floor < fwd < 1.25 * floor
    # the delta rule as the recurrence does it, not the chunk solve
    assert fwd - floor > 6 * 7 * 32 * 128 * 128


def test_the_cell_runs_end_to_end_at_a_small_size(small_root):
    from ray_tpu.telemetry import metrics

    cell = manifest_lib.load_cell(CELL, small_root)
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
                 "kda_rule_step_state_rel_l2", "kda_rule_chunk_state_rel_l2",
                 "kda_rule_step_out_rel_l2", "kda_rule_chunk_out_rel_l2",
                 "iteration_adds_up", "dispatch_program_traced_once",
                 "env_carry_split_over_every_chip",
                 "params_replicated_on_every_chip"):
        assert by_name[name]["ok"], by_name[name]
    assert by_name["rollout_positions_wrong"]["value"] == 0
    assert "depths 0-28, 8 distinct" in by_name["streams_off_phase"]["note"]
    # float32 on the CPU takes the reference's gradient: the chunked
    # per-channel rule against the token-by-token recurrence
    assert by_name["grad_rel_l2"]["value"] < 1e-3
    assert by_name["adam_step_rel_l2"]["value"] < 1e-3
    assert by_name["forms_logit_rel_l2"]["value"] < 1e-4
    assert by_name["route_top_k_mismatch_share"]["value"] < 0.02
    assert by_name["rollout_logit_rel_l2"]["value"] < 1e-3
    # the rule alone, both forms in float32 against the float64 chain on
    # the layer's own operands: rounding, whatever the limits file says
    for name in by_name:
        if name.startswith("kda_rule_"):
            assert 0 < by_name[name]["value"] < 2e-5, by_name[name]
    assert "4 streams x 16 tokens" in by_name["kda_rule_step_state_rel_l2"]["note"]
    assert set(out["metrics"]) == {m_["name"] for m_ in cell.end_to_end}
    # every traced one-token rule was the per-channel one
    assert metrics.get_metric(metrics.DELTANET_STEP_LOWERINGS_TOTAL) is not None
    decays = metrics._totals_by_tag(metrics.DELTANET_STEP_LOWERINGS_TOTAL, "decay")
    assert decays.get("channel", 0) > 0 and not decays.get("head")
    # the counter-fed readers read the program's own routing; a reader of
    # the device trace finds nothing without one and says so
    ctx = run_lib.Context(cell, None, None, 1, "cpu", 64)
    assert cell.reader("moe.max_expert_load_ratio")(ctx) >= 1.0
    # 4 groups of which 2 are kept, the held experts in one: near a half
    assert 25.0 < cell.reader("moe.held_group_chosen_share")(ctx) < 75.0
    for name in ("rollout.decode_device_ms_per_step",
                 "rollout.kda_decode_hbm_roofline_pct",
                 "kda.scope_device_ms_per_update",
                 "kda.decode_scope_device_ms_per_step",
                 "kda.step_hbm_roofline_pct", "kda.state_bytes_per_stream",
                 "mla.scope_device_ms_per_update",
                 "moe.scope_device_ms_per_update",
                 "learner.scope_device_ms_per_update"):
        assert cell.reader(name)(ctx) is None, name
    taken = {m_["name"] for m_ in cell.per_layer}
    assert set(cell.chosen_metrics) <= taken
    assert not {"rollout.decode_hbm_roofline_pct", "rollout.latent_decode_hbm_roofline_pct",
                "linear_attn.scope_device_ms_per_update",
                "hc.scope_device_ms_per_update"} & taken
    assert {"kda.scope_device_ms_per_update", "kda.decode_scope_device_ms_per_step",
            "kda.step_hbm_roofline_pct", "kda.state_bytes_per_stream",
            "rollout.kda_decode_hbm_roofline_pct", "moe.held_group_chosen_share",
            "mla.scope_device_ms_per_update"} <= taken


def test_the_new_readers_find_nothing_in_another_cells_program():
    """Laid over a program without the scopes, the counter or the
    configuration (the parent's, or another cell's), every reader this
    cell brings returns nothing and does not raise."""
    other = manifest_lib.load_cell("xing4_ppo.fused_tokens.1chip")
    ctx = run_lib.Context(other, None, None, 1, "cpu", 64)
    cell = manifest_lib.load_cell(CELL)
    for name in ("kda.scope_device_ms_per_update", "kda.decode_scope_device_ms_per_step",
                 "kda.step_hbm_roofline_pct", "kda.state_bytes_per_stream",
                 "rollout.kda_decode_hbm_roofline_pct"):
        assert cell.reader(name)(ctx) is None, name


def test_controls_come_out_worse_than_the_system(small_root):
    """The reference with int8 and float8 operands in the system's
    place reads further from the float32 reference than the system
    (float32 on the CPU) on every number that is a precision's; a
    bfloat16 KDA matrix, the third control, shows on the rule alone."""
    from perf import control

    cell = manifest_lib.load_cell(CELL, small_root)
    assert cell.control_precisions == ("int8", "fp8", "bf16_state")
    (row,) = control.readings(cell, [2**31 + 11], require_tpu=False)
    for name in ("grad_rel_l2", "rollout_logit_rel_l2", "rollout_value_rel_l2",
                 "rollout_state_rel_l2"):
        for precision in ("int8", "fp8"):
            assert row[precision][name] > 10 * row["system"][name], (name, row)
    rule = cell._module("checks", "kda_rule")
    for name in rule.LIMITS:
        for precision in cell.control_precisions:
            assert row[precision][name] > 100 * row["system"][name], (name, row)
        assert row["bf16_state"][name] > 5e-4, (name, row)


def test_the_step_roofline_counts_the_kernels_it_times():
    """The step kernel's custom-call events inside the span by name,
    whatever path they carry, with their own count as the calls: another
    operation of the same scope, or a call outside the span, is neither
    timed nor counted."""
    from perf import program_trace
    from perf import trace_reduce as tr

    reader = manifest_lib.load_cell(CELL)._module(
        "layer_metrics", "kda.step_hbm_roofline_pct")
    kernel = "%gated_delta_step.7 custom-call f32[16,32,128,128]"
    ops = [
        ["a/rollout/act/while/body/kda/rule/x", 1_000.0, 100.0, kernel],
        ["a/rollout/postprocess/gae/rollout/act/kda/rule/x", 2_000.0, 50.0,
         kernel.replace(".7", ".9")],  # the tail forward's
        ["a/rollout/act/while/body/kda/rule/x", 3_000.0, 50.0,
         "%fusion.1 fusion f32[16,32,128]"],  # an L2 norm beside it
        ["a/rollout/act/while/body/kda/rule/x", 9_000.0, 100.0, kernel],  # outside
    ]
    plain = {"planes": [{"name": tr.DEVICE_PLANE_PREFIX + "0", "lines": [
        {"name": tr.OPS_LINE, "events": [[i, o[1], o[2]] for i, o in enumerate(ops)]}]}]}
    report = lambda some: program_trace.Report(
        tr.Trace(plain, 1, (500, 5_000)), 1, 1.0, some)
    seconds, calls = reader.kernel_seconds_and_calls(report(ops))
    assert calls == 2 and abs(seconds - 150e-9) < 1e-15
    assert reader.kernel_seconds_and_calls(report(ops[2:3])) is None
