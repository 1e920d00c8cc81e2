"""The latent-attention cell's files, rehearsed on the CPU at a small
size: the committed configuration, traffic mix, reference, checks, FLOP
rule and readers of ``xing4_ppo.fused_tokens.1chip`` with only the
sizes rewritten (hidden 32, unequal head parts 16 / 8 / 12 over a
latent of 24, 3 lanes, 8 router outputs of which 2 held, a vocabulary
of 64, 8 streams x 16 tokens)."""

import os

import numpy as np
import pytest

from perf import latent_model
from perf import manifest as manifest_lib
from perf import run as run_lib
from perf.tests.conftest import _rewrite

CELL = "xing4_ppo.fused_tokens.1chip"
CONFIG = "xing4_0_29b_a4b_ppo"
SMALL = {
    "hidden_size": 32, "num_hidden_layers": 3, "num_attention_heads": 4,
    "q_lora_rank": 20, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 12, "intermediate_size": 48,
    "moe_intermediate_size": 16, "n_routed_experts": 2, "router_outputs": 8,
    "experts_held": [0, 2], "num_experts_per_tok": 3, "hc_mult": 3,
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
    _rewrite(os.path.join(perf, "traffic", "fused_tokens_v16384.json"), shrink_traffic)
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
    assert t["env_config"]["vocab_size"] == c["vocab_size"] == 16384
    assert t["env_config"]["episode_length"] == c["max_position_embeddings"]
    assert t["rollout_fragment_length"] == c["algo_config"]["model"]["max_seq_len"]
    assert (t["num_envs_per_worker"] * t["rollout_fragment_length"]
            == t["train_batch_size"] == t["sgd_minibatch_size"] == 4096)
    assert c["experts_held"] == [0, c["n_routed_experts"]] == [0, 8]
    assert c["published"]["n_routed_experts"] == c["router_outputs"] == 64
    assert set(c["reduced"]) == set(c["published"]) - {"deployment"}
    assert set(c["reduced"]) == set(c["reduced_why"]) == set(cell.config_entry["reduced"])
    # the traffic mix is ``fused_tokens`` with the vocabulary held here,
    # at half its streams (and twice the stride between them): the one
    # departure, stated in ``what``
    with open(os.path.join(manifest_lib.PERF_DIR, "traffic", "fused_tokens.json")) as f:
        base = json.load(f)
    base["name"] = cell.traffic["name"]
    base["what"] = cell.traffic["what"]
    assert "32 streams" in base["what"] and "15.86 GiB" in base["what"]
    base["algo_config"]["env_config"].update(vocab_size=16384, phase_stride=64)
    base["algo_config"].update(
        num_envs_per_worker=32, train_batch_size=4096, sgd_minibatch_size=4096)
    assert base == cell.traffic
    assert "32 streams" in cell.why
    # every published number of the catalogue's entry, but the reduced
    # keys: widths among them
    for key, value in {
        "hidden_size": 3584, "num_attention_heads": 32, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "v_head_dim": 128, "intermediate_size": 9216, "moe_intermediate_size": 1024,
        "n_shared_experts": 1, "num_experts_per_tok": 4, "routed_scaling_factor": 2,
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30, "rope_theta": 10000,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "num_nextn_predict_layers": 1, "tie_word_embeddings": False,
    }.items():
        assert c[key] == value, key
    assert c["rope_scaling"]["factor"] == 64
    assert c["rope_scaling"]["original_max_position_embeddings"] == 4096


def test_parameter_and_byte_arithmetic_at_the_published_widths():
    c = manifest_lib.load_cell(CELL).config
    v = c["vocab_size"]
    p = latent_model.layer_param_counts(c, v)
    assert round((p["mixer_products"] + p["mixer_norms"]) / 1e6, 2) == 28.41
    assert round(p["hyper_connection"] / 1e6, 3) == 0.358
    assert p["one_expert"] == 3 * 3584 * 1024
    assert round(p["router"] / 1e6, 2) == 0.23
    assert latent_model.param_count(c, v) == 759_493_391  # 759.49 M x 16 B = 12.15e9
    # the policy's own shapes add up to the same count
    from ray_tpu.models.sequence_lm import SequenceLM

    shapes = SequenceLM(v, c["algo_config"]["model"]["sequence_lm"]).param_shapes()
    assert sum(int(np.prod(s)) for g in shapes.values() for s in g.values()) == 759_493_391
    # the same sum over 2 + 38 layers, 64 experts and the whole
    # vocabulary is the published size
    full = dict(c, num_hidden_layers=40, first_k_dense_replace=2,
                experts_held=[0, 64])
    assert round(latent_model.param_count(full, 131072) / 1e9, 1) == 29.5
    # a decode step of 64 streams: product weights at 2 bytes, half an
    # episode of latent rows
    in_products = latent_model.product_weight_count(c, v)
    assert round(2 * in_products / 1e9, 2) == 1.39
    need = latent_model.decode_step_bytes(c, v, 64)
    cache = 5 * 64 * 1152 * 1026
    assert round(cache / 1e9, 2) == 0.38
    assert abs(need - (2 * in_products + cache)) < 0.03e9


def test_cache_bytes_per_position_reads_the_leaves():
    import jax.numpy as jnp

    c = manifest_lib.load_cell(CELL).config
    latent = [jnp.zeros((2, 2048, 576), jnp.bfloat16)] * 5 + [jnp.zeros((2,), jnp.int32)]
    assert latent_model.cache_bytes_per_position(latent, c) == 1152
    expanded = [jnp.zeros((2, 2048, 32 * 320), jnp.bfloat16)] * 5
    assert latent_model.cache_bytes_per_position(expanded, c) == 20480
    assert latent_model.cache_bytes_per_position(latent[-1:], c) is None


def test_flop_rule_counts_rollout_and_update():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    rule = cell._module("flop_rules", "xing4_ppo")
    fwd = rule.forward_flops_per_token(c, c["vocab_size"])
    assert rule.train_flops_per_env_step(c, c["vocab_size"]) == 4 * fwd
    p = latent_model.layer_param_counts(c, c["vocab_size"])
    # the dense parts alone: five mixers, the dense layer, four routers
    # and shared experts, the head
    floor = 2 * (5 * p["mixer_products"] + p["dense_mlp"]
                 + 4 * (p["router"] + p["shared"]) + p["head"])
    assert floor < fwd < 1.5 * floor


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
                 "iteration_adds_up", "dispatch_program_traced_once",
                 "env_carry_split_over_every_chip",
                 "params_replicated_on_every_chip"):
        assert by_name[name]["ok"], by_name[name]
    assert by_name["rollout_positions_wrong"]["value"] == 0
    assert "depths 0-28, 8 distinct" in by_name["streams_off_phase"]["note"]
    # the real dispatch, grouped through the whole stack (8 streams in
    # groups of ``learn_streams``): float32 on the CPU takes the
    # reference's gradient, and Adam's step on it is the program's
    assert by_name["grad_rel_l2"]["value"] < 1e-3
    assert by_name["adam_step_rel_l2"]["value"] < 1e-3
    assert by_name["forms_logit_rel_l2"]["value"] < 1e-4
    assert by_name["route_top_k_mismatch_share"]["value"] < 0.02
    assert by_name["rollout_logit_rel_l2"]["value"] < 1e-3
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    # the rollout took the absorbed form, the learn program the expanded
    forms = metrics.mla_decode_lowerings()
    assert forms.get("absorbed", 0) > 0 and forms.get("expanded", 0) > 0
    # the counter-fed reader reads the program's own routing; a reader
    # of the device trace finds nothing without one and says so
    ctx = run_lib.Context(cell, None, None, 1, "cpu", 64)
    assert cell.reader("moe.max_expert_load_ratio")(ctx) >= 1.0
    for name in ("rollout.decode_device_ms_per_step",
                 "rollout.latent_decode_hbm_roofline_pct",
                 "mla.scope_device_ms_per_update",
                 "hc.scope_device_ms_per_update",
                 "mla.cache_bytes_per_position",
                 "moe.scope_device_ms_per_update",
                 "learner.scope_device_ms_per_update"):
        assert cell.reader(name)(ctx) is None, name
    taken = {m["name"] for m in cell.per_layer}
    assert set(cell.chosen_metrics) <= taken
    assert not {"rollout.decode_hbm_roofline_pct", "attn.scope_device_ms_per_update",
                "linear_attn.scope_device_ms_per_update"} & taken
    assert {"mla.scope_device_ms_per_update", "hc.scope_device_ms_per_update",
            "rollout.latent_decode_hbm_roofline_pct",
            "mla.cache_bytes_per_position"} <= taken


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
