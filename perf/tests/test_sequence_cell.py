"""The sequence-model cell's files, rehearsed on the CPU at a small
size: the committed configuration, traffic mix, reference, checks,
FLOP rule and readers of ``qwen3next_ppo.fused_tokens.1chip`` with
only the sizes rewritten (hidden 64, 8 router outputs of which 2 held,
a vocabulary of 64, 8 streams x 16 tokens)."""

import os

import pytest

from perf import manifest as manifest_lib
from perf import run as run_lib
from perf import sequence_model
from perf.tests.conftest import _rewrite

CELL = "qwen3next_ppo.fused_tokens.1chip"
SMALL = {
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 8, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 2,
    "router_outputs": 8, "experts_held": [0, 2], "num_experts_per_tok": 3,
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

    _rewrite(os.path.join(perf, "configs", "qwen3_next_80b_a3b_ppo.json"), shrink_config)
    _rewrite(os.path.join(perf, "traffic", "fused_tokens.json"), shrink_traffic)
    _rewrite(os.path.join(perf, "limits", "qwen3_next_80b_a3b_ppo.json"), loosen)
    return tiny_root


def test_the_committed_files_agree_with_each_other():
    cell = manifest_lib.load_cell(CELL)
    c, t = cell.config, cell.traffic["algo_config"]
    lm = c["algo_config"]["model"]["sequence_lm"]
    for key, value in lm.items():
        assert c[key] == value, key  # one architecture, stated twice
    assert "dtype" not in c["algo_config"]["model"]  # the shipped bfloat16
    assert t["env_config"]["vocab_size"] == c["vocab_size"]
    assert t["env_config"]["episode_length"] == c["max_position_embeddings"]
    assert t["rollout_fragment_length"] == c["algo_config"]["model"]["max_seq_len"]
    assert (t["num_envs_per_worker"] * t["rollout_fragment_length"]
            == t["train_batch_size"] == t["sgd_minibatch_size"] == 8192)
    assert c["experts_held"] == [0, c["num_experts"]]
    assert c["published"]["num_experts"] == c["router_outputs"] == 512
    assert set(c["reduced"]) == set(c["published"]) - {"deployment"}


def test_parameter_and_byte_arithmetic_at_the_published_widths():
    c = manifest_lib.load_cell(CELL).config
    p = sequence_model.layer_param_counts(c, c["vocab_size"])
    assert round(p["linear_mixer"] / 1e6, 2) == 33.72
    assert round(p["full_mixer"] / 1e6, 2) == 27.26
    assert round(p["router_and_shared"] / 1e6, 2) == 4.20
    assert p["one_expert"] == 3 * 2048 * 512
    total = sequence_model.param_count(c, c["vocab_size"])
    assert round(total / 1e6, 1) == 625.7  # x 16 B = 10.01 GB
    # a decode step of 64 streams: the products' weights at 2 bytes
    # (all but 5 M of the parameters outside the embedding), the
    # DeltaNet state read and written, half an episode of cache
    in_products = sequence_model.product_weight_count(c, c["vocab_size"])
    assert 0.99 < in_products / (total - p["embedding"]) < 1.0
    need = sequence_model.decode_step_bytes(c, c["vocab_size"], 64)
    state = 2 * 4 * 64 * 3 * (32 * 128 * 128 + 3 * 8192)
    cache = 2 * 64 * 2 * 2 * 256 * 1025
    assert abs(need - (2 * in_products + state + cache)) < 0.03e9


def test_flop_rule_counts_rollout_and_update():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    rule = cell._module("flop_rules", "qwen3_next_ppo")
    fwd = rule.forward_flops_per_token(c, c["vocab_size"])
    assert rule.train_flops_per_env_step(c, c["vocab_size"]) == 4 * fwd
    p = sequence_model.layer_param_counts(c, c["vocab_size"])
    # the dense parts alone: three DeltaNet mixers, one attention
    # mixer, four routers + shared experts, the head
    floor = 2 * (3 * p["linear_mixer"] + p["full_mixer"]
                 + 4 * p["router_and_shared"] + p["head"])
    assert floor < fwd < 1.3 * floor


def test_the_cell_runs_end_to_end_at_a_small_size(small_root):
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
    # set-up brought every stream's model state to its place in the
    # episode: 8 streams, 4 tokens apart, as deep as their env
    assert "depths 0-28, 8 distinct" in by_name["streams_off_phase"]["note"]
    # the real dispatch: float32 on the CPU takes the reference's
    # gradient, and Adam's step on it is the program's to rounding
    assert by_name["grad_rel_l2"]["value"] < 1e-3
    assert by_name["adam_step_rel_l2"]["value"] < 1e-3
    assert "one Algorithm.train()" in by_name["loss_rel"]["note"]
    # float32 on the CPU: the forms agree and routing is the reference's
    assert by_name["forms_logit_rel_l2"]["value"] < 1e-4
    assert by_name["route_top_k_mismatch_share"]["value"] < 0.02
    assert by_name["rollout_logit_rel_l2"]["value"] < 1e-3
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    # the counter-fed reader reads the program's own routing; a reader
    # of the device trace finds nothing without one and says so
    ctx = run_lib.Context(cell, None, None, 1, "cpu", 64)
    assert cell.reader("moe.max_expert_load_ratio")(ctx) >= 1.0
    for name in ("rollout.decode_device_ms_per_step",
                 "rollout.decode_hbm_roofline_pct",
                 "moe.scope_device_ms_per_update",
                 "linear_attn.scope_device_ms_per_update",
                 "attn.scope_device_ms_per_update",
                 "learner.scope_device_ms_per_update",
                 "learner.host_idle_ms_per_iter",
                 "device.unscoped_device_ms_per_iter",
                 "entry.unattributed_idle_pct"):
        assert cell.reader(name)(ctx) is None, name
    # the cell takes the restricted readers of another cell through
    # its own perf/cells file, not through entries of its own
    taken = {m["name"] for m in cell.per_layer}
    assert set(cell.chosen_metrics) <= taken
    assert not any(m.startswith("learner.tokens_") for m in taken)


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
