"""The state-space cell's files, rehearsed on the CPU at a small size:
the committed configuration, traffic mix, reference, checks, FLOP rule
and readers of ``granite4h_ppo.fused_tokens.1chip`` with only the sizes
rewritten (hidden 32, four layers mamba x 2, attention, mamba: two runs
of unequal length; 8 state-space heads of 8 over a state of 16, chunks
of 8, a vocabulary of 64, 8 streams x 16 tokens)."""

import json
import os

import numpy as np
import pytest

from perf import manifest as manifest_lib
from perf import run as run_lib
from perf import ssm_model
from perf.tests.conftest import _rewrite

CELL = "granite4h_ppo.fused_tokens.1chip"
CONFIG = "granite_4_0_h_micro_ppo"
SMALL = {
    "hidden_size": 32, "num_hidden_layers": 4,
    "layer_types": ["mamba", "mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 8, "mamba_d_state": 16,
    "mamba_chunk_size": 8, "shared_intermediate_size": 48,
    "intermediate_size": 48, "max_position_embeddings": 32, "vocab_size": 64,
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
    _rewrite(os.path.join(perf, "traffic", "fused_tokens_v12544.json"), shrink_traffic)
    _rewrite(os.path.join(perf, "limits", CONFIG + ".json"), loosen)
    return tiny_root


def test_the_committed_files_agree_with_each_other():
    cell = manifest_lib.load_cell(CELL)
    c, t = cell.config, cell.traffic["algo_config"]
    lm = c["algo_config"]["model"]["sequence_lm"]
    for key, value in lm.items():
        if key == "layer_types":  # published whole; the first period is run
            assert c[key][: c["num_hidden_layers"]] == value
            assert len(c[key]) == c["published"]["num_hidden_layers"] == 40
        else:
            assert c[key] == value, key  # one architecture, stated twice
    assert lm["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert "dtype" not in c["algo_config"]["model"]  # the shipped bfloat16
    assert t["env_config"]["vocab_size"] == c["vocab_size"] == 12544 == 100352 // 8
    assert t["env_config"]["episode_length"] == c["max_position_embeddings"] == 2048
    assert t["rollout_fragment_length"] == c["algo_config"]["model"]["max_seq_len"]
    assert t["rollout_fragment_length"] == c["mamba_chunk_size"] == 256
    assert t["num_envs_per_worker"] == 16
    assert (t["num_envs_per_worker"] * t["rollout_fragment_length"]
            == t["train_batch_size"] == t["sgd_minibatch_size"] == 4096)
    # depths cover the episode: 16 streams, 128 tokens apart
    assert t["env_config"]["phase_stride"] * t["num_envs_per_worker"] == 2048
    assert set(c["reduced"]) == set(c["published"]) - {"deployment"}
    assert set(c["reduced"]) == set(c["reduced_why"]) == set(cell.config_entry["reduced"])
    assert cell.config_entry["source"] == c["source"]
    assert cell.chips == 1 and "16 streams x 256" in cell.why
    # the traffic mix is ``fused_tokens`` but for its geometry
    with open(os.path.join(manifest_lib.PERF_DIR, "traffic", "fused_tokens.json")) as f:
        base = json.load(f)
    base["name"], base["what"] = cell.traffic["name"], cell.traffic["what"]
    base["algo_config"]["env_config"].update(vocab_size=12544, phase_stride=128)
    base["algo_config"].update(
        num_envs_per_worker=16, rollout_fragment_length=256,
        train_batch_size=4096, sgd_minibatch_size=4096)
    assert base == cell.traffic
    # every number of the catalogue's entry but the reduced keys: no
    # width differs from the source
    for key, value in {
        "hidden_size": 2048, "intermediate_size": 8192, "shared_intermediate_size": 8192,
        "num_attention_heads": 32, "num_key_value_heads": 8,
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "residual_multiplier": 0.22, "logits_scaling": 8, "rms_norm_eps": 1e-5,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
        "num_local_experts": 0, "num_experts_per_tok": 0,
        "position_embedding_type": "nope", "tie_word_embeddings": True,
        "attention_bias": False, "rope_theta": 10000, "rope_scaling": None,
        "model_type": "granitemoehybrid",
    }.items():
        assert c[key] == value, key


def test_parameter_and_byte_arithmetic_at_the_published_widths():
    c = manifest_lib.load_cell(CELL).config
    v = c["vocab_size"]
    p = ssm_model.layer_param_counts(c, v)
    assert p["ssm_products"] + p["ssm_others"] == 25_847_232
    assert p["ssm_products"] == 17_432_576 + 8_388_608
    assert p["mlp"] == 50_331_648 and p["attention_products"] == 10_485_760
    assert p["table"] == 25_690_112 and p["value_and_final_norm"] == 4_097
    assert ssm_model.param_count(c, v) == 772_162_497  # x 16 B = 12.36e9
    # the policy's own shapes add up to the same count
    from ray_tpu.models.sequence_lm import SequenceLM

    shapes = SequenceLM(v, c["algo_config"]["model"]["sequence_lm"]).param_shapes()
    assert sorted(shapes) == [
        "embed", "final_norm", "layer_5", "layers_0_4", "layers_6_9", "value"]
    assert sum(int(np.prod(s)) for g in shapes.values() for s in g.values()) == 772_162_497
    # the reference's own shapes too
    ref = manifest_lib.load_cell(CELL).reference()
    assert ref.param_shapes(c, v) == shapes
    # the same sum over all 40 layers and the whole vocabulary is the
    # published size
    full = dict(c, num_hidden_layers=40)
    assert round(ssm_model.param_count(full, 100352) / 1e9, 2) == 3.19
    # a decode step of 16 streams: product weights at 2 bytes, nine
    # matrices and tails in and out, half an episode of keys and values
    assert round(2 * ssm_model.product_weight_count(c, v) / 1e9, 2) == 1.54
    s = ssm_model.state_bytes(c)
    assert s["ssm_layer"] == 4 * (64 * 64 * 128 + 3 * 4352)
    state = 9 * 16 * 2 * s["ssm_layer"]
    assert round(state / 1e9, 2) == 0.62
    cache = 16 * 2048 * 1025
    need = ssm_model.decode_step_bytes(c, v, 16)
    assert abs(need - (2 * ssm_model.product_weight_count(c, v) + state + cache)) < 0.01e9
    assert round(need / 1e9, 2) == 2.20


def test_state_bytes_per_stream_reads_the_leaves():
    import jax.numpy as jnp

    c = manifest_lib.load_cell(CELL).config
    from ray_tpu.models.sequence_lm import SequenceLM

    state = SequenceLM(
        c["vocab_size"], c["algo_config"]["model"]["sequence_lm"]).initial_state(2)
    s = ssm_model.state_bytes(c)
    want = 9 * s["ssm_layer"] + s["cache_layer"] + 4
    assert ssm_model.state_bytes_per_stream(state) == want == 23_538_692
    assert ssm_model.state_bytes_per_stream([]) is None


def test_flop_rule_counts_rollout_and_update():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    rule = cell._module("flop_rules", "granite4h_ppo")
    fwd = rule.forward_flops_per_token(c, c["vocab_size"])
    assert rule.train_flops_per_env_step(c, c["vocab_size"]) == 4 * fwd
    # the products alone, and not much over them: the recurrence is
    # bytes, not operations
    floor = 2 * ssm_model.product_weight_count(c, c["vocab_size"])
    assert floor < fwd < 1.05 * floor


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
    # on it is the program's; there is no router to disagree about
    assert by_name["grad_rel_l2"]["value"] < 1e-3
    assert by_name["adam_step_rel_l2"]["value"] < 1e-3
    assert by_name["forms_logit_rel_l2"]["value"] < 1e-4
    assert by_name["route_top_k_mismatch_share"]["value"] == 0
    assert by_name["rollout_logit_rel_l2"]["value"] < 1e-3
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    # the rollout's programs traced the one-token step, once a run
    assert metrics.ssm_step_lowerings().get("xla", 0) > before
    # no expert layer: nothing fed the expert-load counters' reader,
    # the state's reader reads the live carry, and a reader of the
    # device trace finds nothing without one and says so
    ctx = run_lib.Context(cell, None, None, 1, "cpu", 64)
    for name in ("rollout.decode_device_ms_per_step",
                 "rollout.ssm_decode_hbm_roofline_pct",
                 "ssm.scope_device_ms_per_update",
                 "ssm.decode_scope_device_ms_per_step",
                 "ssm.state_bytes_per_stream",
                 "attn.scope_device_ms_per_update",
                 "learner.scope_device_ms_per_update"):
        assert cell.reader(name)(ctx) is None, name
    taken = {m["name"] for m in cell.per_layer}
    assert set(cell.chosen_metrics) <= taken
    assert not {"rollout.decode_hbm_roofline_pct", "moe.scope_device_ms_per_update",
                "moe.max_expert_load_ratio", "mla.scope_device_ms_per_update",
                "linear_attn.scope_device_ms_per_update"} & taken
    assert {"ssm.scope_device_ms_per_update", "ssm.decode_scope_device_ms_per_step",
            "rollout.ssm_decode_hbm_roofline_pct", "ssm.state_bytes_per_stream",
            "learner.mfu_pct"} <= taken


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


def test_the_decode_scope_reader_matches_the_two_scopes_in_order():
    """A run of layers is a scan: the loop's frames stand between the
    lane's ``rollout/act`` and the model's ``ssm`` on an operation's
    path. The learn program's ``learn/ssm`` is not the rollout's."""
    import types

    cell = manifest_lib.load_cell(CELL)
    seconds = cell._module(
        "layer_metrics", "ssm.decode_scope_device_ms_per_step").seconds
    act = "jit(rollout_superstep)/while/body/closed_call/rollout/act/"
    ops = [
        [act + "while/body/closed_call/ssm/step/mul", 0, 1000],
        [act + "while/body/closed_call/mlp/dot_general", 1000, 500],
        ["jit(rollout_superstep)/sgd_nest/while/body/learn/loss_grad/learn/ssm/step/mul",
         2000, 700],
        [act + "attn/dot_general", 3000, 300],
    ]
    rep = lambda ops: types.SimpleNamespace(
        op_scopes=ops, trace=types.SimpleNamespace(bounds=None))
    assert seconds(rep(ops)) == 1000 / 1e9
    assert seconds(rep(ops[1:])) is None
    assert seconds(None) is None
