"""The window-attention cell's files, rehearsed on the CPU at a small
size: the committed configuration, traffic mix, reference, checks, FLOP
rule and readers of ``smallthinker_ppo.fused_tokens.1chip`` with only
the sizes rewritten (hidden 32, one period full, window, window, window;
4 heads of 8 over 2 KV heads, a window of 8 in episodes of 32, a router
over 8 experts of which 2 are held, top-3, a vocabulary of 64, 8 streams
x 8 tokens: half of the streams past the window)."""

import json
import os

import numpy as np
import pytest

from perf import manifest as manifest_lib
from perf import run as run_lib
from perf import window_model
from perf.tests.conftest import _rewrite

CELL = "smallthinker_ppo.fused_tokens.1chip"
CONFIG = "smallthinker_21b_a3b_ppo"
TRAFFIC = "fused_tokens_v18992_e8192"
SMALL = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 8, "sliding_window_size": 8, "moe_num_primary_experts": 2,
    "router_outputs": 8, "experts_held": [0, 2],
    "moe_num_active_primary_experts": 3, "moe_ffn_hidden_size": 16,
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


def test_the_committed_files_agree_with_each_other():
    cell = manifest_lib.load_cell(CELL)
    c, t = cell.config, cell.traffic["algo_config"]
    lm = c["algo_config"]["model"]["sequence_lm"]
    for key, value in lm.items():
        if key in ("sliding_window_layout", "rope_layout"):
            # published whole; the first period is run
            assert c[key][: c["num_hidden_layers"]] == value == [0, 1, 1, 1]
            assert c[key] == [0, 1, 1, 1] * 13
            assert len(c[key]) == c["published"]["num_hidden_layers"] == 52
        else:
            assert c[key] == value, key  # one architecture, stated twice
    assert "dtype" not in c["algo_config"]["model"]  # the shipped bfloat16
    assert t["env_config"]["vocab_size"] == c["vocab_size"] == 18992 == 151936 // 8
    assert t["env_config"]["episode_length"] == c["max_position_embeddings"] == 8192
    assert t["rollout_fragment_length"] == c["algo_config"]["model"]["max_seq_len"] == 256
    assert t["num_envs_per_worker"] == 32
    assert (t["num_envs_per_worker"] * t["rollout_fragment_length"]
            == t["train_batch_size"] == t["sgd_minibatch_size"] == 8192)
    # depths cover the episode: 32 streams, 256 tokens apart, half past the window
    assert t["env_config"]["phase_stride"] * t["num_envs_per_worker"] == 8192
    assert c["sliding_window_size"] * 2 == 8192
    assert c["experts_held"] == [0, c["moe_num_primary_experts"]] == [0, 8]
    assert c["router_outputs"] == c["published"]["moe_num_primary_experts"] == 64
    assert set(c["reduced"]) == set(c["published"]) - {"deployment"}
    assert set(c["reduced"]) == set(c["reduced_why"]) == set(cell.config_entry["reduced"])
    assert "8 chips share each layer" in c["published"]["deployment"]
    assert "13 stages" in c["published"]["deployment"]
    for key in ("activation", "router_input", "secondary_experts",
                "window_convention", "no_qk_norm_no_bias", "rope", "ppo",
                "value_head", "weights"):
        assert key in c["assumed"], key
    assert cell.config_entry["source"] == c["source"]
    assert cell.chips == 1 and "32 streams x 256" in cell.why
    # the traffic mix is ``fused_tokens`` but for its geometry
    with open(os.path.join(manifest_lib.PERF_DIR, "traffic", "fused_tokens.json")) as f:
        base = json.load(f)
    base["name"], base["what"] = cell.traffic["name"], cell.traffic["what"]
    base["algo_config"]["env_config"].update(
        vocab_size=18992, episode_length=8192, phase_stride=256)
    base["algo_config"].update(
        num_envs_per_worker=32, rollout_fragment_length=256,
        train_batch_size=8192, sgd_minibatch_size=8192)
    assert base == cell.traffic
    # every number of the catalogue's entry but the reduced keys: no
    # width differs from the source
    for key, value in {
        "head_dim": 128, "hidden_size": 2560, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-6,
        "rope_scaling": None, "rope_theta": 1500000, "sliding_window_size": 4096,
        "tie_word_embeddings": False, "model_name": "smallthinker_21b_instruct",
    }.items():
        assert c[key] == value, key
    assert c["published"] == dict(
        c["published"], num_hidden_layers=52, moe_num_primary_experts=64,
        vocab_size=151936, max_position_embeddings=16384)


def test_parameter_and_byte_arithmetic_at_the_published_widths():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    v = c["vocab_size"]
    p = window_model.layer_param_counts(c, v)
    assert p["attention"] == 20_971_520 == 2 * 9_175_040 + 2 * 1_310_720
    assert p["router"] == 163_840 and p["norms"] == 5_120
    assert p["one_expert"] == 5_898_240 and p["experts_held"] == 47_185_920
    assert p["embedding"] == p["head"] == 48_619_520
    assert p["value_and_final_norm"] == 5_121
    assert window_model.param_count(c, v) == 370_549_761  # x 16 B = 5.93e9
    # the policy's own shapes add up to the same count
    from ray_tpu.models.sequence_lm import SequenceLM

    model = SequenceLM(v, c["algo_config"]["model"]["sequence_lm"])
    shapes = model.param_shapes()
    assert sorted(shapes) == [
        "embed", "final_norm", "head", "layer_0", "layer_1", "layer_2", "layer_3",
        "value"]
    assert sum(int(np.prod(s)) for g in shapes.values() for s in g.values()) == 370_549_761
    assert sum(int(np.prod(s)) for s in shapes["layer_1"].values()) == 68_326_400
    # the reference's own shapes too
    assert cell.reference().param_shapes(c, v) == shapes
    # the same sum over all 52 layers, 64 experts and the whole
    # vocabulary is the published size
    full = dict(c, num_hidden_layers=52, moe_num_primary_experts=64)
    assert round(window_model.param_count(full, 151936) / 1e9, 1) == 21.5
    # a stream's caches: the full layer's 8,192 rows, three rings of 4,096
    cache = window_model.cache_bytes(c)
    assert cache == {"full": 16_777_216, "window": 8_388_608}
    state = model.initial_state(2)
    assert [s.shape for s in state[:-1]] == (
        [(2, 8192, 512)] * 2 + [(2, 4096, 512)] * 6)
    assert window_model.cache_bytes_per_stream(state) == 41_943_040
    assert window_model.cache_bytes_per_stream(state[-1:]) is None
    # a decode step of 32 streams: product weights at 2 bytes, the rows
    # inside the masks at the mean depth; every slot under a mask is more
    assert round(2 * window_model.product_weight_count(c, v) / 1e9, 2) == 0.64
    seen = window_model.mean_rows_seen(c)
    assert seen == {"full": 4096.5, "window": 3072.25}
    rows = 32 * 2048 * (seen["full"] + 3 * seen["window"] + 4)
    assert round(rows / 1e9, 2) == 0.87
    need = window_model.decode_step_bytes(c, v, 32)
    assert abs(need - (2 * window_model.product_weight_count(c, v) + rows)) < 0.005e9
    assert 0.57 < rows / need < 0.58  # the caches are most of a step's bytes
    assert round(32 * 41_943_040 / 1e9, 2) == 1.34  # every slot read under a mask


def test_flop_rule_counts_rollout_and_update():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    rule = cell._module("flop_rules", "smallthinker_ppo")
    fwd = rule.forward_flops_per_token(c, c["vocab_size"])
    assert rule.train_flops_per_env_step(c, c["vocab_size"]) == 4 * fwd
    p = window_model.layer_param_counts(c, c["vocab_size"])
    # routed-and-held products only: 6 x 8 / 64 of an expert a token and layer
    products = 2 * (p["head"] + 2560 + 4 * (
        p["attention"] + p["router"] + 0.75 * p["one_expert"]))
    scores = 2 * 28 * 2 * 128 * (4096.5 + 3 * 3072.25)
    assert abs(fwd - (products + scores)) < 1.0
    # attention's products over the keys inside the mask outweigh a
    # token's held experts many times and are the size of its projections
    assert scores > 5 * 2 * 4 * 0.75 * p["one_expert"]
    assert 0.9 < scores / (2 * 4 * p["attention"]) < 1.2


def test_the_limits_file_passes_the_manifests_test():
    limits = manifest_lib.load_cell(CELL).limits
    for name, entry in limits.entries.items():
        if entry["separates"]:
            assert entry["sound_max"] < entry["limit"] < min(
                entry["control_min"].values()), name


def test_the_cell_runs_end_to_end_at_a_small_size(small_root):
    from ray_tpu.telemetry import metrics

    cell = manifest_lib.load_cell(CELL, small_root)
    before = dict(metrics.window_cache_lowerings())
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
    # half of the streams are past the window of 8
    assert "depths 0-28, 8 distinct" in by_name["streams_off_phase"]["note"]
    # float32 on the CPU takes the reference's gradient and its routes
    assert by_name["grad_rel_l2"]["value"] < 1e-3
    assert by_name["adam_step_rel_l2"]["value"] < 1e-3
    assert by_name["forms_logit_rel_l2"]["value"] < 1e-4
    assert by_name["route_top_k_mismatch_share"]["value"] == 0
    assert by_name["rollout_logit_rel_l2"]["value"] < 1e-3
    assert by_name["rollout_state_rel_l2"]["value"] < 1e-3
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    # the programs traced the ring in both forms
    after = metrics.window_cache_lowerings()
    assert after.get("step", 0) > before.get("step", 0)
    assert after.get("fragment", 0) > before.get("fragment", 0)
    # a reader of the device trace finds nothing without one and says so
    ctx = run_lib.Context(cell, None, None, 1, "cpu", 64)
    for name in ("rollout.decode_device_ms_per_step",
                 "rollout.window_decode_hbm_roofline_pct",
                 "swa.scope_device_ms_per_update",
                 "swa.decode_scope_device_ms_per_step",
                 "swa.cache_bytes_per_stream",
                 "attn.scope_device_ms_per_update",
                 "moe.scope_device_ms_per_update",
                 "learner.scope_device_ms_per_update"):
        assert cell.reader(name)(ctx) is None, name
    taken = {m["name"] for m in cell.per_layer}
    assert set(cell.chosen_metrics) <= taken
    assert not {"rollout.decode_hbm_roofline_pct", "mla.scope_device_ms_per_update",
                "ssm.scope_device_ms_per_update",
                "linear_attn.scope_device_ms_per_update"} & taken
    assert {"swa.scope_device_ms_per_update", "swa.decode_scope_device_ms_per_step",
            "rollout.window_decode_hbm_roofline_pct", "swa.cache_bytes_per_stream",
            "moe.max_expert_load_ratio", "learner.mfu_pct"} <= taken


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


def test_the_readers_return_nothing_for_a_cell_without_window_layers():
    """What the parent's program, or another configuration's, gives the
    four readers: no scope, no key, no number, and no error."""
    import types

    other = manifest_lib.load_cell("granite4h_ppo.fused_tokens.1chip")
    cell = manifest_lib.load_cell(CELL)
    ctx = run_lib.Context(other, None, None, 1, "cpu", 64)
    for name in ("swa.scope_device_ms_per_update", "swa.decode_scope_device_ms_per_step",
                 "rollout.window_decode_hbm_roofline_pct", "swa.cache_bytes_per_stream"):
        assert cell.reader(name)(ctx) is None, name
    seconds = cell._module(
        "layer_metrics", "swa.decode_scope_device_ms_per_step").seconds
    act = "jit(rollout_superstep)/while/body/closed_call/rollout/act/"
    ops = [
        [act + "swa/scores/dot_general", 0, 1000],
        [act + "swa/mul", 1000, 200],
        [act + "attn/dot_general", 2000, 500],
        ["jit(rollout_superstep)/sgd_nest/while/body/learn/loss_grad/learn/swa/out/mul",
         3000, 700],
        [act + "moe/experts/dot_general", 4000, 300],
    ]
    rep = lambda ops: types.SimpleNamespace(
        op_scopes=ops, trace=types.SimpleNamespace(bounds=None))
    assert seconds(rep(ops)) == 1200 / 1e9
    assert seconds(rep(ops[2:])) is None
    assert seconds(None) is None
