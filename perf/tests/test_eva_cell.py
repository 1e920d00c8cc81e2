"""The EVA-attention cell's files, rehearsed on the CPU at a small size:
the committed configuration, traffic mix, reference, checks, FLOP rule
and readers of ``evabyte_ppo.fused_tokens.1chip`` with only the sizes
rewritten (hidden 32, two layers, 4 heads of 8, a feed-forward of 48, a
window of 8 and chunks of 2 in episodes of 48, a vocabulary of 20, 8
streams x 6 tokens: depths 0-42, and since 6 does not divide 8, two of
the eight fragments cross a window boundary in every iteration)."""

import json
import os

import numpy as np
import pytest

from perf import eva_model
from perf import manifest as manifest_lib
from perf import run as run_lib
from perf.tests.conftest import _rewrite

CELL = "evabyte_ppo.fused_tokens.1chip"
CONFIG = "evabyte_6_5b_ppo"
TRAFFIC = "fused_tokens_v320_e10240_f640"
SMALL = {
    "hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 8, "heads_held": [0, 4],
    "intermediate_size": 48, "window_size": 8, "chunk_size": 2,
    "max_position_embeddings": 48, "vocab_size": 20,
}
READERS = (
    "rollout.eva_decode_hbm_roofline_pct", "eva.step_hbm_roofline_pct",
    "eva.decode_scope_device_ms_per_step", "eva.scope_device_ms_per_update",
    "eva.cache_bytes_per_stream")


@pytest.fixture()
def small_root(tiny_root):
    perf = os.path.join(tiny_root, "perf")

    def shrink_config(c):
        c.update(SMALL)
        lm = c["algo_config"]["model"]["sequence_lm"]
        lm.update({k: v for k, v in SMALL.items() if k != "vocab_size"})
        c["algo_config"]["model"]["dtype"] = "float32"
        c["algo_config"]["model"]["max_seq_len"] = 6
        c["algo_config"]["lr"] = 1e-4

    def shrink_traffic(t):
        t["algo_config"].update(
            num_envs_per_worker=8, rollout_fragment_length=6,
            train_batch_size=48, sgd_minibatch_size=48,
            env_config={"vocab_size": 20, "episode_length": 48, "phase_stride": 6},
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
    assert "dtype" not in c["algo_config"]["model"]  # the shipped bfloat16
    assert t["env_config"]["vocab_size"] == c["vocab_size"] == 320  # whole
    assert t["env_config"]["episode_length"] == c["max_position_embeddings"] == 10240
    assert t["rollout_fragment_length"] == c["algo_config"]["model"]["max_seq_len"] == 640
    assert (t["num_envs_per_worker"], t["superstep"], t["num_sgd_iter"]) == (16, 1, 1)
    assert (t["num_envs_per_worker"] * t["rollout_fragment_length"]
            == t["train_batch_size"] == t["sgd_minibatch_size"] == 10240)
    # depths cover the episode: 16 streams, 640 bytes apart, five windows
    assert t["env_config"]["phase_stride"] * t["num_envs_per_worker"] == 10240
    assert c["window_size"] * 5 == 10240 and 640 % c["chunk_size"] == 0
    assert c["window_size"] % 640 != 0  # so some fragment crosses every iteration
    assert c["heads_held"] == [0, c["num_attention_heads"]] == [0, 8]
    assert c["num_key_value_heads"] == 8 and c["head_dim"] == 128
    assert set(c["reduced"]) == set(c["published"]) - {"deployment"}
    assert set(c["reduced"]) == set(c["reduced_why"]) == set(cell.config_entry["reduced"])
    assert set(c["reduced"]) == {
        "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
        "num_pred_heads", "max_position_embeddings"}
    deployment = c["published"]["deployment"]
    assert "4 chips share each layer's heads" in deployment
    assert "8 stages" in deployment and "4 : 1" in deployment
    for key in ("head_dim", "mu_phi", "pooling_logits", "summaries_after_rope",
                "window_alignment", "num_pred_heads", "value_head", "ppo", "weights"):
        assert key in c["assumed"], key
    assert c["algo_config"]["vf_clip_param"] == 100.0
    assert cell.config_entry["source"] == c["source"]
    assert cell.chips == 1 and "16 streams x 640" in cell.why and "4:1" in cell.why
    assert cell.config["checks"] == ["fused_dispatch"]
    # the traffic mix is ``fused_tokens_v18992_e8192`` but for its geometry
    with open(os.path.join(
            manifest_lib.PERF_DIR, "traffic", "fused_tokens_v18992_e8192.json")) as f:
        base = json.load(f)
    base["name"], base["what"] = cell.traffic["name"], cell.traffic["what"]
    base["algo_config"]["env_config"].update(
        vocab_size=320, episode_length=10240, phase_stride=640)
    base["algo_config"].update(
        num_envs_per_worker=16, rollout_fragment_length=640,
        train_batch_size=10240, sgd_minibatch_size=10240, num_sgd_iter=1)
    assert base == cell.traffic
    # every number of the catalogue's entry but the reduced keys: no
    # width differs from the source
    for key, value in {
        "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
        "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
        "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
        "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
        "lazy_init": True, "max_seq_length": 32768, "mixedp_attn": True,
        "model_type": "evabyte", "norm_add_unit_offset": True, "num_chunks": None,
        "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 100000,
        "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048,
    }.items():
        assert c[key] == value, key
    assert c["published"] == dict(
        c["published"], num_hidden_layers=32, num_attention_heads=32,
        num_key_value_heads=32, num_pred_heads=8, max_position_embeddings=32768)
    # the new metrics are this cell's alone, and it takes the lane's own
    by_name = {m["name"]: m for m in cell.manifest["per_layer"]}
    for name in READERS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "env_steps_per_s"
    assert {"rollout.decode_device_ms_per_step", "learner.scope_device_ms_per_update",
            "rollout.exposed_wait_device_ms_per_step",
            "learner.exposed_wait_device_ms_per_update",
            "device.unscoped_device_ms_per_iter"} <= set(cell.chosen_metrics)


def test_parameter_and_byte_arithmetic_at_the_published_widths():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    v = c["vocab_size"]
    p = eva_model.layer_param_counts(c, v)
    assert p["attention"] == 16_777_216 == 4 * 4096 * 1024
    assert p["eva_vectors"] == 2_048 and p["norms"] == 8_192
    assert p["feed_forward"] == 135_266_304 == 3 * 4096 * 11008
    assert p["embedding"] == p["head"] == 1_310_720
    assert p["value_and_final_norm"] == 8_193
    assert eva_model.param_count(c, v) == 610_844_673  # x 16 B = 9.77e9
    assert 0.885 < p["feed_forward"] / 152_053_760 < 0.895  # 89% of a held layer
    # the policy's own shapes add up to the same count
    from ray_tpu.models.sequence_lm import SequenceLM

    model = SequenceLM(v, c["algo_config"]["model"]["sequence_lm"])
    shapes = model.param_shapes()
    assert sorted(shapes) == [
        "embed", "final_norm", "head", "layer_0", "layer_1", "layer_2", "layer_3",
        "value"]
    assert sum(int(np.prod(s)) for g in shapes.values() for s in g.values()) == 610_844_673
    assert sum(int(np.prod(s)) for s in shapes["layer_1"].values()) == 152_053_760
    # the reference's own shapes too
    assert cell.reference().param_shapes(c, v) == shapes
    # the same sum over all 32 layers and heads is the published size
    full = dict(c, num_hidden_layers=32, num_attention_heads=32)
    assert round(eva_model.param_count(full, v) / 1e9, 1) == 6.5
    assert eva_model.layer_param_counts(full, v)["attention"] + 8_192 + p[
        "feed_forward"] + p["norms"] == 202_391_552
    # a stream's two stores a layer: 2,048 window rows and 640 summary rows
    assert eva_model.cache_bytes(c) == {"window": 8_388_608, "summary": 2_621_440}
    assert sum(eva_model.cache_bytes(c).values()) == 11_010_048
    state = model.initial_state(2)
    assert [s.shape for s in state[:-1]] == (
        [(2, 2048, 1024)] * 2 + [(2, 640, 1024)] * 2) * 4
    assert eva_model.cache_bytes_per_stream(state) == 44_040_192
    assert eva_model.cache_bytes_per_stream(state[-1:]) is None
    # three copies in the lane, 16 streams
    assert round(3 * 16 * 44_040_192 / 1e9, 2) == 2.11
    # 4 of the 16 fragments cross a window boundary, every iteration
    starts = 640 * np.arange(16)
    crossing = [int(s) for s in starts if s // 2048 != (s + 639) // 2048]
    assert crossing == [1920, 3840, 5760, 7680]
    # a decode step of 16 streams: product weights at 2 bytes, the rows
    # inside the two masks at the mean depth, not the stores' 2,688
    assert round(2 * eva_model.product_weight_count(c, v) / 1e9, 2) == 1.22
    seen = eva_model.mean_rows_seen(c)
    assert seen == {"window": 1024.5, "summary": 256.0}
    rows = 4 * 16 * 4096 * (seen["window"] + seen["summary"] + 1 + 1 / 16)
    assert round(rows / 1e9, 3) == 0.336
    need = eva_model.decode_step_bytes(c, v, 16)
    assert abs(need - (2 * eva_model.product_weight_count(c, v) + rows)) < 0.005e9
    assert 0.21 < rows / need < 0.22  # the two stores are a fifth of a step's bytes
    assert round(need / 819e9 * 1e3, 2) == 1.9  # ms at the roofline
    # full attention at the same depths: more than the weights
    assert 4 * 16 * 4096 * 5120.5 > 2 * eva_model.product_weight_count(c, v)
    # one call of the one-token attention: the masks' rows of 16 streams
    assert round(eva_model.eva_step_bytes(c, 16) / 1e6, 1) == 84.0
    assert eva_model.eva_step_bytes(c, 16) < 16 * 4096 * (2048 + 640) / 2


def test_flop_rule_counts_rollout_and_update():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    rule = cell._module("flop_rules", "evabyte_ppo")
    fwd = rule.forward_flops_per_token(c, c["vocab_size"])
    assert rule.train_flops_per_env_step(c, c["vocab_size"]) == 4 * fwd
    p = eva_model.layer_param_counts(c, c["vocab_size"])
    products = 2 * (p["head"] + 4096 + 4 * (p["attention"] + p["feed_forward"]))
    scores = 2 * 4 * 1024 * 2 * (1024.5 + 256.0)
    pooling = 2 * 4 * 4 * 1024
    assert abs(fwd - (products + scores + pooling)) < 1.0
    # the feed-forward does most of the work of any token; the products
    # over both stores are an eighth of the attention projections'
    assert 0.87 < 2 * 4 * p["feed_forward"] / fwd < 0.89
    assert 0.15 < scores / (2 * 4 * p["attention"]) < 0.16
    # an update over 10,240 tokens: 3.8e13 operations, 3.7e13 of them
    # the 6 x 608 M of the weights' products
    assert round(10240 * 3 * fwd / 1e13, 1) == 3.8
    assert round(10240 * 3 * products / 1e13, 1) == 3.7


def test_the_limits_file_passes_the_manifests_test():
    limits = manifest_lib.load_cell(CELL).limits
    for name, entry in limits.entries.items():
        if entry["separates"]:
            assert entry["sound_max"] < entry["limit"] < min(
                entry["control_min"].values()), name
    separating = [n for n, e in limits.entries.items() if e["separates"]]
    assert {"grad_rel_l2", "rollout_logit_rel_l2", "rollout_state_rel_l2"} <= set(
        separating)


def test_the_cell_runs_end_to_end_at_a_small_size(small_root):
    from ray_tpu.telemetry import metrics

    cell = manifest_lib.load_cell(CELL, small_root)
    before = dict(metrics.eva_lowerings())
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
    # the streams stand six apart, through all six windows of 8
    assert "depths 0-42, 8 distinct" in by_name["streams_off_phase"]["note"]
    # float32 on the CPU takes the reference's gradient
    assert by_name["grad_rel_l2"]["value"] < 1e-3
    assert by_name["adam_step_rel_l2"]["value"] < 1e-3
    assert by_name["forms_logit_rel_l2"]["value"] < 1e-4
    assert by_name["route_top_k_mismatch_share"]["value"] == 0  # no layer routes
    assert by_name["rollout_logit_rel_l2"]["value"] < 1e-3
    assert by_name["rollout_state_rel_l2"]["value"] < 1e-3
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    # the programs traced both forms of the two-store attention
    after = metrics.eva_lowerings()
    assert after.get("step", 0) > before.get("step", 0)
    assert after.get("fragment", 0) > before.get("fragment", 0)
    # a reader of the device trace finds nothing without one and says so
    ctx = run_lib.Context(cell, None, None, 1, "cpu", 20)
    for name in READERS + ("rollout.decode_device_ms_per_step",
                           "learner.scope_device_ms_per_update"):
        assert cell.reader(name)(ctx) is None, name
    taken = {m["name"] for m in cell.per_layer}
    assert set(cell.chosen_metrics) <= taken and set(READERS) <= taken
    assert not {"rollout.decode_hbm_roofline_pct", "swa.cache_bytes_per_stream",
                "rollout.window_decode_hbm_roofline_pct",
                "moe.scope_device_ms_per_update"} & taken
    assert {"learner.mfu_pct", "device.idle_pct", "device.peak_hbm_gb"} <= taken


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


def test_the_readers_return_nothing_for_a_cell_without_eva_attention():
    """What the parent's program, or another configuration's, gives the
    five readers: no scope, no key, no number, and no error."""
    import types

    other = manifest_lib.load_cell("smallthinker_ppo.fused_tokens.1chip")
    cell = manifest_lib.load_cell(CELL)
    ctx = run_lib.Context(other, None, None, 1, "cpu", 64)
    for name in READERS:
        assert cell.reader(name)(ctx) is None, name
    # this cell's files over a program without the scopes (the parent's)
    ctx = run_lib.Context(cell, types.SimpleNamespace(), None, 1, "cpu", 320)
    for name in READERS:
        assert cell.reader(name)(ctx) is None, name
    from perf import ssm_moe_model

    act = "jit(rollout_superstep)/while/body/closed_call/rollout/act/"
    ops = [
        [act + "eva/scores/eva_step_attention", 0, 1000],
        [act + "eva/summarise/reduce", 1000, 200],
        [act + "mlp/dot_general", 2000, 500],
        ["jit(rollout_superstep)/sgd_nest/while/body/learn/loss_grad/learn/eva/out/mul",
         3000, 700],
        [act + "eva/dot_general", 4000, 300],
    ]
    rep = lambda ops: types.SimpleNamespace(
        op_scopes=ops, trace=types.SimpleNamespace(bounds=None))
    # the two needles the readers hand ``act_seconds_under``
    assert ssm_moe_model.act_seconds_under(rep(ops), "/eva/") == 1500 / 1e9
    assert ssm_moe_model.act_seconds_under(rep(ops), "/eva/scores/") == 1000 / 1e9
    assert ssm_moe_model.act_seconds_under(rep(ops[2:4]), "/eva/") is None
