"""The SambaY cell's files, rehearsed on the CPU at a small size: the
committed configuration, traffic mix, reference, checks, FLOP rule and
readers of ``phi4flash_ppo.fused_tokens.1chip`` with only the sizes
rewritten (hidden 64, the six layers of the published indices 0, 1, 16,
17, 18, 19, 8 heads of 8 over 4 key heads, a feed-forward of 96, inner
128 with a state of 4, a window of 8 in episodes of 48, a vocabulary of
20, 8 streams x 6 tokens: depths 0-42, every window fragment reads
stored ring rows and the ring wraps)."""

import json
import os

import numpy as np
import pytest

from perf import manifest as manifest_lib
from perf import run as run_lib
from perf import sambay_model as m
from perf.tests.conftest import _rewrite

CELL = "phi4flash_ppo.fused_tokens.1chip"
CONFIG = "phi4_mini_flash_ppo"
TRAFFIC = "fused_tokens_v25008_e8192_f256"
SMALL = {
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 4,
    "intermediate_size": 96, "sliding_window": 8, "mamba_d_state": 4,
    "mamba_dt_rank": 4, "max_position_embeddings": 48, "vocab_size": 20,
}
READERS = (
    "rollout.shared_cache_decode_hbm_roofline_pct", "xattn.step_hbm_roofline_pct",
    "scan.fragment_hbm_roofline_pct", "xattn.decode_scope_device_ms_per_step",
    "xattn.scope_device_ms_per_update", "scan.decode_scope_device_ms_per_step",
    "scan.scope_device_ms_per_update", "shared_kv.cache_bytes_per_stream")


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
    assert t["env_config"]["vocab_size"] == c["vocab_size"] == 25008 == 200064 // 8
    assert t["env_config"]["episode_length"] == c["max_position_embeddings"] == 8192
    assert t["rollout_fragment_length"] == c["algo_config"]["model"]["max_seq_len"] == 256
    assert (t["num_envs_per_worker"], t["superstep"], t["num_sgd_iter"]) == (16, 1, 1)
    assert (t["num_envs_per_worker"] * t["rollout_fragment_length"]
            == t["train_batch_size"] == t["sgd_minibatch_size"] == 4096)
    # depths cover the episode: 16 streams 512 apart, a fragment half a window
    assert t["env_config"]["phase_stride"] * t["num_envs_per_worker"] == 8192
    assert 2 * t["rollout_fragment_length"] == c["sliding_window"] == 512
    assert (t["env_backend"], t["num_workers"]) == ("jax", 0)
    assert c["layer_indices"] == [0, 1, 16, 17, 18, 19]
    assert c["num_hidden_layers"] == 6 and c["published_num_hidden_layers"] == 32
    assert set(c["reduced"]) == set(c["reduced_why"]) == set(
        cell.config_entry["reduced"]) == {
        "num_hidden_layers", "vocab_size", "max_position_embeddings"}
    assert {k: c["published"][k] for k in c["reduced"]} == {
        "num_hidden_layers": 32, "vocab_size": 200064,
        "max_position_embeddings": 262144}
    deployment = c["published"]["deployment"]
    assert "8 chips share the tied table's rows" in deployment
    assert "WHOLE" in deployment and "pipeline stages" in deployment
    assert "9 : 8 : 1 : 7 : 7" in c["published"]["layer_ratio"]
    assert "2 : 1 : 1 : 1 : 1" in c["published"]["layer_ratio"]
    assert "9:8:1:7:7" in cell.why and "2:1:1:1:1" in cell.why
    for key in ("state_space_sizes", "attention_biases", "exporters", "pairing",
                "lambda", "weights", "norm_weights", "column_orders", "value_head",
                "ppo", "dropout"):
        assert key in c["assumed"], key
    assert "Kimi-K2.7-Code" in c["reduced_why"]["num_hidden_layers"]
    assert cell.config_entry["source"] == c["source"]
    assert cell.chips == 1 and "16 streams x 256" in cell.why
    assert cell.config["checks"] == ["fused_dispatch"]
    assert cell.traffic["checks"] == ["token_streams_at_phase", "rollout_fragment"]
    # the traffic mix is ``fused_tokens_v12544`` but for its geometry
    with open(os.path.join(
            manifest_lib.PERF_DIR, "traffic", "fused_tokens_v12544.json")) as f:
        base = json.load(f)
    base["name"], base["what"] = cell.traffic["name"], cell.traffic["what"]
    base["algo_config"]["env_config"].update(
        vocab_size=25008, episode_length=8192, phase_stride=512)
    base["algo_config"]["num_sgd_iter"] = 1
    assert base == cell.traffic
    # every number of the catalogue's entry but the reduced keys: no
    # width differs from the source
    for key, value in {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-5, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_key_value_heads": 20, "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False, "lm_head_bias": False,
    }.items():
        assert c[key] == value, key
    # the new metrics are this cell's alone, and it takes the lane's own
    by_name = {m_["name"]: m_ for m_ in cell.manifest["per_layer"]}
    for name in READERS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "env_steps_per_s"
    assert {"rollout.decode_device_ms_per_step", "learner.scope_device_ms_per_update",
            "rollout.exposed_wait_device_ms_per_step",
            "learner.exposed_wait_device_ms_per_update",
            "learner.host_idle_ms_per_iter", "entry.unattributed_idle_pct",
            "device.unscoped_device_ms_per_iter"} <= set(cell.chosen_metrics)
    assert len(cell.manifest["workloads"]) == 10


def test_parameter_and_byte_arithmetic_at_the_published_widths():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    v = c["vocab_size"]
    assert m.kinds(c) == [m.SCAN, m.WINDOW, m.SCAN, m.FULL, m.MEMORY, m.CROSS]
    p = m.layer_param_counts(c, v)
    block = sum(p["block"].values())
    assert block == 78_653_440
    assert [sum(p[k].values()) + block for k in (m.SCAN, m.WINDOW, m.MEMORY, m.CROSS)
            ] == [119_895_040, 98_322_304, 104_867_840, 91_766_144]
    assert m.param_count(c, v) == 697_096_833  # x 16 B = 11.15e9
    assert m.published_param_count(c) == 3_852_562_944  # the published 3.8 B
    # the policy's own shapes add up to the same count
    from ray_tpu.models.sequence_lm import SequenceLM

    model = SequenceLM(v, c["algo_config"]["model"]["sequence_lm"])
    shapes = model.param_shapes()
    assert sorted(shapes) == ["embed", "final_norm"] + [
        f"layer_{i}" for i in range(6)] + ["value"]
    assert sum(int(np.prod(s)) for g in shapes.values() for s in g.values()) == 697_096_833
    # the reference's own shapes too
    assert cell.reference().param_shapes(c, v) == shapes
    # what a stream carries: the shared cache ONCE
    assert m.cache_bytes(c) == {
        "shared": 41_943_040, "rings": 2_621_440, "scans": 2 * 389_120}
    state = model.initial_state(2)
    assert m.cache_bytes_per_stream(state) == 45_342_720 == sum(m.cache_bytes(c).values())
    assert m.cache_bytes_per_stream(state[-1:]) is None
    # three copies in the lane, 16 streams
    assert round(3 * 16 * 45_342_720 / 1e9, 2) == 2.18
    # a decode step of 16 streams: product weights at 2 bytes, the shared
    # cache's rows below the position once a READING layer. ISSUE 57
    # reckoned 2.46e9 B with three readings; the six layers held have two
    # (the full layer and ONE cross layer), which is 2.13e9
    assert round(2 * m.product_weight_count(c, v) / 1e9, 3) == 1.394
    seen = m.mean_rows_seen(c)
    assert seen["full"] == 4096.5 and 495 < seen["window"] < 497
    one_reading = 16 * 5120 * seen["full"]
    assert round(one_reading / 1e9, 3) == 0.336
    need = m.decode_step_bytes(c, v, 16)
    assert abs(need / 2.132e9 - 1) < 0.01
    assert abs((need + one_reading) / 2.46e9 - 1) < 0.01  # the issue's, a reading more
    assert 0.31 < 2 * one_reading / need < 0.32  # the shared cache: a third of a step
    assert round(need / 819e9 * 1e3, 2) == 2.6  # ms at the roofline
    # one call of a cross layer's one-token attention
    assert round(m.xattn_step_bytes(c, 16) / 1e6, 1) == 336.2
    # one layer's fragment scan, forward and backward, 16 x 256 tokens
    assert round(m.scan_fragment_bytes(c, 16, 256) / 1e6) == 694
    # the states a materialising form would hold: what must never be alive
    assert 16 * 256 * 5120 * 16 * 4 == 1_342_177_280


def test_flop_rule_counts_rollout_and_update():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    rule = cell._module("flop_rules", "phi4flash_ppo")
    fwd = rule.forward_flops_per_token(c, c["vocab_size"])
    assert rule.train_flops_per_env_step(c, c["vocab_size"]) == 4 * fwd
    products = 2 * (m.product_weight_count(c, c["vocab_size"]) + 2560)
    seen = m.mean_rows_seen(c)
    # 20 pairs, two score products of 64 and two value products of 128
    attention = 2 * 20 * (2 * 64 + 2 * 128) * (2 * seen["full"] + seen["window"])
    scans = 2 * (2 * 5120 * 4 + 9 * 5120 * 16)
    assert abs(fwd - (products + attention + scans)) < 1.0
    assert 0.08 < attention / fwd < 0.09 and scans / fwd < 0.002
    # an update over 4,096 tokens: 1.9e13 operations, 1.7e13 of them the
    # 6 x 697 M of the weights' products
    assert round(4096 * 3 * fwd / 1e13, 1) == 1.9
    assert round(4096 * 3 * products / 1e13, 1) == 1.7


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
    before = dict(metrics.selective_scan_lowerings())
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
    assert "depths 0-42, 8 distinct" in by_name["streams_off_phase"]["note"]
    # float32 on the CPU takes the reference's gradient
    assert by_name["grad_rel_l2"]["value"] < 1e-3
    assert by_name["adam_step_rel_l2"]["value"] < 1e-3
    assert by_name["forms_logit_rel_l2"]["value"] < 1e-4
    assert by_name["route_top_k_mismatch_share"]["value"] == 0  # no layer routes
    assert by_name["rollout_logit_rel_l2"]["value"] < 1e-3
    assert by_name["rollout_state_rel_l2"]["value"] < 1e-3
    assert set(out["metrics"]) == {m_["name"] for m_ in cell.end_to_end}
    # the programs traced both forms of the scan and both exports
    after = metrics.selective_scan_lowerings()
    assert after.get("step", 0) > before.get("step", 0)
    assert after.get("fragment", 0) > before.get("fragment", 0)
    assert {"kv/1", "memory/1"} <= set(metrics.shared_state_lowerings())
    # a reader of the device trace finds nothing without one and says so
    ctx = run_lib.Context(cell, None, None, 1, "cpu", 20)
    for name in READERS + ("rollout.decode_device_ms_per_step",
                           "learner.scope_device_ms_per_update"):
        assert cell.reader(name)(ctx) is None, name
    taken = {m_["name"] for m_ in cell.per_layer}
    assert set(cell.chosen_metrics) <= taken and set(READERS) <= taken
    assert not {"rollout.ssm_decode_hbm_roofline_pct", "swa.cache_bytes_per_stream",
                "rollout.window_decode_hbm_roofline_pct",
                "eva.cache_bytes_per_stream"} & taken
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


def test_the_readers_return_nothing_for_a_cell_that_is_not_phi4flash():
    """What the parent's program, or another configuration's, gives the
    eight readers: no scope, no key, no number, and no error."""
    import types

    other = manifest_lib.load_cell("granite4h_ppo.fused_tokens.1chip")
    cell = manifest_lib.load_cell(CELL)
    ctx = run_lib.Context(other, None, None, 1, "cpu", 64)
    for name in READERS:
        assert cell.reader(name)(ctx) is None, name
    # this cell's files over a program without the scopes (the parent's)
    ctx = run_lib.Context(cell, types.SimpleNamespace(), None, 1, "cpu", 25008)
    for name in READERS:
        assert cell.reader(name)(ctx) is None, name
    from perf import sequence_model, ssm_moe_model

    act = "jit(rollout_superstep)/while/body/closed_call/rollout/act/"
    learn = "jit(rollout_superstep)/sgd_nest/while/body/learn/loss_grad/learn/"
    ops = [
        [act + "xattn/scores/step_attention", 0, 1000],
        [act + "xattn/diff/sub", 1000, 200],
        [act + "attn/scores/step_attention", 2000, 900],
        [act + "scan/step/mul", 3000, 400],
        [act + "gmu/dot_general", 4000, 500],
        [learn + "scan/step/while/body/mul", 5000, 700],
        [learn + "xattn/out/mul", 6000, 300],
        [act + "xattn/dot_general", 7000, 100],
    ]
    rep = lambda ops: types.SimpleNamespace(
        op_scopes=ops, trace=types.SimpleNamespace(bounds=None))
    # the needles the readers hand the two sums
    assert ssm_moe_model.act_seconds_under(rep(ops), "/xattn/") == pytest.approx(1300e-9)
    assert ssm_moe_model.act_seconds_under(rep(ops), "/xattn/scores/") == 1000 / 1e9
    assert ssm_moe_model.act_seconds_under(rep(ops), "/scan/") == 400 / 1e9
    assert sequence_model.seconds_under(rep(ops), "learn/scan/step") == 700 / 1e9
    assert sequence_model.seconds_under(rep(ops), "learn/xattn/") == 300 / 1e9
    assert sequence_model.seconds_under(rep(ops), "learn/scan/") == 700 / 1e9
    assert ssm_moe_model.act_seconds_under(rep(ops[2:3]), "/xattn/") is None
