"""The learned-index cell's files, rehearsed on the CPU at a small size:
the committed configuration, traffic mix, reference, checks, FLOP rule
and readers of ``keye2_ppo.fused_tokens.1chip`` with only the sizes
rewritten (hidden 64, 2 layers, 4 heads of 16 over 2 KV heads, an index
of 8 heads of 16 that keeps 8 rows, episodes of 64, a router over 8
experts of which 2 are held, top-3, a vocabulary of 64, 8 streams x 8
tokens 8 apart: 7 of the 8 streams past ``topk``). The committed cell
is ISSUE 65's permitted departure: episodes of 8,192, streams 512
apart."""

import json
import os
import types

import numpy as np
import pytest

from perf import manifest as manifest_lib
from perf import run as run_lib
from perf import sparse_attention_model as model_lib
from perf.tests.conftest import _rewrite

CELL = "keye2_ppo.fused_tokens.1chip"
CONFIG = "keye_vl_2_0_30b_a3b_ppo"
TRAFFIC = "fused_tokens_v18992_e8192_s16"
SMALL = {
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_experts": 2,
    "router_outputs": 8, "experts_held": [0, 2], "num_experts_per_tok": 3,
    "moe_intermediate_size": 16, "max_position_embeddings": 64, "vocab_size": 64,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 16, "indexer_num_heads": 8,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 4, "q_chunk_size": 4,
                  "topk": 8},
}
READERS = ("index.scope_device_ms_per_update", "index.decode_scope_device_ms_per_step",
           "index.selected_share", "index.cache_bytes_per_stream",
           "rollout.sparse_decode_hbm_roofline_pct")


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
            env_config={"vocab_size": 64, "episode_length": 64, "phase_stride": 8},
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


def _catalog_entry():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        return None
    with open(path) as f:
        for line in f:
            entry = json.loads(line)
            if entry["name"] == "Keye-VL-2.0-30B-A3B":
                return entry
    return None


def test_the_committed_files_agree_with_each_other():
    cell = manifest_lib.load_cell(CELL)
    c, t = cell.config, cell.traffic["algo_config"]
    lm = c["algo_config"]["model"]["sequence_lm"]
    for key, value in lm.items():
        assert c[key] == value, key  # one architecture, stated twice
    assert "dtype" not in c["algo_config"]["model"]  # the shipped bfloat16
    assert t["env_config"]["vocab_size"] == c["vocab_size"] == 18992 == 151936 // 8
    # ISSUE 65's permitted departure from 16,384, with the measured seconds
    assert t["env_config"]["episode_length"] == c["max_position_embeddings"] == 8192
    for text in (cell.traffic["what"], c["reduced_why"]["max_position_embeddings"]):
        assert "433 s" in text and "400 s" in text and "332 s" in text
    assert t["rollout_fragment_length"] == c["algo_config"]["model"]["max_seq_len"] == 256
    assert t["num_envs_per_worker"] == 16
    assert (t["num_envs_per_worker"] * t["rollout_fragment_length"]
            == t["train_batch_size"] == t["sgd_minibatch_size"] == 4096)
    # depths cover the episode: 16 streams 512 apart, 12 past topk
    assert t["env_config"]["phase_stride"] * t["num_envs_per_worker"] == 8192
    depths = np.arange(16) * t["env_config"]["phase_stride"]
    assert int(np.sum(depths >= c["sa_config"]["topk"])) == 12
    assert c["experts_held"] == [0, c["num_experts"]] == [0, 8]
    assert c["router_outputs"] == c["published"]["num_experts"] == 128
    assert set(c["reduced"]) == set(c["published"]) - {"deployment"}
    assert set(c["reduced"]) == set(c["reduced_why"]) == set(cell.config_entry["reduced"])
    assert "16 chips share each layer" in c["published"]["deployment"]
    assert "12 stages" in c["published"]["deployment"]
    for key in ("index_chunks", "index_rope", "index_key_norm", "qk_norm",
                "text_tokens", "no_vision_tower", "no_index_loss", "value_head",
                "ppo", "weights"):
        assert key in c["assumed"], key
    assert cell.config_entry["source"] == c["source"]
    assert cell.chips == 1 and "16 streams x 256" in cell.why
    assert c["checks"] == ["fused_dispatch", "index_selection"]
    assert cell.traffic["checks"] == ["token_streams_at_phase", "rollout_fragment"]
    # the traffic mix is the SmallThinker cell's but for its geometry
    with open(os.path.join(
            manifest_lib.PERF_DIR, "traffic", "fused_tokens_v18992_e8192.json")) as f:
        base = json.load(f)
    base["name"], base["what"] = cell.traffic["name"], cell.traffic["what"]
    base["algo_config"]["env_config"].update(episode_length=8192, phase_stride=512)
    base["algo_config"].update(
        num_envs_per_worker=16, train_batch_size=4096, sgd_minibatch_size=4096)
    assert base == cell.traffic
    # every number of the catalogue's entry but the reduced keys: no
    # width differs from the source
    entry = _catalog_entry()
    if entry is not None:
        assert c["source"] == entry["source_url"]
        for key, value in entry["config"].items():
            if key not in c["reduced"]:
                assert c[key] == value, key
        assert c["published"] == dict(
            c["published"], **{k: entry["config"][k] for k in c["reduced"]})
    assert c["hidden_size"] == 2048 and c["moe_intermediate_size"] == 768
    assert (c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]) == (
        32, 4, 128)
    assert c["num_experts_per_tok"] == 8
    assert c["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
        "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}


def test_parameter_and_byte_arithmetic_at_the_published_widths():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    v = c["vocab_size"]
    p = model_lib.layer_param_counts(c, v)
    assert p["attention"] == 18_874_368 == 2 * 8_388_608 + 2 * 1_048_576
    assert p["qk_norms"] == 256 and p["norms"] == 4_096 and p["router"] == 262_144
    assert p["index_products"] == 2_097_152 + 131_072
    assert p["index_others"] == 32_768 + 128
    assert p["index_products"] + p["index_others"] == 2_261_120
    assert p["one_expert"] == 4_718_592 and p["experts_held"] == 37_748_736
    assert p["embedding"] == p["head"] == 38_895_616
    assert p["value_and_final_norm"] == 4_097
    assert model_lib.param_count(c, v) == 314_398_209 == c["parameters_held"]
    # the policy's own shapes add up to the same count
    from ray_tpu.models.sequence_lm import SequenceLM

    model = SequenceLM(v, c["algo_config"]["model"]["sequence_lm"])
    shapes = model.param_shapes()
    assert sorted(shapes) == [
        "embed", "final_norm", "head", "layer_0", "layer_1", "layer_2", "layer_3",
        "value"]
    assert sum(int(np.prod(s)) for g in shapes.values() for s in g.values()) == 314_398_209
    assert sum(int(np.prod(s)) for s in shapes["layer_1"].values()) == 59_150_720
    index = {k: s for k, s in shapes["layer_0"].items() if k.startswith("index_")}
    assert index == {
        "index_q_proj": (2048, 1024), "index_k_proj": (2048, 64),
        "index_w_proj": (2048, 16), "index_k_norm": (64,), "index_k_norm_bias": (64,)}
    # the reference's own shapes too
    assert cell.reference().param_shapes(c, v) == shapes
    # the same sum over all 48 layers, 128 experts and the whole
    # vocabulary is the published size
    full = dict(c, num_hidden_layers=48, num_experts=128)
    assert round(model_lib.param_count(full, 151936) / 1e9, 1) == 30.6
    # a stream's caches: three leaves a layer, 2,176 B a position
    row = model_lib.cache_row_bytes(c)
    assert row == {"kv": 2048.0, "index": 128.0}
    assert model_lib.cache_bytes(c) == 17_825_792
    state = model.initial_state(2)
    assert [s.shape for s in state[:-1]] == (
        [(2, 8192, 512), (2, 8192, 512), (2, 8192, 64)] * 4)
    z = cell.reference().sizes(c, v)
    assert [s.shape for s in cell.reference().initial_state(z, 2)] == [
        s.shape for s in state]
    assert model_lib.cache_bytes_per_stream(state) == 71_303_168 == 4 * 17_825_792
    assert model_lib.cache_bytes_per_stream(state[-1:]) is None
    assert round(16 * 71_303_168 / 1e9, 2) == 1.14
    # what a query scores and keeps at depths drawn evenly from an episode
    rows = model_lib.mean_rows(c)
    assert rows["scored"] == 4096.5 and rows["dense_query_share"] == 0.25
    assert rows["selected"] == (2048 * 2049 / 2 + (8192 - 2048) * 2048) / 8192
    by_hand = np.minimum(np.arange(8192) + 1, 2048) / (np.arange(8192) + 1)
    assert abs(rows["selected_share"] - by_hand.mean()) < 1e-12
    assert 0.59 < rows["selected_share"] < 0.60
    # at the 16,384 ISSUE 65 named: 0.385
    assert 0.38 < model_lib.mean_rows(
        dict(c, max_position_embeddings=16384))["selected_share"] < 0.39
    # a decode step of 16 streams: product weights at 2 bytes, every
    # index row below the depth, the chosen key and value rows
    assert round(2 * model_lib.product_weight_count(c, v) / 1e9, 2) == 0.55
    cache = 16 * 4 * (128 * (4096.5 + 1) + 2048 * (rows["selected"] + 1))
    assert round(cache / 1e9, 3) == 0.269
    need = model_lib.decode_step_bytes(c, v, 16)
    assert abs(need - (2 * model_lib.product_weight_count(c, v) + cache)) < 0.01e9
    # at depth 16,384 a stream and layer: 6.3 MB against every row's 33.6 MB
    assert round((16384 * 128 + 2048 * 2048) / 1e6, 1) == 6.3
    assert round(16384 * 2048 / 1e6, 1) == 33.6


def test_flop_rule_equals_a_count_by_hand():
    cell = manifest_lib.load_cell(CELL)
    c = cell.config
    rule = cell._module("flop_rules", "keye2_ppo")
    fwd = rule.forward_flops_per_token(c, c["vocab_size"])
    assert rule.train_flops_per_env_step(c, c["vocab_size"]) == 4 * fwd
    # by hand: a layer's projections, router, the held share of 8 routed
    # experts (8 x 8 / 128 = half an expert a token), the index's three
    # projections, its 16 heads over 4,096.5 rows of 64 (+ the weighted
    # sum), 32 heads over 1,792.125 chosen rows twice; the head and value
    layer = (18_874_368 + 262_144 + 0.5 * 4_718_592
             + 2_097_152 + 131_072 + 32_768
             + 16 * 4096.5 * 65 + 32 * 1792.125 * 2 * 128)
    assert abs(fwd - 2 * (4 * layer + 38_895_616 + 2048)) < 1.0
    # NOT a dense count: attention over every row seen would be 2.29x
    dense = 32 * 4096.5 * 2 * 128
    assert 2.2 < dense / (32 * 1792.125 * 2 * 128) < 2.3
    # the index's scores are under a third of what the chosen rows cost
    assert 0.28 < 16 * 4096.5 * 65 / (32 * 1792.125 * 2 * 128) < 0.30


def test_the_limits_file_passes_the_manifests_test():
    limits = manifest_lib.load_cell(CELL).limits
    for name, entry in limits.entries.items():
        if entry["separates"]:
            assert entry["sound_max"] < entry["limit"] < min(
                entry["control_min"].values()), name
    assert "index_top_k_mismatch_share" in limits
    assert limits.limit("index_rows_selected_wrong") == 0


def test_the_cell_runs_end_to_end_at_a_small_size(small_root):
    """The fused superstep against separate rollout and learn accounts
    (``fused_dispatch``), the rollout against the reference
    (``rollout_fragment``) and the choices (``index_selection``)."""
    from ray_tpu.telemetry import metrics

    cell = manifest_lib.load_cell(CELL, small_root)
    frag = dict(metrics.attention_fragment_lowerings())
    step = dict(metrics.attention_step_lowerings())
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
                 "index_top_k_mismatch_share", "index_rows_selected_wrong",
                 "iteration_adds_up", "dispatch_program_traced_once",
                 "env_carry_split_over_every_chip",
                 "params_replicated_on_every_chip"):
        assert by_name[name]["ok"], by_name[name]
    assert by_name["rollout_positions_wrong"]["value"] == 0
    # 7 of the 8 streams are past topk 8
    assert "depths 0-56, 8 distinct" in by_name["streams_off_phase"]["note"]
    # float32 on the CPU takes the reference's gradient, routes and CHOICES
    assert by_name["grad_rel_l2"]["value"] < 1e-3
    assert by_name["adam_step_rel_l2"]["value"] < 1e-3
    assert by_name["forms_logit_rel_l2"]["value"] < 1e-4
    assert by_name["route_top_k_mismatch_share"]["value"] == 0
    assert by_name["index_top_k_mismatch_share"]["value"] == 0
    assert by_name["index_rows_selected_wrong"]["value"] == 0
    assert by_name["rollout_logit_rel_l2"]["value"] < 1e-3
    assert by_name["rollout_state_rel_l2"]["value"] < 1e-3
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    # the programs traced both forms under a selection, and nothing else
    now_frag, now_step = (
        metrics.attention_fragment_lowerings(), metrics.attention_step_lowerings())
    assert now_frag.get("selected_xla", 0) > frag.get("selected_xla", 0)
    assert now_step.get("selected_xla", 0) > step.get("selected_xla", 0)
    assert now_frag.get("xla", 0) == frag.get("xla", 0)
    assert now_step.get("xla", 0) == step.get("xla", 0)
    # the two program counters read a number; the device trace's readers
    # find nothing without a trace and say so
    ctx = run_lib.Context(cell, None, None, 1, "cpu", 64)
    share = cell.reader("index.selected_share")(ctx)
    want = np.mean(np.minimum(np.arange(64) + 1, 8) / (np.arange(64) + 1.0))
    assert abs(share - want) < 0.05 and 0.2 < share < 0.5
    for name in ("rollout.decode_device_ms_per_step",
                 "rollout.sparse_decode_hbm_roofline_pct",
                 "index.scope_device_ms_per_update",
                 "index.decode_scope_device_ms_per_step",
                 "attn.scope_device_ms_per_update",
                 "moe.scope_device_ms_per_update",
                 "learner.scope_device_ms_per_update"):
        assert cell.reader(name)(ctx) is None, name
    taken = {m["name"] for m in cell.per_layer}
    assert set(cell.chosen_metrics) <= taken and set(READERS) <= taken
    assert not {"rollout.window_decode_hbm_roofline_pct", "swa.cache_bytes_per_stream",
                "mla.scope_device_ms_per_update", "ssm.scope_device_ms_per_update",
                "linear_attn.scope_device_ms_per_update"} & taken
    assert {"moe.max_expert_load_ratio", "learner.mfu_pct",
            "rollout.decode_device_ms_per_step"} <= taken


def test_controls_come_out_worse_than_the_system(small_root):
    """The reference with int8 and float8 operands in the system's
    place reads further from the float32 reference than the system
    (float32 on the CPU) on every number that is a precision's, the
    choices among them."""
    from perf import control

    cell = manifest_lib.load_cell(CELL, small_root)
    (row,) = control.readings(cell, [2**31 + 11], require_tpu=False)
    for name in ("grad_rel_l2", "rollout_logit_rel_l2", "rollout_value_rel_l2",
                 "rollout_state_rel_l2"):
        for precision in ("int8", "fp8"):
            assert row[precision][name] > 10 * row["system"][name], (name, row)
    assert row["system"]["index_top_k_mismatch_share"] == 0
    for precision in ("int8", "fp8"):
        assert row[precision]["index_top_k_mismatch_share"] > 0.01, row[precision]
        assert row[precision]["index_rows_selected_wrong"] == 0


def test_the_readers_return_nothing_for_a_cell_without_an_index():
    """What the parent's program, or another configuration's, gives the
    five readers: no key, no scope, no number, and no error; and what a
    saved cut of a trace gives the two that read scopes."""
    other = manifest_lib.load_cell("smallthinker_ppo.fused_tokens.1chip")
    cell = manifest_lib.load_cell(CELL)
    ctx = run_lib.Context(other, None, None, 1, "cpu", 64)
    for name in READERS:
        if name != "index.selected_share":  # the process's counter, not the cell's
            assert cell.reader(name)(ctx) is None, name
    # a program without the scopes (the parent's): nothing, not zero
    ctx = run_lib.Context(cell, None, None, 1, "cpu", 64)
    for name in READERS:
        if name != "index.selected_share":
            assert cell.reader(name)(ctx) is None, name
    seconds = cell._module(
        "layer_metrics", "index.decode_scope_device_ms_per_step").seconds
    act = "jit(rollout_superstep)/while/body/closed_call/rollout/act/"
    learn = "jit(rollout_superstep)/sgd_nest/while/body/learn/loss_grad/learn/"
    ops = [
        [act + "attn/index/scores/dot_general", 0, 1000],
        [act + "attn/index/topk/sort", 1000, 400],
        [act + "attn/select/gather", 1400, 200],
        [act + "attn/index/proj/dot_general", 1600, 100],
        [act + "attn/scores/dot_general", 2000, 500],
        [learn + "attn/index/scores/dot_general", 3000, 700],
        [act + "moe/experts/dot_general", 4000, 300],
    ]
    rep = lambda ops: types.SimpleNamespace(
        op_scopes=ops, trace=types.SimpleNamespace(bounds=None))
    assert seconds(rep(ops)) == pytest.approx(1700 / 1e9)
    assert seconds(rep(ops[4:])) is None
    assert seconds(None) is None
    from perf import sequence_model

    assert sequence_model.seconds_under(
        rep(ops), "learn/attn/index/", "learn/attn/select") == pytest.approx(700 / 1e9)
    assert sequence_model.seconds_under(
        rep(ops[:5]), "learn/attn/index/", "learn/attn/select") is None


def test_the_reference_ranks_by_a_stable_sort_and_the_traffic_covers_the_depths():
    """The cases ISSUE 65 asked for in ``test_reference.py`` and
    ``test_traffic.py``, which a PR that adds a cell may not edit: the
    reference's choice on a small fragment against a sort by hand (ties,
    an episode boundary, depths below and beyond ``topk``), and the
    depths the traffic's streams stand at by its own numbers."""
    import jax
    import jax.numpy as jnp

    cell = manifest_lib.load_cell(CELL)
    ref = cell.reference()
    config = dict(cell.config, **SMALL)
    config["algo_config"] = {"model": {"max_seq_len": 8}}
    z = ref.sizes(config, 64)
    params = ref.init_params(jax.random.PRNGKey(5), config, 64)["layer_0"]
    rng = np.random.default_rng(1)
    b, t = 3, 8
    x = jnp.asarray(rng.standard_normal((b, t, 64)), jnp.float32)
    caches = tuple(jnp.asarray(rng.standard_normal((b, 64, w)), jnp.float32)
                   for w in (32, 32, 16))
    pos0 = jnp.asarray([0, 5, 40], jnp.int32)
    fresh = np.zeros((b, t), bool)
    fresh[0, 0] = fresh[2, 3] = True
    positions, _ = ref._positions(pos0, jnp.asarray(fresh))
    with jax.default_matmul_precision("highest"):
        _, after, chosen = ref._attention(
            params, x, caches, pos0, positions, jnp.asarray(fresh), z, lambda v: v)
        # the index's own numbers again, the plain way
        qi = ref._rope((x @ params["index_q_proj"]).reshape(b, t, 8, 16), positions,
                       z["theta"])
        ki = ref._layer_norm(x @ params["index_k_proj"], params["index_k_norm"],
                             params["index_k_norm_bias"], z["eps"])
        ki = ref._rope(ki[:, :, None], positions, z["theta"])[:, :, 0]
        keys = jnp.concatenate([caches[2], ki], axis=1)
        index = np.asarray(jnp.einsum(
            "bths,bth->bts", jax.nn.relu(jnp.einsum("bthd,bsd->bths", qi, keys)),
            x @ params["index_w_proj"]))
    chosen, positions = np.asarray(chosen), np.asarray(positions)
    seg = np.cumsum(fresh, axis=1)
    for n in range(b):
        for i in range(t):
            seen = [s for s in range(64) if seg[n, i] == 0 and s < int(pos0[n])] + [
                64 + j for j in range(i + 1) if seg[n, j] == seg[n, i]]
            assert len(seen) == positions[n, i] + 1
            best = sorted(seen, key=lambda s: (-index[n, i, s], s))[:8]
            assert sorted(np.flatnonzero(chosen[n, i])) == sorted(best), (n, i)
    # the index keys are written at the slot of their position
    np.testing.assert_allclose(
        np.asarray(after[2])[1, 5:13], np.asarray(ki)[1], atol=1e-6)
    # the traffic: stream i stands 512 i tokens into its episode
    env = cell.traffic["algo_config"]["env_config"]
    depths = (np.arange(16) * env["phase_stride"]) % env["episode_length"]
    assert depths.max() == 7680 and len(set(depths)) == 16
    share = np.mean([np.mean(np.minimum(d + np.arange(256) + 1, 2048)
                             / (d + np.arange(256) + 1.0)) for d in depths])
    assert 0.60 < share < 0.62  # ISSUE 65's 0.61, at the iteration that starts there
