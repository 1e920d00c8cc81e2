import json
import os

import pytest

from perf import flops
from perf import manifest as manifest_lib


def _config(name):
    with open(os.path.join(manifest_lib.PERF_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def _rule(family):
    cell = manifest_lib.load_cell(manifest_lib.load_manifest()["workloads"][0]["name"])
    return cell._module("flop_rules", family).train_flops_per_env_step


def test_nature_cnn_forward_flops_by_hand():
    cfg = _config("nature_cnn_dqn_per")
    # 84x84x4: conv0 20x20x32 x 8x8x4; conv1 9x9x64 x 4x4x32;
    # conv2 7x7x64 x 3x3x64; dense 3136x512; heads 512x4
    macs = (20 * 20 * 32 * 256 + 9 * 9 * 64 * 512 + 7 * 7 * 64 * 576
            + 3136 * 512 + 512 * 4)
    assert flops.forward_flops_per_sample(cfg["model"], 4) == 2.0 * macs
    ppo = {"flops_family": "ppo", "model": cfg["model"],
           "algo_config": {"num_sgd_iter": 6}}
    assert _rule("ppo")(ppo, 3) == 2.0 * macs * 3 * 6


def test_dqn_counts_target_and_double_q_forwards():
    cfg = _config("nature_cnn_dqn_per")
    fwd = flops.forward_flops_per_sample(cfg["model"], 4)
    assert _rule("dqn")(cfg, 3) == 5.0 * fwd * 8
    cell = manifest_lib.load_cell("dqn_per.fused.1chip")
    assert cell.flop_rule()(cell.config, 3) == 5.0 * fwd * 8


def test_flops_py_keeps_no_family_switch():
    src = open(os.path.join(manifest_lib.PERF_DIR, "flops.py")).read()
    assert "flops_family" not in src.split('"""', 2)[2]
    assert not hasattr(flops, "train_flops_per_env_step")


def test_unknown_device_kind_is_an_error():
    assert flops.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.load_peaks("cpu")
    with pytest.raises(KeyError):
        flops.load_peaks("source")
