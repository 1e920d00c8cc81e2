"""Model catalog: space + config → model / action-distribution.

Counterpart of the reference's ``rllib/models/catalog.py:195`` (ModelCatalog:
``get_action_dist :212``, ``get_model_v2 :414``, ``get_preprocessor :768``).
Returns flax module instances plus a distribution *class*; policies
instantiate distributions from the model's ``dist_inputs`` output inside jit.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple, Type

import numpy as np

from ray_tpu.models.base import RTModel
from ray_tpu.models.cnn import VisionNet, get_filter_config
from ray_tpu.models.fcnet import FCNet
from ray_tpu.models.rnn import LSTMWrapper
from ray_tpu.models.attention import GTrXLNet
from ray_tpu.models import distributions as dists
from ray_tpu.models.preprocessors import (
    Preprocessor,
    get_preprocessor_for_space,
)

try:
    from gymnasium import spaces
except ImportError:  # pragma: no cover
    spaces = None

# Reference MODEL_DEFAULTS (rllib/models/catalog.py:52).
MODEL_DEFAULTS: Dict[str, Any] = {
    "fcnet_hiddens": [256, 256],
    "fcnet_activation": "tanh",
    "conv_filters": None,
    "conv_activation": "relu",
    "post_fcnet_hiddens": [],
    "post_fcnet_activation": "relu",
    "free_log_std": False,
    "vf_share_layers": False,
    "use_lstm": False,
    "max_seq_len": 20,
    "lstm_cell_size": 256,
    "lstm_use_prev_action": False,
    "lstm_use_prev_reward": False,
    "use_attention": False,
    "attention_num_transformer_units": 1,
    "attention_dim": 64,
    "attention_num_heads": 2,
    "attention_head_dim": 32,
    "attention_memory_inference": 50,
    "attention_memory_training": 50,
    "attention_position_wise_mlp_dim": 32,
    "attention_init_gru_gate_bias": 2.0,
    "custom_model": None,
    "custom_model_config": {},
    "custom_action_dist": None,
    "dtype": None,  # None → per-model default (bf16 convs, f32 mlps)
    # decoder-style transformer torso (models/transformer.py):
    # tensor-parallel over the mesh's "model" axis when
    # AlgorithmConfig.sharding(model_parallel=...) builds a 2-D mesh
    "use_transformer": False,
    "transformer_num_layers": 2,
    "transformer_dim": 64,
    "transformer_num_heads": 4,
    "transformer_head_dim": None,  # None → dim // num_heads
    "transformer_ff_dim": None,  # None → 4 * dim
    "transformer_seq_len": 8,
    # per-leaf placement override (ordered (pattern, spec) rules —
    # sharding.specs grammar); None → the model class's own rules
    "partition_rules": None,
    # decoder language model composed from a layer pattern
    # (models/sequence_lm): observation = the last token id, action
    # = the next token. "sequence_lm" is the architecture under the
    # key names of a Hugging Face config.json (hidden_size,
    # num_hidden_layers, full_attention_interval or layer_types, the
    # attention / linear_* / expert sizes, max_position_embeddings =
    # the longest episode), plus the chip's share: "router_outputs"
    # (experts the router scores) and "experts_held" ([first, count]
    # of them computed here). The vocabulary held is the action
    # space's size; "max_seq_len" is the fragment the learn form runs;
    # "dtype" the operands of its products (None: bfloat16).
    "use_sequence_lm": False,
    "sequence_lm": None,
}

_custom_models: Dict[str, Type[RTModel]] = {}
_custom_action_dists: Dict[str, type] = {}


class ModelCatalog:
    """Static registry, mirroring reference catalog.py:195."""

    @staticmethod
    def register_custom_model(name: str, model_cls: Type[RTModel]) -> None:
        _custom_models[name] = model_cls

    @staticmethod
    def register_custom_action_dist(name: str, dist_cls: type) -> None:
        _custom_action_dists[name] = dist_cls

    @staticmethod
    def get_preprocessor_for_space(obs_space) -> Preprocessor:
        return get_preprocessor_for_space(obs_space)

    @staticmethod
    def get_action_dist(
        action_space, config: Optional[Dict] = None, dist_type: Optional[str] = None
    ) -> Tuple[type, int]:
        """→ (dist_class, required model output size).
        Reference catalog.py:212."""
        config = {**MODEL_DEFAULTS, **(config or {})}
        if config.get("custom_action_dist"):
            cls = _custom_action_dists[config["custom_action_dist"]]
            return cls, cls.required_model_output_shape(action_space)
        if isinstance(action_space, spaces.Discrete):
            return dists.Categorical, int(action_space.n)
        if isinstance(action_space, spaces.Box):
            size = int(np.prod(action_space.shape))
            if dist_type == "squashed_gaussian":
                low = float(np.min(action_space.low))
                high = float(np.max(action_space.high))
                cls = functools.partial(
                    dists.SquashedGaussian, low=low, high=high
                )
                return cls, size * 2
            if dist_type == "deterministic":
                return dists.Deterministic, size
            return dists.DiagGaussian, size * 2
        if isinstance(action_space, spaces.MultiDiscrete):
            lens = tuple(int(n) for n in action_space.nvec)
            cls = functools.partial(dists.MultiCategorical, input_lens=lens)
            return cls, int(sum(lens))
        if isinstance(action_space, spaces.MultiBinary):
            return dists.Bernoulli, int(action_space.n)
        raise NotImplementedError(
            f"Unsupported action space: {action_space}"
        )

    @staticmethod
    def get_model(
        obs_space,
        action_space,
        num_outputs: int,
        model_config: Optional[Dict] = None,
    ) -> RTModel:
        """→ flax module instance. Reference get_model_v2 (catalog.py:414)."""
        cfg = {**MODEL_DEFAULTS, **(model_config or {})}

        if cfg.get("custom_model"):
            cm = cfg["custom_model"]
            cls = _custom_models[cm] if isinstance(cm, str) else cm
            return cls(num_outputs=num_outputs, **cfg["custom_model_config"])

        obs_shape = obs_space.shape
        is_image = len(obs_shape) == 3

        if cfg["use_sequence_lm"]:
            from ray_tpu.models.sequence_lm import SequenceLM

            return SequenceLM(
                num_outputs, cfg["sequence_lm"], dtype=cfg["dtype"] or "bfloat16"
            )
        if cfg["use_transformer"]:
            from ray_tpu.models.transformer import TransformerPolicyNet

            cls = TransformerPolicyNet
            if cfg.get("partition_rules"):
                cls = cls.with_logical_rules(cfg["partition_rules"])
            return cls(
                num_outputs=num_outputs,
                d_model=cfg["transformer_dim"],
                num_layers=cfg["transformer_num_layers"],
                num_heads=cfg["transformer_num_heads"],
                head_dim=cfg["transformer_head_dim"],
                ff_dim=cfg["transformer_ff_dim"],
                seq_len=cfg["transformer_seq_len"],
                dtype_=cfg["dtype"] or "float32",
            )
        if cfg["use_lstm"]:
            return LSTMWrapper(
                num_outputs=num_outputs,
                cell_size=cfg["lstm_cell_size"],
                hiddens=tuple(cfg["fcnet_hiddens"]),
                activation=cfg["fcnet_activation"],
                use_prev_action=cfg["lstm_use_prev_action"],
                use_prev_reward=cfg["lstm_use_prev_reward"],
            )
        if cfg["use_attention"]:
            return GTrXLNet(
                num_outputs=num_outputs,
                attention_dim=cfg["attention_dim"],
                num_transformer_units=cfg["attention_num_transformer_units"],
                num_heads=cfg["attention_num_heads"],
                head_dim=cfg["attention_head_dim"],
                memory_len=cfg["attention_memory_training"],
                position_wise_mlp_dim=cfg["attention_position_wise_mlp_dim"],
                init_gru_gate_bias=cfg["attention_init_gru_gate_bias"],
            )
        if is_image:
            filters = cfg["conv_filters"] or get_filter_config(obs_shape)
            return VisionNet(
                num_outputs=num_outputs,
                conv_filters=tuple(
                    (int(c), tuple(k) if isinstance(k, (list, tuple)) else (k, k),
                     tuple(s) if isinstance(s, (list, tuple)) else (s, s))
                    for c, k, s in filters
                ),
                conv_activation=cfg["conv_activation"],
                post_fcnet_hiddens=tuple(cfg["post_fcnet_hiddens"] or [512]),
                post_fcnet_activation=cfg["post_fcnet_activation"],
                vf_share_layers=True,
                dtype_=cfg["dtype"] or "bfloat16",
            )
        return FCNet(
            num_outputs=num_outputs,
            hiddens=tuple(cfg["fcnet_hiddens"]),
            activation=cfg["fcnet_activation"],
            vf_share_layers=cfg["vf_share_layers"],
            free_log_std=cfg["free_log_std"],
            dtype_=cfg["dtype"] or "float32",
        )
