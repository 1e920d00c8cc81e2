"""The sequence model's surface, pinned: what ``perf/reference/*.to_policy_tree``
maps onto (group and leaf names and shapes of the parameter tree), what the
lanes carry (the state tuple's order, shapes and dtypes), what the learn
program reports (the keys of ``stats_out`` in both forms) and what the scope
readers sum under (the named scopes of ``jax.make_jaxpr(model.apply)``'s name
stacks), each at the five families' small test configs and recorded from the
commit before the model became a package of kinds (PR 45, parent 1a90899).
A change that moves any of these moves the benchmark's references or its
per-layer metrics with it, and has to say so here. Since PR 64 the gated
delta rule alone sits under ``linear_attn/rule`` in both forms (what
``linear_attn.rule_device_ms_per_update`` reads); ``linear_attn`` still
holds everything it held.
"""
import importlib.util
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp

from ray_tpu.models.sequence_lm import SequenceLM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB = 64

SURFACE = {
    "sequence_lm": {
        "params": {
            "embed": {'embedding': (64, 64)},
            "final_norm": {'weight': (64,)},
            "head": {'kernel': (64, 64)},
            "layer_0": {'input_norm': (64,), 'post_norm': (64,), 'router': (64, 8), 'experts_gate': (2,
             64, 32), 'experts_up': (2, 64, 32), 'experts_down': (2, 32, 64), 'shared_gate':
             (64, 32), 'shared_up': (64, 32), 'shared_down': (32, 64), 'shared_expert_gate':
             (64, 1), 'in_proj_qkvz': (64, 96), 'in_proj_ba': (64, 8), 'conv': (64, 4),
             'A_log': (4,), 'dt_bias': (4,), 'gdn_norm': (8,), 'out_proj': (32, 64)},
            "layer_1": {'input_norm': (64,), 'post_norm': (64,), 'router': (64, 8), 'experts_gate': (2,
             64, 32), 'experts_up': (2, 64, 32), 'experts_down': (2, 32, 64), 'shared_gate':
             (64, 32), 'shared_up': (64, 32), 'shared_down': (32, 64), 'shared_expert_gate':
             (64, 1), 'in_proj_qkvz': (64, 96), 'in_proj_ba': (64, 8), 'conv': (64, 4),
             'A_log': (4,), 'dt_bias': (4,), 'gdn_norm': (8,), 'out_proj': (32, 64)},
            "layer_2": {'input_norm': (64,), 'post_norm': (64,), 'router': (64, 8), 'experts_gate': (2,
             64, 32), 'experts_up': (2, 64, 32), 'experts_down': (2, 32, 64), 'shared_gate':
             (64, 32), 'shared_up': (64, 32), 'shared_down': (32, 64), 'shared_expert_gate':
             (64, 1), 'in_proj_qkvz': (64, 96), 'in_proj_ba': (64, 8), 'conv': (64, 4),
             'A_log': (4,), 'dt_bias': (4,), 'gdn_norm': (8,), 'out_proj': (32, 64)},
            "layer_3": {'input_norm': (64,), 'post_norm': (64,), 'router': (64, 8), 'experts_gate': (2,
             64, 32), 'experts_up': (2, 64, 32), 'experts_down': (2, 32, 64), 'shared_gate':
             (64, 32), 'shared_up': (64, 32), 'shared_down': (32, 64), 'shared_expert_gate':
             (64, 1), 'q_proj': (64, 128), 'k_proj': (64, 32), 'v_proj': (64, 32), 'o_proj':
             (64, 64), 'q_norm': (16,), 'k_norm': (16,)},
            "value": {'kernel': (64, 1), 'bias': (1,)},
        },
        "state": [((2, 4, 8, 8), 'float32'), ((2, 3, 64), 'float32'), ((2, 4, 8, 8), 'float32'), ((2,
         3, 64), 'float32'), ((2, 4, 8, 8), 'float32'), ((2, 3, 64), 'float32'), ((2, 48,
         32), 'float32'), ((2, 48, 32), 'float32'), ((2,), 'int32')],
        "fragment_stats": ['attn_decode_key_blocks_skipped_share', 'attn_key_blocks_skipped_share',
         'moe_decode_held_experts_touched_share', 'moe_max_tokens_per_held_expert',
         'moe_rows_computed_share', 'moe_slots_on_absent_experts',
         'moe_tokens_per_held_expert'],
        "step_stats": ['moe_decode_held_experts_touched_share', 'moe_max_tokens_per_held_expert',
         'moe_rows_computed_share', 'moe_slots_on_absent_experts',
         'moe_tokens_per_held_expert'],
        "fragment_scopes": ['attn', 'attn/gate', 'attn/out', 'attn/scatter', 'attn/scores', 'head',
         'linear_attn', 'linear_attn/rule', 'moe/experts', 'moe/route', 'moe/shared'],
        "step_scopes": ['attn', 'attn/gate', 'attn/out', 'attn/scatter', 'attn/scores', 'head',
         'linear_attn', 'linear_attn/rule', 'moe/experts', 'moe/route', 'moe/shared'],
    },
    "latent_lm": {
        "params": {
            "embed": {'embedding': (64, 32)},
            "final_norm": {'weight': (32,)},
            "head": {'kernel': (32, 64)},
            "layer_0": {'input_norm': (32,), 'post_norm': (32,), 'mlp_gate': (32, 48), 'mlp_up': (32,
             48), 'mlp_down': (48, 32), 'hc_mixer_norm': (96,), 'hc_mixer_phi': (96, 15),
             'hc_mixer_a': (3,), 'hc_mixer_b': (15,), 'hc_ffn_norm': (96,), 'hc_ffn_phi':
             (96, 15), 'hc_ffn_a': (3,), 'hc_ffn_b': (15,), 'q_a': (32, 20), 'q_a_norm':
             (20,), 'q_b': (20, 96), 'kv_a': (32, 32), 'kv_a_norm': (24,), 'kv_b': (24,
             112), 'o_proj': (48, 32)},
            "layer_1": {'input_norm': (32,), 'post_norm': (32,), 'router': (32, 8), 'experts_gate': (2,
             32, 16), 'experts_up': (2, 32, 16), 'experts_down': (2, 16, 32), 'shared_gate':
             (32, 16), 'shared_up': (32, 16), 'shared_down': (16, 32), 'select_bias': (8,),
             'hc_mixer_norm': (96,), 'hc_mixer_phi': (96, 15), 'hc_mixer_a': (3,),
             'hc_mixer_b': (15,), 'hc_ffn_norm': (96,), 'hc_ffn_phi': (96, 15), 'hc_ffn_a':
             (3,), 'hc_ffn_b': (15,), 'q_a': (32, 20), 'q_a_norm': (20,), 'q_b': (20, 96),
             'kv_a': (32, 32), 'kv_a_norm': (24,), 'kv_b': (24, 112), 'o_proj': (48, 32)},
            "layer_2": {'input_norm': (32,), 'post_norm': (32,), 'router': (32, 8), 'experts_gate': (2,
             32, 16), 'experts_up': (2, 32, 16), 'experts_down': (2, 16, 32), 'shared_gate':
             (32, 16), 'shared_up': (32, 16), 'shared_down': (16, 32), 'select_bias': (8,),
             'hc_mixer_norm': (96,), 'hc_mixer_phi': (96, 15), 'hc_mixer_a': (3,),
             'hc_mixer_b': (15,), 'hc_ffn_norm': (96,), 'hc_ffn_phi': (96, 15), 'hc_ffn_a':
             (3,), 'hc_ffn_b': (15,), 'q_a': (32, 20), 'q_a_norm': (20,), 'q_b': (20, 96),
             'kv_a': (32, 32), 'kv_a_norm': (24,), 'kv_b': (24, 112), 'o_proj': (48, 32)},
            "value": {'kernel': (32, 1), 'bias': (1,)},
        },
        "state": [((2, 48, 32), 'float32'), ((2, 48, 32), 'float32'), ((2, 48, 32), 'float32'),
         ((2,), 'int32')],
        "fragment_stats": ['attn_decode_key_blocks_skipped_share', 'attn_key_blocks_skipped_share',
         'hc_res_col_sum_err_max', 'hc_res_row_sum_err_max', 'moe_held_load',
         'moe_rows_computed_share', 'moe_slots_on_absent_experts'],
        "step_stats": ['hc_res_col_sum_err_max', 'hc_res_row_sum_err_max', 'moe_held_load',
         'moe_rows_computed_share', 'moe_slots_on_absent_experts'],
        "fragment_scopes": ['hc', 'head', 'mla', 'mlp', 'moe/experts', 'moe/route', 'moe/shared'],
        "step_scopes": ['hc', 'head', 'mla', 'mla/absorb', 'mla/out', 'mla/scores', 'mlp', 'moe/experts',
         'moe/route', 'moe/shared'],
    },
    "ssm_lm": {
        "params": {
            "embed": {'embedding': (64, 32)},
            "final_norm": {'weight': (32,)},
            "layer_2": {'input_norm': (32,), 'post_norm': (32,), 'mlp_gate': (32, 48), 'mlp_up': (32,
             48), 'mlp_down': (48, 32), 'q_proj': (32, 32), 'k_proj': (32, 16), 'v_proj':
             (32, 16), 'o_proj': (32, 32)},
            "layers_0_1": {'input_norm': (2, 32), 'post_norm': (2, 32), 'mlp_gate': (2, 32, 48), 'mlp_up':
             (2, 32, 48), 'mlp_down': (2, 48, 32), 'in_proj': (2, 32, 168), 'conv': (2, 96,
             4), 'dt_bias': (2, 8), 'A_log': (2, 8), 'D': (2, 8), 'ssm_norm': (2, 64),
             'out_proj': (2, 64, 32), 'conv_bias': (2, 96)},
            "layers_3_5": {'input_norm': (3, 32), 'post_norm': (3, 32), 'mlp_gate': (3, 32, 48), 'mlp_up':
             (3, 32, 48), 'mlp_down': (3, 48, 32), 'in_proj': (3, 32, 168), 'conv': (3, 96,
             4), 'dt_bias': (3, 8), 'A_log': (3, 8), 'D': (3, 8), 'ssm_norm': (3, 64),
             'out_proj': (3, 64, 32), 'conv_bias': (3, 96)},
            "value": {'kernel': (32, 1), 'bias': (1,)},
        },
        "state": [((2, 2, 8, 8, 16), 'float32'), ((2, 2, 3, 96), 'float32'), ((2, 48, 16),
         'float32'), ((2, 48, 16), 'float32'), ((2, 3, 8, 8, 16), 'float32'), ((2, 3, 3,
         96), 'float32'), ((2,), 'int32')],
        "fragment_stats": ['attn_decode_key_blocks_skipped_share', 'attn_key_blocks_skipped_share',
         'ssm_dt_max'],
        "step_stats": ['ssm_dt_max'],
        "fragment_scopes": ['attn', 'attn/out', 'attn/scatter', 'attn/scores', 'head', 'mlp', 'ssm/conv',
         'ssm/in', 'ssm/out', 'ssm/step'],
        "step_scopes": ['attn', 'attn/out', 'attn/scatter', 'attn/scores', 'head', 'mlp', 'ssm/carry',
         'ssm/conv', 'ssm/in', 'ssm/out', 'ssm/step'],
    },
    "window_lm": {
        "params": {
            "embed": {'embedding': (64, 32)},
            "final_norm": {'weight': (32,)},
            "head": {'kernel': (32, 64)},
            "layer_0": {'input_norm': (32,), 'post_norm': (32,), 'router': (32, 8), 'experts_gate': (2,
             32, 16), 'experts_up': (2, 32, 16), 'experts_down': (2, 16, 32), 'q_proj': (32,
             32), 'k_proj': (32, 16), 'v_proj': (32, 16), 'o_proj': (32, 32)},
            "layer_1": {'input_norm': (32,), 'post_norm': (32,), 'router': (32, 8), 'experts_gate': (2,
             32, 16), 'experts_up': (2, 32, 16), 'experts_down': (2, 16, 32), 'q_proj': (32,
             32), 'k_proj': (32, 16), 'v_proj': (32, 16), 'o_proj': (32, 32)},
            "layer_2": {'input_norm': (32,), 'post_norm': (32,), 'router': (32, 8), 'experts_gate': (2,
             32, 16), 'experts_up': (2, 32, 16), 'experts_down': (2, 16, 32), 'q_proj': (32,
             32), 'k_proj': (32, 16), 'v_proj': (32, 16), 'o_proj': (32, 32)},
            "layer_3": {'input_norm': (32,), 'post_norm': (32,), 'router': (32, 8), 'experts_gate': (2,
             32, 16), 'experts_up': (2, 32, 16), 'experts_down': (2, 16, 32), 'q_proj': (32,
             32), 'k_proj': (32, 16), 'v_proj': (32, 16), 'o_proj': (32, 32)},
            "value": {'kernel': (32, 1), 'bias': (1,)},
        },
        "state": [((2, 32, 16), 'float32'), ((2, 32, 16), 'float32'), ((2, 8, 16), 'float32'), ((2,
         8, 16), 'float32'), ((2, 8, 16), 'float32'), ((2, 8, 16), 'float32'), ((2, 8, 16),
         'float32'), ((2, 8, 16), 'float32'), ((2,), 'int32')],
        "fragment_stats": ['attn_decode_key_blocks_skipped_share', 'attn_key_blocks_skipped_share',
         'moe_decode_held_experts_touched_share', 'moe_max_tokens_per_held_expert',
         'moe_rows_computed_share', 'moe_slots_on_absent_experts',
         'moe_tokens_per_held_expert', 'window_rows_seen_mean'],
        "step_stats": ['moe_decode_held_experts_touched_share', 'moe_max_tokens_per_held_expert',
         'moe_rows_computed_share', 'moe_slots_on_absent_experts',
         'moe_tokens_per_held_expert', 'window_rows_seen_mean'],
        "fragment_scopes": ['attn', 'attn/out', 'attn/scatter', 'attn/scores', 'head', 'moe/experts',
         'moe/route', 'swa', 'swa/out', 'swa/scatter', 'swa/scores'],
        "step_scopes": ['attn', 'attn/out', 'attn/scatter', 'attn/scores', 'head', 'moe/experts',
         'moe/route', 'swa', 'swa/out', 'swa/scatter', 'swa/scores'],
    },
    "mixed_attention_lm": {
        "params": {
            "embed": {'embedding': (64, 32)},
            "final_norm": {'weight': (32,)},
            "head": {'kernel': (32, 64)},
            "layer_0": {'input_norm': (32,), 'post_norm': (32,), 'mlp_gate': (32, 48), 'mlp_up': (32,
             48), 'mlp_down': (48, 32), 'q_proj': (32, 64), 'k_proj': (32, 32), 'v_proj':
             (32, 32), 'o_proj': (64, 32), 'q_norm': (16,), 'k_norm': (16,), 'g_proj': (32,
             4)},
            "layer_1": {'input_norm': (32,), 'post_norm': (32,), 'router': (32, 8), 'experts_gate': (2,
             32, 16), 'experts_up': (2, 32, 16), 'experts_down': (2, 16, 32), 'shared_gate':
             (32, 16), 'shared_up': (32, 16), 'shared_down': (16, 32), 'q_proj': (32, 96),
             'k_proj': (32, 32), 'v_proj': (32, 32), 'o_proj': (96, 32), 'q_norm': (16,),
             'k_norm': (16,), 'g_proj': (32, 6)},
            "layer_2": {'input_norm': (32,), 'post_norm': (32,), 'router': (32, 8), 'experts_gate': (2,
             32, 16), 'experts_up': (2, 32, 16), 'experts_down': (2, 16, 32), 'shared_gate':
             (32, 16), 'shared_up': (32, 16), 'shared_down': (16, 32), 'q_proj': (32, 96),
             'k_proj': (32, 32), 'v_proj': (32, 32), 'o_proj': (96, 32), 'q_norm': (16,),
             'k_norm': (16,), 'g_proj': (32, 6)},
            "layer_3": {'input_norm': (32,), 'post_norm': (32,), 'router': (32, 8), 'experts_gate': (2,
             32, 16), 'experts_up': (2, 32, 16), 'experts_down': (2, 16, 32), 'shared_gate':
             (32, 16), 'shared_up': (32, 16), 'shared_down': (16, 32), 'q_proj': (32, 96),
             'k_proj': (32, 32), 'v_proj': (32, 32), 'o_proj': (96, 32), 'q_norm': (16,),
             'k_norm': (16,), 'g_proj': (32, 6)},
            "layer_4": {'input_norm': (32,), 'post_norm': (32,), 'router': (32, 8), 'experts_gate': (2,
             32, 16), 'experts_up': (2, 32, 16), 'experts_down': (2, 16, 32), 'shared_gate':
             (32, 16), 'shared_up': (32, 16), 'shared_down': (16, 32), 'q_proj': (32, 64),
             'k_proj': (32, 32), 'v_proj': (32, 32), 'o_proj': (64, 32), 'q_norm': (16,),
             'k_norm': (16,), 'g_proj': (32, 4)},
            "value": {'kernel': (32, 1), 'bias': (1,)},
        },
        "state": [((2, 32, 32), 'float32'), ((2, 32, 32), 'float32'), ((2, 8, 32), 'float32'), ((2,
         8, 32), 'float32'), ((2, 8, 32), 'float32'), ((2, 8, 32), 'float32'), ((2, 8, 32),
         'float32'), ((2, 8, 32), 'float32'), ((2, 32, 32), 'float32'), ((2, 32, 32),
         'float32'), ((2,), 'int32')],
        "fragment_stats": ['attn_decode_key_blocks_skipped_share', 'attn_key_blocks_skipped_share',
         'moe_decode_held_experts_touched_share', 'moe_max_tokens_per_held_expert',
         'moe_rows_computed_share', 'moe_slots_on_absent_experts',
         'moe_tokens_per_held_expert', 'window_rows_seen_mean'],
        "step_stats": ['moe_decode_held_experts_touched_share', 'moe_max_tokens_per_held_expert',
         'moe_rows_computed_share', 'moe_slots_on_absent_experts',
         'moe_tokens_per_held_expert', 'window_rows_seen_mean'],
        "fragment_scopes": ['attn', 'attn/gate', 'attn/out', 'attn/scatter', 'attn/scores', 'head', 'mlp',
         'moe/experts', 'moe/route', 'moe/shared', 'swa', 'swa/gate', 'swa/out',
         'swa/scatter', 'swa/scores'],
        "step_scopes": ['attn', 'attn/gate', 'attn/out', 'attn/scatter', 'attn/scores', 'head', 'mlp',
         'moe/experts', 'moe/route', 'moe/shared', 'swa', 'swa/gate', 'swa/out',
         'swa/scatter', 'swa/scores'],
    },
    # a model that commits a block a step (PR 47): its fragment form is the
    # update's replay of a trace (a clean and the noisy passes), its step form
    # a block with the commit
    "block_diffusion_lm":
    {'params': {'embed': {'embedding': (64, 64)},
                'final_norm': {'weight': (64,)},
                'head': {'kernel': (64, 64)},
                'value': {'kernel': (64, 1), 'bias': (1,)},
                'layer_0': {'input_norm': (64,),
                            'post_norm': (64,),
                            'router': (64, 8),
                            'experts_gate': (4, 64, 32),
                            'experts_up': (4, 64, 32),
                            'experts_down': (4, 32, 64),
                            'q_proj': (64, 64),
                            'k_proj': (64, 32),
                            'v_proj': (64, 32),
                            'o_proj': (64, 64),
                            'q_norm': (16,),
                            'k_norm': (16,)},
                'layer_1': {'input_norm': (64,),
                            'post_norm': (64,),
                            'router': (64, 8),
                            'experts_gate': (4, 64, 32),
                            'experts_up': (4, 64, 32),
                            'experts_down': (4, 32, 64),
                            'q_proj': (64, 64),
                            'k_proj': (64, 32),
                            'v_proj': (64, 32),
                            'o_proj': (64, 64),
                            'q_norm': (16,),
                            'k_norm': (16,)}},
     'state': [((2, 32, 32), 'float32'),
               ((2, 32, 32), 'float32'),
               ((2, 32, 32), 'float32'),
               ((2, 32, 32), 'float32'),
               ((2,), 'int32')],
     'fragment_stats': ['attn_decode_key_blocks_skipped_share',
                        'attn_key_blocks_skipped_share',
                        'diffusion_clean_token_passes',
                        'diffusion_commit_confidence_mean',
                        'diffusion_noisy_token_passes',
                        'moe_decode_held_experts_touched_share',
                        'moe_max_tokens_per_held_expert',
                        'moe_rows_computed_share',
                        'moe_slots_on_absent_experts',
                        'moe_tokens_per_held_expert'],
     'fragment_scopes': ['clean',
                         'clean/p/attn',
                         'clean/p/attn/out',
                         'clean/p/attn/scatter',
                         'clean/p/attn/scores',
                         'clean/p/moe/experts',
                         'clean/p/moe/route',
                         'head',
                         'noisy',
                         'noisy/p/attn',
                         'noisy/p/attn/out',
                         'noisy/p/attn/scatter',
                         'noisy/p/attn/scores',
                         'noisy/p/moe/experts',
                         'noisy/p/moe/route'],
     'step_stats': ['moe_decode_held_experts_touched_share',
                    'moe_max_tokens_per_held_expert',
                    'moe_rows_computed_share',
                    'moe_slots_on_absent_experts',
                    'moe_tokens_per_held_expert'],
     'step_scopes': ['attn',
                     'attn/out',
                     'attn/scatter',
                     'attn/scores',
                     'head',
                     'moe/experts',
                     'moe/route']},
}


def _small_config(family):
    path = os.path.join(ROOT, "tests", f"test_{family}.py")
    spec = importlib.util.spec_from_file_location("surface_" + family, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.small_config()["algo_config"]["model"]["sequence_lm"]


def _name_stacks(jaxpr, prefix=""):
    """Every equation's name stack, those of nested jaxprs under their
    equation's."""
    out = set()
    for eqn in jaxpr.eqns:
        stack = prefix + str(eqn.source_info.name_stack)
        out.add(stack)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list)) else (value,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out |= _name_stacks(inner, stack + "/" if stack else "")
    return out


def _scopes(stacks, prefix):
    """The model's scopes under ``prefix``, without ``einsum``'s own jit
    names."""
    out = set()
    for stack in stacks:
        parts = [c for c in stack.split("/") if c and "->" not in c]
        if parts[1:]:
            assert parts[0] == prefix, stack
            out.add("/".join(parts[1:]))
    return sorted(out)


@pytest.mark.parametrize("family", sorted(SURFACE))
def test_the_surface_is_the_pinned_one(family):
    want = SURFACE[family]
    model = SequenceLM(VOCAB, _small_config(family), dtype="float32")
    model.learn_streams = 2  # 4 streams: two groups inside a block
    assert model.param_shapes() == want["params"]
    assert [(s.shape, s.dtype.name) for s in model.initial_state(2)] == want["state"]
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_map(lambda x: x.shape, params) == want["params"]
    state = model.initial_state(4)
    for form, t in (("fragment", 16), ("step", model.tokens_per_step)):
        stats = {}
        # a model that commits a block a step: its update replays a
        # trace, its lane's form is a block with the commit
        block_form = {} if model.tokens_per_step == 1 else (
            {"commit": True} if form == "step"
            else {"trace": jnp.zeros((4, t), jnp.int32)})

        def apply(p, tokens, state, fresh):
            stats.clear()
            return model.apply(
                p, tokens, state, resets=fresh, scope="p", stats_out=stats,
                **block_form)

        jaxpr = jax.make_jaxpr(apply)(
            params, jnp.zeros((4, t), jnp.int32), state, jnp.zeros((4, t)))
        assert sorted(stats) == want[form + "_stats"], form
        assert _scopes(_name_stacks(jaxpr.jaxpr), "p") == want[form + "_scopes"], form
