"""A decoder language model composed from per-layer kinds, as the policy
of a token-level RL problem: observation = the last token id, action =
the next token, a value head beside the output head. ``config`` carries
the key names of the published ``config.json`` each kind comes from;
``config.describe`` is the one reader of them.

A block is two sublayers, a MIXER and a FEED-FORWARD, each with its own
norm (the model's, ``kinds.Norm``: the zero-centred RMSNorm ``rms(x) = x
* rsqrt(mean(x^2) + eps) * (1 + w)``, or ``phi4flash``'s LayerNorm with a
bias), each applied through the block's RESIDUAL kind; or ONE of the two
(``nemotron_h``: every character of ``hybrid_override_pattern`` is a
block of one sublayer under one norm; the half it lacks is a
``NoSublayer``, which has no norm, no leaf and no state, and the block
runs the half it has). A kind is one frozen
description with one protocol (``kinds.Kind``), its published source
and arithmetic on its docstring; ``SequenceLM`` (``model.py``) asks the
kinds in loops and knows none by name. What is the model's: the pattern
and its groups, the ONE channel between layers besides the stream (what a
mixer ``exports`` under a name, a later mixer ``imports``:
docs/policy_state.md, "State that one layer makes and others read"), the embedding and the head, the residual's wiring around
the two sublayers, the learn form's grouping, and the state tuple.

The Granite multipliers, each applied only where the config states it:
``x0 = embedding_multiplier * E[token]``, each sublayer's output times
``residual_multiplier`` before it is added, logits over
``logits_scaling``. With ``tie_word_embeddings`` the output head IS the
embedding (``logits = rms(x) E^T``): the tree has no ``head`` leaf, the
table's gradient comes from both uses and Adam holds one pair of
moments for it.

A run of consecutive layers of a ``stacked`` kind (``"mamba"``) is ONE
group of the parameter tree, ``"layers_<first>_<last>"``, its leaves
stacked on a leading layer axis, and one ``lax.scan`` over them in
either form (a state-space block between other kinds' blocks is a run of
ONE layer, ``"layers_<n>_<n>"``: its state is still the stacked leaf the
one-token kernel takes). Every other layer is its own group
``"layer_<n>"``.

State (``initial_state``; one row per stream, a flat tuple): for each
group in order the leaves its mixer's ``state_shapes`` names (a stacked
run's with the layer axis after the stream's); last, the stream's
position. ``apply`` has two forms that are the same function of the
same weights: ``T == 1`` is the recurrence (one token, state in and
out: the rollout lane's step), ``T > 1`` runs a fragment from a stored
start state (the delta rules and the state-space layers in chunks, attention
over the stored keys plus the fragment's own, ``resets`` opening a new
episode inside it: the learn program's form).

Precision: float32 parameters; the projections, expert products, the
head, the attention products and a learned index's two projections and
score product take bfloat16 operands and accumulate in float32; the
index's weights, relu, sum over heads and top-k, the router, softmax, top-k, ``g``, ``beta``, the delta
rules' state, the hyper-connection maps and mixes and every norm are float32
(the router, the maps' projection and the delta rule at precision
"highest"); the state-space recurrence in both forms, ``dt``, ``A``,
its convolution and the multipliers float32 (the recurrence at
precision "highest").

Not a flax module (cf. ``models/transformer.py``): plain-dict params,
two levels deep, ``{"layer_0": {"in_proj_qkvz": ...}, ...}``.
"""

from ray_tpu.models.sequence_lm.config import (
    Segment, attention_layers_of, describe, layer_types_of)
from ray_tpu.models.sequence_lm.kinds import (
    AttentionLayer, DeltaNetLayer, DenseLayer, EvaLayer, ExpertLayer, GatedMemoryLayer,
    HyperResidual, Indexer, KDALayer, LatentLayer, MambaLayer, Norm, NoSublayer,
    PlainResidual, SelectiveScanLayer)
from ray_tpu.models.sequence_lm.model import SequenceLM

__all__ = [
    "SequenceLM", "Segment", "describe", "layer_types_of", "attention_layers_of",
    "AttentionLayer", "Indexer", "LatentLayer", "DeltaNetLayer", "KDALayer",
    "MambaLayer", "EvaLayer",
    "SelectiveScanLayer", "GatedMemoryLayer", "DenseLayer", "ExpertLayer",
    "NoSublayer", "PlainResidual", "HyperResidual", "Norm",
]
