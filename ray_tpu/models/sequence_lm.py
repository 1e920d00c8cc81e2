"""A decoder language model composed from per-layer kinds, as the policy
of a token-level RL problem: observation = the last token id, action =
the next token, a value head beside the output head. ``config`` carries
the key names of the published ``config.json`` each kind comes from.

A block is two sublayers, a MIXER and a FEED-FORWARD, each with its own
zero-centred RMSNorm ``rms(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)``,
each applied through the block's RESIDUAL kind.

Mixer kinds (``layer_types``, under the published names, its first
``num_hidden_layers`` entries; every ``full_attention_interval``-th
layer full attention where it is not stated; all latent where the
config has ``kv_lora_rank``; window and position-free layers by
``sliding_window_layout`` where the config has one). The three softmax
kinds (``"full_attention"``, ``"attention"``, ``"sliding_attention"``)
are ONE body, ``_attention``, over a per-layer description
(:class:`AttentionLayer`, read from the config by
:func:`attention_layers_of`): query heads, window, RoPE (how much of
the head, base, YaRN), gate and q/k norm are the layer's own:

- ``"full_attention"`` (Hugging Face ``qwen3_next``,
  ``Qwen3NextAttention``): gated softmax attention. One projection gives
  each head its query and an output gate, ``k`` and ``v`` come for fewer
  KV heads (GQA), ``q`` and ``k`` are RMS-normed over the head, RoPE
  turns the first ``partial_rotary_factor`` of the head, and the
  attention output is multiplied by ``sigmoid(gate)`` before the output
  projection. No biases;
- ``"linear_attention"`` (``Qwen3NextGatedDeltaNet``; Yang et al.,
  arXiv:2412.06464): one projection gives ``q, k, v, z``, another ``b,
  a``; a causal depthwise convolution (width ``linear_conv_kernel_dim``)
  and SiLU over the channels of ``(q, k, v)``; ``beta = sigmoid(b)``,
  ``g = -exp(A_log) * softplus(a + dt_bias)``; ``q`` and ``k``
  L2-normalised; the gated delta rule (``ops/deltanet.py``) per value
  head; ``rms(o) * w * silu(z)`` per head, then the output projection;
- ``"latent_attention"`` (DeepSeek-V3's ``DeepseekV3Attention``,
  arXiv:2412.19437, with YaRN as that file applies it): ``c_q = rms(x
  W_qa)``, ``q = c_q W_qb`` per head ``[q_nope | q_pe]``; ``[c_kv |
  k_pe] = x W_kva``, ``c_kv = rms(c_kv)``, one roped ``k_pe`` for all
  heads; ``[k_nope | v]`` per head ``= c_kv W_kvb``; ``score = (q_nope .
  k_nope + rope(q_pe) . k_pe) * s``, ``s = (nope + rope)^-1/2 * m^2``,
  causal softmax, ``o = P v``, output projection. The query/key product
  is ``nope + rope`` wide, the value product ``v_head_dim``
  (``ops/latent_attention.py`` holds both forms);
- ``"mamba"`` (Mamba-2 as Hugging Face ``granitemoehybrid`` holds it;
  Dao & Gu, arXiv:2405.21060): ``[z | x | B | C | dt] = h W_in`` (no
  bias); a causal depthwise convolution (width ``mamba_d_conv``) WITH a
  bias and SiLU over the channels of ``(x, B, C)``; ``dt = softplus(dt
  + dt_bias)``, ``A = -exp(A_log)`` a head; per head ``S <- exp(dt A) S
  + dt x B^T``, ``y = S C + D x`` (``ops/ssd.py``; ``B`` and ``C`` are
  shared by every head: ``mamba_n_groups`` 1); ``rms(y * silu(z)) * w``
  over the whole inner width, then the output projection;
- ``"attention"`` (``GraniteMoeHybridAttention`` with
  ``position_embedding_type: nope``): plain GQA softmax attention with
  NO positions, no q/k norm and no gate, its scores scaled by
  ``attention_multiplier`` (``head^-1/2`` where the config states
  none). No biases;
- ``"sliding_attention"`` (SmallThinker's window layers:
  ``sliding_window_layout[l] = 1``, which is also where ``rope_layout``
  turns): GQA softmax attention with RoPE (rotate-half) over the WHOLE
  head, no q/k norm, no gate, scores scaled by ``head^-1/2``; a query
  at position ``p`` sees the keys at ``p - sliding_window_size + 1 ..
  p`` of its episode and nothing older. The layers where the layout
  says 0 are the ``"attention"`` kind above: full depth, no positions.
- Laguna's layers (``model_type: laguna``: ``layer_types`` names them
  ``"full_attention"`` and ``"sliding_attention"``, and the keys beside
  it say what they are): ``num_attention_heads_per_layer`` gives every
  layer its own count of query heads over the same KV heads (48 at full
  depth, 64 in a window of ``sliding_window`` 512); ``rope_parameters``
  holds a block a kind (the full layers YaRN on the first
  ``partial_rotary_factor`` of the head, ``cos`` and ``sin`` times its
  ``attention_factor``; the window layers plain RoPE with another base
  over the whole head); ``gating`` puts an output gate on EVERY
  attention layer, ``sigmoid(x W_g)`` with one number a head and token
  (``g_proj``, a leaf of its own), so a gated layer stands on a ring;
  and a gated layer norms ``q`` and ``k`` over the head.

Feed-forward kinds (``mlp_layer_types``, ``"dense"`` or ``"sparse"`` a
layer, where the config states them; else ``"dense"`` for the first
``first_k_dense_replace`` layers, ``"experts"`` after):

- ``"dense"``: SwiGLU of width ``intermediate_size``
  (``shared_intermediate_size`` where the config states one). A config
  that counts no experts (``num_local_experts`` 0, or no such key) has
  NO expert layer: every feed-forward is dense, there is no router and
  ``apply`` reports no expert statistic;
- ``"experts"``: a router over ALL ``router_outputs`` experts, the
  experts this chip HOLDS (``experts_held``: ``[first, count]``;
  ``ops/moe.py``) and a shared expert. ``qwen3_next``: softmax, top-k,
  renormalised, the shared expert times ``sigmoid(x w_s)``. DeepSeek-V3
  (``scoring_func: sigmoid``, ``topk_method: noaux_tc``): a sigmoid each,
  the top-k of ``score + select_bias`` chosen, weights the scores
  without the bias over their sum times ``routed_scaling_factor``, the
  shared expert ungated. ``select_bias`` is a buffer: it lies in the
  parameter tree and the model reads it through ``stop_gradient``.
  SmallThinker (``moe_num_primary_experts``): softmax, top-k,
  renormalised like ``qwen3_next``, but the router reads the LAYER'S
  INPUT, un-normed and before the mixer runs (the experts still take
  ``rms`` of the stream after the mixer); the experts gate with ReLU
  (ReGLU) where the others gate with SiLU (``ops/moe.py``'s
  ``activation``); and there is NO shared expert: the layer has no
  ``shared_*`` leaves and its result is the routed sum alone.
  Laguna (``moe_routed_scaling_factor`` beside ``qwen3_next``'s names
  for the counts and widths): a sigmoid each, top-k with no selection
  bias, the chosen scores over their sum times the factor, and the
  shared expert of ``shared_expert_intermediate_size`` UNGATED.

Residual kinds:

- ``"plain"``: ``x <- x + F(rms(x))``;
- ``"hyper_connection"`` (``hc_mult`` lanes; manifold-constrained
  hyper-connections, arXiv:2512.24880; ``ops/hyper_connection.py``):
  the stream is ``hc_mult`` lanes, ``X <- H_res X + H_post^T F(rms(H_pre
  X))`` with the maps made from the token's own stream and ``H_res``
  through ``hc_sinkhorn_iters`` Sinkhorn rounds. The embedding is
  copied into the lanes and the lanes are summed before the final norm.

The Granite multipliers, each applied only where the config states it:
``x0 = embedding_multiplier * E[token]``, each sublayer's output times
``residual_multiplier`` before it is added, logits over
``logits_scaling``. With ``tie_word_embeddings`` the output head IS the
embedding (``logits = rms(x) E^T``): the tree has no ``head`` leaf, the
table's gradient comes from both uses and Adam holds one pair of
moments for it.

A run of consecutive ``"mamba"`` layers is ONE group of the parameter
tree, ``"layers_<first>_<last>"``, its leaves stacked on a leading
layer axis, and one ``lax.scan`` over them in either form: the run's
layers are traced once (ten layers unrolled compiled for longer than a
run of the benchmark may take). Every other layer is its own group
``"layer_<n>"``.

State (``initial_state``; one row per stream, a flat tuple): for each
linear layer the ``(value heads, dk, dv)`` float32 DeltaNet matrix and
the last ``conv - 1`` inputs of the convolution; for each full layer
the keys and values of the episode so far (bfloat16, ``(positions,
kv heads x head)``, keys stored after norm and RoPE); for each latent
layer ONE leaf of latent rows (bfloat16, ``(positions, kv_lora_rank +
qk_rope_head_dim)``: the normed latent and the roped key part, whatever
the head count); for each RUN of state-space layers two leaves with the
layer axis after the stream's, the ``(layers, heads, head, state)``
float32 matrices and the last ``conv - 1`` inputs of each convolution
(the one-token form reads and writes one layer's slice of them in
place, the fragment form scans over them); for an ``"attention"`` layer
keys and values as for a full layer (no norm, no RoPE); for a
``"sliding_attention"`` layer a RING of keys and values, ``(min(window,
positions), kv heads x head)`` bfloat16 whatever the episode's depth,
keys stored after the q/k norm and RoPE (YaRN's factor included), the
token at position ``p`` in slot ``p mod window`` (docs/policy_state.md,
"The ring"; a row is KV heads x head wide whatever the layer's query
heads); last, the stream's position. ``apply`` has two forms
that are the same function of the same weights: ``T == 1`` is the
recurrence (one token, state in and out: the rollout lane's step; for
latent attention the ABSORBED product against the latent rows), ``T >
1`` runs a fragment from a stored start state (DeltaNet in chunks,
attention over the stored keys plus the fragment's own, latent
attention EXPANDED through ``W_kvb`` a block of streams at a time,
``resets`` opening a new episode inside it: the learn program's form).

Precision: float32 parameters; the projections, expert products, the
head and the attention products take bfloat16 operands and accumulate
in float32; the router, softmax, top-k, ``g``, ``beta``, the DeltaNet
state, the hyper-connection maps and mixes and every norm are float32
(the router, the maps' projection and the delta rule at precision
"highest"); the state-space recurrence in both forms, ``dt``, ``A``,
its convolution and the multipliers float32 (the recurrence at
precision "highest").

Not a flax module (cf. ``models/transformer.py``): plain-dict params,
two levels deep, ``{"layer_0": {"in_proj_qkvz": ...}, ...}``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import (
    deltanet, flash_attention, hyper_connection, latent_attention, moe, ssd)
from ray_tpu.telemetry import metrics

_HI = jax.lax.Precision.HIGHEST

LINEAR, FULL, LATENT = "linear_attention", "full_attention", "latent_attention"
MAMBA, ATTENTION, SLIDING = "mamba", "attention", "sliding_attention"
DENSE, EXPERTS = "dense", "experts"
PLAIN, HYPER = "plain", "hyper_connection"

# envs of a fragment whose attention scores are alive at once: at most
# 8, fewer (a power of two) where 8 streams' float32 scores of every
# head over the rows a block sees pass ``_ATTN_SCORE_BYTES`` (8 at 2,304
# rows of 32 heads x 256 tokens; 2 at 8,448 rows of 28 x 256, 4 at
# 4,352); for latent attention, whose keys and values of 32 heads are
# rebuilt for the block as well (0.27 GB for 8 streams, and as much
# again for their cotangents), a constant
_ATTN_SCORE_BYTES = 5 * 2 ** 27
_LATENT_ENV_BLOCK = 4
# state leaves a layer of each mixer kind holds (a state-space RUN: its
# layers' matrices stacked in one leaf, their convolution inputs in another)
_STATE_LEAVES = {LINEAR: 2, FULL: 2, LATENT: 1, MAMBA: 2, ATTENTION: 2, SLIDING: 2}
# a stacked run's leaves that enter a bfloat16 product
_RUN_PRODUCT_LEAVES = ("in_proj", "out_proj", "mlp_gate", "mlp_up", "mlp_down")


def layer_types_of(config: Dict) -> Tuple[str, ...]:
    """The pattern: ``layer_types`` if stated; all latent attention
    where the config has a ``kv_lora_rank``; by
    ``sliding_window_layout`` where the config has one (1: a window
    layer, which is also where ``rope_layout`` turns; 0: full depth and
    no positions); else every ``full_attention_interval``-th layer is
    full attention."""
    if config.get("layer_types"):
        # a published list: its first ``num_hidden_layers``
        return tuple(config["layer_types"])[:config.get("num_hidden_layers")]
    layers = int(config["num_hidden_layers"])
    if "sliding_window_layout" in config:
        window = list(config["sliding_window_layout"])[:layers]
        if window != list(config.get("rope_layout", window))[:layers]:
            raise ValueError(
                "a window layer without RoPE, or a full layer with it, is no kind")
        return tuple(SLIDING if w else ATTENTION for w in window)
    if "kv_lora_rank" in config:
        return (LATENT,) * layers
    every = int(config.get("full_attention_interval", 4))
    return tuple(FULL if (i + 1) % every == 0 else LINEAR for i in range(layers))


@dataclasses.dataclass(frozen=True)
class AttentionLayer:
    """One softmax-attention layer as ``SequenceLM._attention`` runs it:
    what the ``"full_attention"``, ``"attention"`` and
    ``"sliding_attention"`` kinds of every family differ in, read from
    the config once (:func:`attention_layers_of`). Hashable: a
    checkpointed block takes it as a static argument."""

    kind: str
    heads: int
    kv_heads: int
    head_dim: int
    scale: float  # of the scores
    # a ring of ``min(window, positions)`` rows; None: the episode's rows
    window: Optional[int] = None
    # RoPE: the head's leading dimensions it turns (0: no positions),
    # its base, a YaRN block's items and the factor on cos and sin
    rotary: int = 0
    theta: float = 10000.0
    yarn: Tuple[Tuple[str, float], ...] = ()
    rope_factor: float = 1.0
    # the output gate: "element" (``q_proj`` gives every head ``[q |
    # gate]``, one number a dimension), "head" (``g_proj``, one number a
    # head and token) or None
    gate: Optional[str] = None
    qk_norm: bool = False

    @property
    def scope(self) -> str:
        return "swa" if self.window else "attn"

    @property
    def rope(self) -> str:
        return "none" if not self.rotary else "yarn" if self.yarn else "default"

    def cache_rows(self, positions: int) -> int:
        return positions if self.window is None else min(self.window, positions)


_YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow")


def attention_layers_of(config: Dict, layer_types) -> Dict[int, AttentionLayer]:
    """``{layer: AttentionLayer}`` for the softmax-attention layers of
    ``layer_types``, each key read for what it states and under
    whichever family's name it has:

    - heads: ``num_attention_heads_per_layer[l]``, else
      ``num_attention_heads``; KV heads and the head's size are one for
      the model;
    - window (a ``"sliding_attention"`` layer): ``sliding_window_size``
      or ``sliding_window``;
    - RoPE: the ``rope_parameters`` block of the layer's kind where the
      config has them (``rope_theta``, ``partial_rotary_factor``,
      ``rope_type`` ``default`` or ``yarn`` with its ``factor``,
      ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``
      and ``attention_factor``, ``0.1 ln(factor) + 1`` where none is
      stated); else ``rope_theta`` on the first
      ``partial_rotary_factor`` of a ``"full_attention"`` head, on the
      whole of a ``"sliding_attention"`` head, and none on an
      ``"attention"`` layer;
    - gate: ``gating`` states one for EVERY attention layer, a number a
      head (``g_proj``); without the key a ``"full_attention"`` layer is
      ``qwen3_next``'s, gated a dimension out of ``q_proj``, and the
      other kinds have none. A gated layer norms ``q`` and ``k`` over
      the head;
    - scale: ``attention_multiplier`` on an ``"attention"`` layer that
      states one, else ``head^-1/2``."""
    c = config
    out = {}
    per_layer = c.get("num_attention_heads_per_layer")
    for i, kind in enumerate(layer_types):
        if kind not in (FULL, ATTENTION, SLIDING):
            continue
        heads = int(per_layer[i] if per_layer else c["num_attention_heads"])
        head_dim = int(c.get("head_dim") or int(c["hidden_size"]) // heads)
        window = None
        if kind == SLIDING:
            window = int(c.get("sliding_window_size") or c["sliding_window"])
        rotary, theta, yarn, factor = 0, float(c.get("rope_theta", 10000.0)), (), 1.0
        if "rope_parameters" in c:
            rope = c["rope_parameters"][kind]
            theta = float(rope["rope_theta"])
            rotary = int(head_dim * float(rope.get("partial_rotary_factor", 1.0)))
            kind_of = rope.get("rope_type", "default")
            if kind_of == "yarn":
                yarn = tuple((k, float(rope[k])) for k in _YARN_KEYS if k in rope)
                factor = float(rope.get("attention_factor")
                               or 0.1 * np.log(float(rope["factor"])) + 1.0)
            elif kind_of != "default":
                raise ValueError(f"rope_type {kind_of!r} is not supported")
        elif kind == FULL:
            rotary = int(head_dim * float(c.get("partial_rotary_factor", 1.0)))
        elif kind == SLIDING:
            rotary = head_dim
        elif c.get("position_embedding_type", "nope") != "nope":
            raise ValueError('an "attention" layer takes no positions')
        gate = "head" if c.get("gating") else "element" if kind == FULL else None
        scale = head_dim ** -0.5
        if kind == ATTENTION:
            scale = float(c.get("attention_multiplier", scale))
        out[i] = AttentionLayer(
            kind=kind, heads=heads, kv_heads=int(c["num_key_value_heads"]),
            head_dim=head_dim, scale=scale, window=window, rotary=rotary,
            theta=theta, yarn=yarn, rope_factor=factor, gate=gate,
            qk_norm=gate is not None)
    return out


def _attn_env_block(heads: int, tokens: int, rows: int) -> int:
    """Streams of a fragment whose float32 scores (every head, every
    token against ``rows`` keys) are alive at once."""
    fit = _ATTN_SCORE_BYTES // (4 * heads * tokens * rows)
    return min(8, 1 << max(0, int(fit).bit_length() - 1))


def _rms(x, weight, eps, centred=True):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y * ((1.0 + weight) if centred else weight)


def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _causal_conv(tail, x, seg, kernel, bias=None):
    """Causal depthwise convolution + SiLU over the stored inputs
    ``tail`` ``(B, width - 1, C)`` and the fragment's own ``x`` ``(B,
    T, C)``; an input of an earlier episode (``seg`` ``(B, T)``, the
    episode's number inside the fragment) is not seen. ``kernel`` ``(C,
    width)``. Returns the output and the last ``width - 1`` inputs of
    the fragment's last episode."""
    b, t, _ = x.shape
    width = kernel.shape[-1]
    full = jnp.concatenate([tail, x], axis=1)  # (B, T+w-1, C)
    full_seg = jnp.concatenate(
        [jnp.zeros((b, width - 1), seg.dtype), seg], axis=1
    )
    conv = jnp.zeros_like(x)
    for back in range(width):
        lo = width - 1 - back
        seen = (full_seg[:, lo : lo + t] == seg)[..., None]
        conv = conv + jnp.where(
            seen, full[:, lo : lo + t], 0.0
        ) * kernel[:, width - 1 - back]
    if bias is not None:
        conv = conv + bias
    out = jax.nn.silu(conv)
    live = (full_seg[:, t:] == seg[:, -1:])[..., None]
    return out, jnp.where(live, full[:, t:], 0.0)


def _rope(x, positions, rotary: int, theta: float, yarn=(), factor: float = 1.0):
    """Rotate the first ``rotary`` dimensions of each head (the
    rotate-half form). ``x`` ``(B, T, H, D)``, ``positions`` ``(B, T)``.
    ``yarn`` (the items of a YaRN block: ``factor``,
    ``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``)
    scales the frequencies as ``ops/latent_attention.yarn_inv_freq``
    does; ``factor`` multiplies ``cos`` and ``sin`` (YaRN's
    ``attention_factor``), so the turned dimensions alone carry it."""
    half = rotary // 2
    if yarn:
        inv = jnp.asarray(latent_attention.yarn_inv_freq(rotary, theta, dict(yarn)))
    else:
        inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary)
    angle = positions.astype(jnp.float32)[..., None] * inv  # (B, T, half)
    cos, sin = jnp.cos(angle)[:, :, None], jnp.sin(angle)[:, :, None]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2, rest = x[..., :half], x[..., half:rotary], x[..., rotary:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1
    )


def _init_ssm_leaf(key, leaf: str, shape):
    """One leaf of a run of state-space layers, ``shape`` with its
    leading layer axis."""
    if leaf == "A_log":
        x = jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32))
    elif leaf == "D":
        x = jnp.ones(shape, jnp.float32)
    elif leaf == "dt_bias":
        dt = jnp.exp(jax.random.uniform(
            key, shape, minval=np.log(1e-3), maxval=np.log(1e-1)))
        x = dt + jnp.log(-jnp.expm1(-dt))
    elif len(shape) == 2:  # norm weights, the convolution's bias
        x = jnp.zeros(shape, jnp.float32)
    else:
        rows = shape[-1] if leaf == "conv" else shape[-2]
        x = jax.random.normal(key, shape, jnp.float32) / np.sqrt(rows)
    return jnp.broadcast_to(x, shape)


class SequenceLM:
    """Duck-typed :class:`~ray_tpu.models.base.RTModel` surface over
    plain-dict params. Registered via ``model_config["use_sequence_lm"]``
    with the architecture under ``model_config["sequence_lm"]``
    (``models/catalog.py``). ``num_outputs`` is the vocabulary held;
    ``dtype`` (``model_config["dtype"]``) the operands' and the cache's."""

    is_recurrent = True
    supports_stored_train_state = True
    # apply() names its scopes under a given prefix and hands the
    # expert-load counts out through ``stats_out``
    train_stats = True
    _partition_rules_override = None
    # tokens a DeltaNet chunk solves at once, and streams of a fragment
    # batch the learn form runs at once (constants; tests shrink them)
    chunk = 64
    learn_streams = 16

    def __init__(self, num_outputs: int, config: Dict, dtype: str = "bfloat16"):
        c = dict(config)
        self.config = c
        self.vocab = int(num_outputs)
        self.hidden = int(c["hidden_size"])
        self.layer_types = layer_types_of(c)
        # experts the config counts, under whichever family's key; none
        # (or no such key) is a model with no expert layer
        experts = int(next(
            (c[k] for k in ("num_experts", "n_routed_experts", "num_local_experts",
                            "moe_num_primary_experts")
             if k in c), 0))
        layers = len(self.layer_types)
        if experts and "mlp_layer_types" in c:  # stated a layer
            self.ffn_types = tuple(
                DENSE if kind == DENSE else EXPERTS
                for kind in c["mlp_layer_types"][:layers])
        else:
            dense_first = int(c.get("first_k_dense_replace", 0)) if experts else layers
            self.ffn_types = tuple(
                DENSE if i < dense_first else EXPERTS for i in range(layers))
        # groups of the parameter tree: a run of state-space layers
        # with its leaves stacked, every other layer alone
        segments = []
        for i, (kind, ffn) in enumerate(zip(self.layer_types, self.ffn_types)):
            if kind == MAMBA and segments and segments[-1][1:3] == [kind, ffn]:
                segments[-1][3] += 1
            else:
                segments.append([i, kind, ffn, 1])
        self.segments = tuple(
            (f"layers_{i}_{i + n - 1}" if kind == MAMBA else f"layer_{i}",
             kind, ffn, n) for i, kind, ffn, n in segments)
        self.embed_scale = float(c.get("embedding_multiplier", 1.0))
        self.residual_scale = float(c.get("residual_multiplier", 1.0))
        self.logits_scale = float(c.get("logits_scaling", 1.0))
        self.tied_head = bool(c.get("tie_word_embeddings", False))
        self.lanes = int(c.get("hc_mult", 1))
        self.residual = HYPER if self.lanes > 1 else PLAIN
        self.eps = float(c.get("rms_norm_eps", 1e-6))
        self.heads = int(c["num_attention_heads"])
        self.theta = float(c.get("rope_theta", 10000.0))
        self.positions = int(c["max_position_embeddings"])
        # the softmax-attention layers, each with its own geometry
        self.attention = {
            f"layer_{i}": a
            for i, a in attention_layers_of(c, self.layer_types).items()}
        if MAMBA in self.layer_types:  # Mamba-2
            if int(c.get("mamba_n_groups", 1)) != 1:
                raise ValueError("B and C are shared by all heads: mamba_n_groups 1")
            self.ssm_heads = int(c["mamba_n_heads"])
            self.ssm_head = int(c["mamba_d_head"])
            self.ssm_state = int(c["mamba_d_state"])
            self.ssm_conv = int(c["mamba_d_conv"])
            self.ssm_inner = self.ssm_heads * self.ssm_head
            if self.ssm_inner != int(c.get("mamba_expand", 2)) * self.hidden:
                raise ValueError("mamba_n_heads x mamba_d_head is not the inner width")
            self.ssm_conv_dim = self.ssm_inner + 2 * self.ssm_state
            self.ssm_conv_bias = bool(c.get("mamba_conv_bias", True))
            # how the published code computes a fragment, not a width
            self.ssm_chunk = int(c.get("mamba_chunk_size", 256))
        if LINEAR in self.layer_types:  # gated deltanet
            self.k_heads = int(c["linear_num_key_heads"])
            self.v_heads = int(c["linear_num_value_heads"])
            self.dk = int(c["linear_key_head_dim"])
            self.dv = int(c["linear_value_head_dim"])
            self.conv = int(c["linear_conv_kernel_dim"])
            self.key_dim = self.k_heads * self.dk
            self.value_dim = self.v_heads * self.dv
            self.conv_dim = 2 * self.key_dim + self.value_dim
        if LATENT in self.layer_types:  # latent attention
            self.q_latent = int(c["q_lora_rank"])
            self.kv_latent = int(c["kv_lora_rank"])
            self.nope = int(c["qk_nope_head_dim"])
            self.rope_dim = int(c["qk_rope_head_dim"])
            self.v_head = int(c["v_head_dim"])
            self.latent_row = self.kv_latent + self.rope_dim
            scaling = c.get("rope_scaling")
            self.inv_freq = latent_attention.yarn_inv_freq(
                self.rope_dim, self.theta, scaling)
            self.softmax_scale = latent_attention.yarn_softmax_scale(
                self.nope + self.rope_dim, scaling)
        if self.residual == HYPER and self.residual_scale != 1.0:
            raise ValueError("residual_multiplier with hc_mult lanes is not defined")
        if self.residual == HYPER:
            # streams of a group of ``loss_groups``: a token's rows are
            # ``hc_mult`` times as wide
            self.learn_streams = 8
            self.hc_rounds = int(c.get("hc_sinkhorn_iters", 20))
            self.hc_eps = float(c.get("hc_eps", 1e-6))
            self.hc_clamp = (float(c.get("mhc_h_res_clamp_min", -30.0)),
                             float(c.get("mhc_h_res_clamp_max", 30.0)))
        if DENSE in self.ffn_types:
            self.dense_width = int(
                c.get("shared_intermediate_size", c.get("intermediate_size")))
        # operands of the projections, the expert products, the head
        # and the attention products; the cache's dtype
        self.dtype = jnp.dtype(dtype)
        if not experts:
            return
        # experts: the router scores all of them, this chip holds some
        self.router_outputs = int(c.get("router_outputs", experts))
        first, count = c.get("experts_held") or (0, experts)
        self.first_expert, self.experts_held = int(first), int(count)
        # SmallThinker's expert layer (its own key names): the router
        # reads the layer's input before the mixer, ReGLU experts, no
        # shared expert
        primary = "moe_num_primary_experts" in c
        if primary and not c.get("moe_primary_router_apply_softmax", True):
            raise ValueError("a primary router without its softmax is not supported")
        self.route_on_input = primary
        self.expert_act = "relu" if primary else "silu"
        if primary and self.residual == HYPER:
            raise ValueError("a router on the layer's input with hc_mult lanes")
        self.top_k = int(c["moe_num_active_primary_experts" if primary
                           else "num_experts_per_tok"])
        self.norm_topk = bool(c.get("norm_topk_prob", True))
        # a config with ``moe_routed_scaling_factor`` (Laguna) names its
        # experts as ``qwen3_next`` does and routes as DeepSeek-V3 does
        # without the selection bias: a sigmoid each, top-k, renormalised,
        # times the factor, the shared expert ungated
        scaled = "moe_routed_scaling_factor" in c
        self.scoring = str(c.get("scoring_func", "sigmoid" if scaled else "softmax"))
        self.route_scale = float(c.get(
            "routed_scaling_factor", c.get("moe_routed_scaling_factor", 1.0)))
        self.select_bias = c.get("topk_method") == "noaux_tc"
        self.expert_width = int(
            c["moe_ffn_hidden_size" if primary else "moe_intermediate_size"])
        # the shared expert: ``qwen3_next`` states its width and gates
        # it; DeepSeek-V3 counts shared experts of the routed width
        stated = "shared_expert_intermediate_size" in c
        self.shared_gated = stated and not scaled
        self.shared_width = int(
            c["shared_expert_intermediate_size"] if stated
            else int(c.get("n_shared_experts", 0 if primary else 1))
            * self.expert_width
        )

    def partition_rules(self):
        return None

    def _dot(self, x, w):
        return jnp.dot(
            x.astype(self.dtype), w.astype(self.dtype),
            preferred_element_type=jnp.float32,
        )

    # -- state -----------------------------------------------------------

    def _segment_state(self, state, n: int):
        lo = sum(_STATE_LEAVES[seg[1]] for seg in self.segments[:n])
        return tuple(state[lo : lo + _STATE_LEAVES[self.segments[n][1]]])

    def initial_state(self, batch_size: int = 1):
        b = int(batch_size)
        state = []
        for name, kind, _, layers in self.segments:
            if kind == MAMBA:
                state.append(jnp.zeros(
                    (b, layers, self.ssm_heads, self.ssm_head, self.ssm_state),
                    jnp.float32))
                state.append(jnp.zeros(
                    (b, layers, self.ssm_conv - 1, self.ssm_conv_dim), jnp.float32))
            elif kind == LINEAR:
                state.append(
                    jnp.zeros((b, self.v_heads, self.dk, self.dv), jnp.float32)
                )
                state.append(
                    jnp.zeros((b, self.conv - 1, self.conv_dim), jnp.float32)
                )
            elif name in self.attention:
                # one row a position: kv heads x head, flat, so that the
                # device tiles (positions, row) without padding 2 heads to 8;
                # a window layer holds a ring of its window's rows
                a = self.attention[name]
                shape = (b, a.cache_rows(self.positions), a.kv_heads * a.head_dim)
                state.append(jnp.zeros(shape, self.dtype))
                state.append(jnp.zeros(shape, self.dtype))
            else:
                # one latent row a position, whatever the head count
                state.append(
                    jnp.zeros((b, self.positions, self.latent_row), self.dtype))
        state.append(jnp.zeros((b,), jnp.int32))
        return tuple(state)

    def reset_state(self, state, mask):
        """Open a new episode on the rows of ``mask``: the DeltaNet and
        state-space matrices, the convolution inputs and the position
        go to zero; a key/value or latent cache is left as it is, since
        only slots below the position are ever read (of a ring: slots
        whose row's position, recovered from the slot and the stream's
        position, is not negative; what an earlier episode left in the
        others is written over before it is read)."""
        out = []
        for n, (_, kind, _, _) in enumerate(self.segments):
            for leaf in self._segment_state(state, n):
                if kind in (LINEAR, MAMBA):
                    m = mask.reshape((-1,) + (1,) * (leaf.ndim - 1))
                    leaf = jnp.where(m, jnp.zeros_like(leaf), leaf)
                out.append(leaf)
        out.append(jnp.where(mask, 0, state[-1]))
        return tuple(out)

    # -- parameters ------------------------------------------------------

    def param_shapes(self) -> Dict[str, Dict[str, tuple]]:
        d, v = self.hidden, self.vocab
        shapes = {
            "embed": {"embedding": (v, d)},
            "final_norm": {"weight": (d,)},
            "head": {"kernel": (d, v)},
            "value": {"kernel": (d, 1), "bias": (1,)},
        }
        if self.tied_head:
            del shapes["head"]
        if EXPERTS in self.ffn_types:
            e, f, fs = self.experts_held, self.expert_width, self.shared_width
        for name, kind, ffn, layers in self.segments:
            layer = {"input_norm": (d,), "post_norm": (d,)}
            if ffn == EXPERTS:
                layer.update(
                    router=(d, self.router_outputs),
                    experts_gate=(e, d, f),
                    experts_up=(e, d, f),
                    experts_down=(e, f, d),
                )
                if fs:
                    layer.update(
                        shared_gate=(d, fs), shared_up=(d, fs), shared_down=(fs, d))
                if self.shared_gated:
                    layer["shared_expert_gate"] = (d, 1)
                if self.select_bias:
                    layer["select_bias"] = (self.router_outputs,)
            else:
                w = self.dense_width
                layer.update(mlp_gate=(d, w), mlp_up=(d, w), mlp_down=(w, d))
            if self.residual == HYPER:
                n = self.lanes
                for sub in ("mixer", "ffn"):
                    layer[f"hc_{sub}_norm"] = (n * d,)
                    layer[f"hc_{sub}_phi"] = (n * d, 2 * n + n * n)
                    layer[f"hc_{sub}_a"] = (3,)
                    layer[f"hc_{sub}_b"] = (2 * n + n * n,)
            if kind == LINEAR:
                layer.update(
                    in_proj_qkvz=(d, 2 * self.key_dim + 2 * self.value_dim),
                    in_proj_ba=(d, 2 * self.v_heads),
                    conv=(self.conv_dim, self.conv),
                    A_log=(self.v_heads,),
                    dt_bias=(self.v_heads,),
                    gdn_norm=(self.dv,),
                    out_proj=(self.value_dim, d),
                )
            elif kind == MAMBA:
                layer.update(
                    # columns [z | x | B | C | dt]
                    in_proj=(d, 2 * self.ssm_inner + 2 * self.ssm_state
                             + self.ssm_heads),
                    conv=(self.ssm_conv_dim, self.ssm_conv),
                    dt_bias=(self.ssm_heads,),
                    A_log=(self.ssm_heads,),
                    D=(self.ssm_heads,),
                    ssm_norm=(self.ssm_inner,),
                    out_proj=(self.ssm_inner, d),
                )
                if self.ssm_conv_bias:
                    layer["conv_bias"] = (self.ssm_conv_dim,)
                layer = {k: (layers,) + shape for k, shape in layer.items()}
            elif name in self.attention:
                a = self.attention[name]
                wide = a.heads * a.head_dim
                layer.update(
                    # every head's [q | gate] where the gate is a dimension's
                    q_proj=(d, wide * (2 if a.gate == "element" else 1)),
                    k_proj=(d, a.kv_heads * a.head_dim),
                    v_proj=(d, a.kv_heads * a.head_dim),
                    o_proj=(wide, d),
                )
                if a.qk_norm:
                    layer.update(q_norm=(a.head_dim,), k_norm=(a.head_dim,))
                if a.gate == "head":
                    layer["g_proj"] = (d, a.heads)
            else:
                h = self.heads
                layer.update(
                    q_a=(d, self.q_latent),
                    q_a_norm=(self.q_latent,),
                    q_b=(self.q_latent, h * (self.nope + self.rope_dim)),
                    kv_a=(d, self.latent_row),
                    kv_a_norm=(self.kv_latent,),
                    kv_b=(self.kv_latent, h * (self.nope + self.v_head)),
                    o_proj=(h * self.v_head, d),
                )
            shapes[name] = layer
        return shapes

    def init(self, rng, obs=None, state=None, **_):
        """Normal matrices of variance 1 / rows, zero-centred norm
        weights at zero, ``A`` uniform in (1, 16), ``dt_bias`` one: the
        published initialisation's forms at a scale that keeps the
        activations of a random model of order one. A hyper-connection
        starts near the plain residual (``a`` 0.01, ``b_res`` twice the
        identity), a selection bias small and not zero. A state-space
        layer starts as the family's does: ``A`` 1..heads, ``D`` one,
        ``dt_bias`` the inverse softplus of a log-uniform step in
        (0.001, 0.1)."""
        shapes = self.param_shapes()
        n = self.lanes
        stacked = {seg[0] for seg in self.segments if seg[1] == MAMBA}

        @jax.jit
        def make(key):
            # XLA's bit generator: a threefry stream for every leaf of a
            # model this size compiles for half a minute on a TPU
            key = jax.random.wrap_key_data(
                jnp.tile(jax.random.key_data(key).astype(jnp.uint32).ravel(), 2)[:4],
                impl="rbg",
            )
            out, count = {}, 0
            for group in sorted(shapes):
                out[group] = {}
                for leaf, shape in sorted(shapes[group].items()):
                    k = jax.random.fold_in(key, count)
                    count += 1
                    if group in stacked:
                        out[group][leaf] = _init_ssm_leaf(k, leaf, shape)
                        continue
                    if leaf == "A_log":
                        x = jnp.log(jax.random.uniform(k, shape, minval=1.0, maxval=16.0))
                    elif leaf in ("dt_bias", "gdn_norm"):
                        x = jnp.ones(shape, jnp.float32)
                    elif leaf.startswith("hc_") and leaf.endswith("_a"):
                        x = jnp.full(shape, 0.01, jnp.float32)
                    elif leaf.startswith("hc_") and leaf.endswith("_b"):
                        x = jnp.concatenate(
                            [jnp.zeros((2 * n,)), 2.0 * jnp.eye(n).ravel()]
                        ).astype(jnp.float32)
                    elif leaf == "select_bias":
                        x = 0.01 * jax.random.normal(k, shape, jnp.float32)
                    elif len(shape) == 1:
                        x = jnp.zeros(shape, jnp.float32)
                    elif leaf == "embedding":
                        x = jax.random.normal(k, shape, jnp.float32)
                    else:
                        x = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[-2])
                    out[group][leaf] = x
            return out

        return make(rng)

    # -- the learn form's grouping ---------------------------------------

    def loss_groups(self, unrolls: int) -> Optional[int]:
        """Groups of unrolls the learn program takes through the WHOLE
        stack, the loss and the backward pass one at a time, its
        gradient accumulated (``JaxPolicy`` asks). ``None`` for the
        plain residual, whose learn form groups the streams inside
        each block instead (``apply``): there a block's saved input is
        one hidden row a token and the batch's fit. With ``hc_mult``
        lanes a token's saved row is ``hc_mult`` times as wide and the
        batch's no longer fit beside the weights, so only one group's
        are alive."""
        if self.residual == PLAIN:
            return None
        s = self.learn_streams
        return unrolls // s if unrolls > s and unrolls % s == 0 else 1

    def reduce_group_stats(self, stats: Dict) -> Dict:
        """``stats`` stacked over the groups of ``loss_groups`` -> one
        update's: loads add up over groups before their mean and max
        are taken, an error's largest is kept, the rest is averaged."""
        out = {}
        for k, v in stats.items():
            if k == "moe_held_load":
                load = v.sum(0)  # (expert layers, held)
                out["moe_tokens_per_held_expert"] = jnp.mean(load)
                out["moe_max_tokens_per_held_expert"] = jnp.max(load)
            elif k == "moe_slots_on_absent_experts":
                out[k] = v.sum(0)
            elif k.endswith("_max"):
                out[k] = v.max(0)
            else:
                out[k] = v.mean(0)
        return out

    # -- forward ---------------------------------------------------------

    def apply(self, params, obs, state, resets=None, scope: str = "",
              stats_out: Optional[Dict] = None, **_):
        """``obs`` ``(B, T[, 1])`` token ids; ``state`` as
        ``initial_state``; ``resets`` ``(B, T)`` (1.0 where a token
        opens an episode). Returns ``(logits (B*T, vocab), value
        (B*T,), state)``. ``scope`` prefixes the named scopes;
        ``stats_out`` receives the expert-load counts."""
        tokens = obs.reshape(obs.shape[0], -1).astype(jnp.int32)
        b, t = tokens.shape
        # the one-token form opens an episode before the token it
        # consumes; without ``resets`` nothing is reset here (the
        # rollout lane has reset the stream after its last step) and
        # no pass over the state is made for it
        if t == 1 and resets is not None:
            state = self.reset_state(state, resets.reshape(b) > 0.5)
        if t == 1 or resets is None:
            resets = jnp.zeros((b, t), jnp.float32)
        fresh = resets.reshape(b, t) > 0.5
        seg = jnp.cumsum(fresh.astype(jnp.int32), axis=1)  # (B, T)
        steps = jnp.arange(t, dtype=jnp.int32)[None]
        opened = jax.lax.cummax(jnp.where(fresh, steps, -1), axis=1)
        pos0 = state[-1]
        positions = jnp.where(seg == 0, pos0[:, None] + steps, steps - opened)
        rows_ctx = {
            "seg": seg, "fresh": fresh, "positions": positions, "pos0": pos0,
        }
        prefix = (scope + "/") if scope else ""
        hyper = self.residual == HYPER

        x = jnp.take(params["embed"]["embedding"], tokens, axis=0)  # (B, T, D)
        if self.embed_scale != 1.0:
            x = x * self.embed_scale
        if hyper:  # the embedding copied into every lane, held flat
            x = jnp.tile(x, (1, 1, self.lanes))

        def block(x, p, layer_state, rows, kind, ffn_kind, attention):
            ctx = dict(rows, scope=prefix)
            if attention is not None:  # a softmax-attention layer's geometry
                mixer = lambda p, h, s, ctx: self._attention(p, h, s, ctx, attention)
            else:
                mixer = {
                    LINEAR: self._linear_attn, LATENT: self._latent_attn,
                    MAMBA: self._mamba,
                }[kind]
            ffn = self._moe if ffn_kind == EXPERTS else self._mlp
            if hyper:
                return self._hyper_block(x, p, layer_state, ctx, mixer, ffn)
            if ffn_kind == EXPERTS and self.route_on_input:
                # the router reads the layer's input, before the mixer
                with jax.named_scope(prefix + "moe/route"):
                    ctx["route"] = self._route(p, x.reshape(-1, x.shape[-1]))
            y, new = mixer(p, _rms(x, p["input_norm"], self.eps), layer_state, ctx)
            x = x + self._scaled(y)
            y, load, routes = ffn(p, _rms(x, p["post_norm"], self.eps), ctx)
            if kind == SLIDING:  # beside the state, the rows its queries saw
                *new, seen = new
                new, load = tuple(new), (load, seen)
            return x + self._scaled(y), new, load, routes

        # the plain residual groups the streams inside each block; with
        # lanes the policy groups them around the whole loss instead
        # (``loss_groups``)
        groups = b // self.learn_streams if (
            not hyper
            and t > 1 and b > self.learn_streams and b % self.learn_streams == 0
        ) else 1
        # the learn form keeps a block's input and recomputes the block
        # in the backward pass, ``learn_streams`` streams at a time: one
        # group's activations of one block are alive, not the batch's
        # of the stack
        whole = jax.checkpoint(block, static_argnums=(4, 5, 6)) if t > 1 else block

        def run_block(x, p, layer_state, rows, *kinds):
            if groups == 1:
                return whole(x, p, layer_state, rows, *kinds)
            split = lambda a: a.reshape((groups, b // groups) + a.shape[1:])
            merge = lambda a: a.reshape((b,) + a.shape[2:])
            x, new, load, routes = jax.lax.map(
                lambda xs: whole(xs[0], p, xs[1], xs[2], *kinds),
                jax.tree_util.tree_map(split, (x, layer_state, rows)),
            )
            return (
                merge(x),
                jax.tree_util.tree_map(merge, new),
                jax.tree_util.tree_map(lambda a: a.sum(0), load),
                jax.tree_util.tree_map(
                    lambda r: r.reshape((-1,) + r.shape[2:]), routes),
            )

        state_out, loads, all_routes, errs, steps_seen, rows_seen = (
            [], [], [], [], [], [])
        for n, (name, kind, ffn_kind, _) in enumerate(self.segments):
            # the block's static arguments: its kinds and, for a
            # softmax-attention layer, its geometry
            args = (params[name], self._segment_state(state, n), rows_ctx,
                    kind, ffn_kind, self.attention.get(name))
            if kind == MAMBA:
                x, new, seen = self._run_of_layers(run_block, prefix, x, *args)
                steps_seen.append(seen)
            else:
                x, new, load, routes = run_block(x, *args)
            state_out.extend(new)
            if hyper:
                load, err = load
                errs.append(err)
            if kind == SLIDING:
                load, seen = load
                rows_seen.append(seen)
            if ffn_kind == EXPERTS:
                loads.append(load)
                all_routes.append(routes)
        state_out.append(positions[:, -1] + 1)

        with jax.named_scope(prefix + "head"):
            if hyper:  # the lanes summed
                x = sum(hyper_connection.lanes_of(x, self.lanes))
            feat = _rms(x, params["final_norm"]["weight"], self.eps).reshape(b * t, -1)
            if self.tied_head:  # the embedding, contracted over the hidden axis
                logits = jax.lax.dot_general(
                    feat.astype(self.dtype),
                    params["embed"]["embedding"].astype(self.dtype),
                    (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
                )
            else:
                logits = self._dot(feat, params["head"]["kernel"])
            if self.logits_scale != 1.0:
                logits = logits / self.logits_scale
            value = (
                jnp.dot(feat, params["value"]["kernel"], precision=_HI)
                + params["value"]["bias"]
            )[:, 0]
        if stats_out is not None and steps_seen:
            stats_out["ssm_dt_max"] = jnp.max(jnp.stack(steps_seen))
        if stats_out is not None and rows_seen:
            # rows inside the window a query of a window layer saw
            stats_out["window_rows_seen_mean"] = sum(rows_seen) / (
                b * t * len(rows_seen))
        if stats_out is not None and t > 1 and (
                self.attention or LATENT in self.layer_types):
            # of the key blocks the fragment kernel walks (a stream's
            # stored blocks and its own), those it skips: the stored ones
            # at or past the start position (0 where the XLA text runs,
            # which multiplies every slot); a latent layer's rows are one
            # key head for all its query heads
            # and of the key blocks the one-token kernel's layers hold
            # (the full-depth softmax layers), those a step at each of
            # the fragment's positions skips: the blocks with no slot at
            # or below the position (0 where the text runs)
            skipped, walked, step_skipped, step_walked = 0.0, 0, 0.0, 0
            for n, (name, kind, _, _) in enumerate(self.segments):
                a = self.attention.get(name)
                if a is not None:
                    geometry = a.heads, a.kv_heads, a.head_dim
                elif kind == LATENT:
                    geometry = self.heads, 1, self.latent_row
                else:
                    continue
                depth = self._segment_state(state, n)[0].shape[1]
                if flash_attention.fragment_kernel_applies(
                        t, *geometry, depth, self.dtype):
                    more, blocks = flash_attention.fragment_key_blocks(pos0, depth)
                    skipped, walked = skipped + more, walked + blocks
                if a is not None and a.window is None and (
                        flash_attention.step_kernel_applies(
                            *geometry, depth, self.dtype)):
                    more, blocks = flash_attention.step_key_blocks(
                        positions + 1, depth)
                    step_skipped, step_walked = (
                        step_skipped + more, step_walked + blocks)
            stats_out["attn_key_blocks_skipped_share"] = skipped / max(walked, 1)
            if self.attention:
                stats_out["attn_decode_key_blocks_skipped_share"] = (
                    step_skipped / max(step_walked, 1))
        if stats_out is not None and not loads:
            if "moe_routes" in stats_out:
                # asked for every token's expert set where no layer
                # routes: the one feed-forward there is, for every token
                stats_out["moe_routes"] = jnp.zeros((1, b * t, 1), jnp.int32)
        elif stats_out is not None:
            per_expert = jnp.stack([l[0] for l in loads])  # (layers, held)
            if hyper:
                # per group of ``loss_groups``; ``reduce_group_stats``
                # makes the update's numbers of them
                stats_out["moe_held_load"] = per_expert
                stats_out["hc_res_row_sum_err_max"] = jnp.max(
                    jnp.stack([e[0] for e in errs]))
                stats_out["hc_res_col_sum_err_max"] = jnp.max(
                    jnp.stack([e[1] for e in errs]))
            else:
                stats_out["moe_tokens_per_held_expert"] = jnp.mean(per_expert)
                stats_out["moe_max_tokens_per_held_expert"] = jnp.max(per_expert)
                # of the held experts, those that some stream's token at
                # the same place of its fragment reached: where a
                # fragment is one stream's rollout (the fused lane) a
                # place is a decode step, and this is the share of the
                # held experts' weights a step's tokens chose
                stats_out["moe_decode_held_experts_touched_share"] = jnp.mean(
                    jnp.stack([l[3] for l in loads]) > 0)
            stats_out["moe_slots_on_absent_experts"] = sum(l[1] for l in loads)
            # of the dense form's (token, held expert) rows, those the
            # experts' products computed (the grouped form: its buffers)
            stats_out["moe_rows_computed_share"] = sum(l[2] for l in loads) / (
                b * t * self.experts_held * len(loads))
            if "moe_routes" in stats_out:
                stats_out["moe_routes"] = jnp.stack(all_routes)
        return logits, value, tuple(state_out)

    # -- the hyper-connection residual -----------------------------------

    def _hyper_block(self, x, p, layer_state, ctx, mixer, ffn):
        """Both sublayers through ``X <- H_res X + H_post^T F(rms(H_pre
        X))``. Returns ``(X, state, (load, (row, column) sum errors of
        H_res), routes)``."""
        n = self.lanes

        def around(sub, norm, f):
            with jax.named_scope(ctx["scope"] + "hc"):
                pre, post, res = hyper_connection.maps(
                    x, p[f"hc_{sub}_norm"], p[f"hc_{sub}_phi"], p[f"hc_{sub}_a"],
                    p[f"hc_{sub}_b"], n, self.eps, self.hc_rounds, self.hc_eps,
                    *self.hc_clamp, unroll=x.shape[1] == 1,
                )
                h = hyper_connection.mix_in(x, pre)
            out = f(p, _rms(h, p[norm], self.eps))
            with jax.named_scope(ctx["scope"] + "hc"):
                err = (jnp.max(jnp.abs(res.sum(-1) - 1.0)),
                       jnp.max(jnp.abs(res.sum(-2) - 1.0)))
                return hyper_connection.mix_out(x, out[0], post, res), out, err

        x, (_, new), e1 = around(
            "mixer", "input_norm", lambda p, h: mixer(p, h, layer_state, ctx))
        x, (_, load, routes), e2 = around(
            "ffn", "post_norm", lambda p, h: ffn(p, h, ctx))
        err = tuple(jnp.maximum(a, b) for a, b in zip(e1, e2))
        return x, new, (load, err), routes

    # -- a run of stacked layers -----------------------------------------

    def _scaled(self, y):
        return y if self.residual_scale == 1.0 else y * self.residual_scale

    def _run_of_layers(self, run_block, scope, x, p, run_state, rows, *kinds):
        """A run of identical layers whose leaves are stacked on a
        leading layer axis, as ONE ``lax.scan``: the layer is traced
        once. ``run_state``'s leaves carry the layer axis after the
        stream's; beside its two new leaves the mixer hands back the
        largest step size each stream saw. Returns ``(x, state leaves,
        largest step size)``."""
        layers = jnp.arange(next(iter(p.values())).shape[0])
        if x.shape[1] == 1:
            # one token: the run's state rides in the carry and a layer
            # reads and writes its own slice of it in place. The product
            # weights are cast here, outside the scan over layers, so
            # that they are loop-invariant in the lane's scan over steps
            # and converted once a rollout, as an unstacked layer's are
            p = {k: v.astype(self.dtype) if k in _RUN_PRODUCT_LEAVES else v
                 for k, v in p.items()}

            def step(carry, xs):
                x, matrices, tails = carry
                p_l, layer = xs
                # the matrices go to the mixer whole with the layer's
                # index (``ssd.ssd_step`` updates that layer where it
                # lies); the convolution tail, 4 MB a run, as a slice
                with jax.named_scope(scope + "ssm/carry"):
                    tail = jax.lax.dynamic_index_in_dim(tails, layer, 1, keepdims=False)
                x, (matrices, tail, seen), _, _ = run_block(
                    x, p_l, ((matrices, layer), tail), rows, *kinds)
                with jax.named_scope(scope + "ssm/carry"):
                    tails = jax.lax.dynamic_update_index_in_dim(
                        tails, tail.astype(tails.dtype), layer, 1)
                return (x, matrices, tails), seen

            (x, *leaves), seen = jax.lax.scan(step, (x, *run_state), (p, layers))
            return x, tuple(leaves), jnp.max(seen)

        def fragment(x, xs):
            p_l, mine = xs
            x, (*new, seen), _, _ = run_block(x, p_l, mine, rows, *kinds)
            return x, (tuple(new), seen)

        x, (new, seen) = jax.lax.scan(
            fragment, x, (p, tuple(jnp.moveaxis(s, 1, 0) for s in run_state)))
        return x, tuple(jnp.moveaxis(s, 0, 1) for s in new), jnp.max(seen)

    # -- state space (Mamba-2) -------------------------------------------

    def _mamba(self, p, x, state, ctx):
        scope = ctx["scope"] + "ssm"
        s0, tail = state
        b, t, _ = x.shape
        inner, n, heads = self.ssm_inner, self.ssm_state, self.ssm_heads
        with jax.named_scope(scope + "/in"):
            zxbcdt = self._dot(x, p["in_proj"])
            z = zxbcdt[..., :inner]
            mixed = zxbcdt[..., inner : inner + self.ssm_conv_dim]
            dt = jax.nn.softplus(zxbcdt[..., inner + self.ssm_conv_dim :] + p["dt_bias"])
            a = -jnp.exp(p["A_log"])
        with jax.named_scope(scope + "/conv"):
            mixed, new_tail = _causal_conv(
                tail, mixed, ctx["seg"], p["conv"], p.get("conv_bias"))
        with jax.named_scope(scope + "/step"):
            xs = mixed[..., :inner].reshape(b, t, heads, self.ssm_head)
            bt, ct = mixed[..., inner : inner + n], mixed[..., inner + n :]
            if t == 1:  # the run's stacked matrices and this layer's index
                stacked, layer = s0
                s1, y = ssd.ssd_step(
                    stacked, xs[:, 0], dt[:, 0], a, bt[:, 0], ct[:, 0], layer=layer)
                y = y[:, None]
            else:
                y, s1 = ssd.ssd_chunked(
                    s0, xs, dt, a, bt, ct,
                    resets=ctx["fresh"].astype(jnp.float32), chunk=self.ssm_chunk,
                )
            y = (y + p["D"][:, None] * xs).reshape(b, t, inner)
        with jax.named_scope(scope + "/out"):
            y = _rms(y * jax.nn.silu(z), p["ssm_norm"], self.eps)
            # beside the state, the largest step size each stream saw
            return self._dot(y, p["out_proj"]), (s1, new_tail, jnp.max(dt, axis=(1, 2)))

    # -- gated deltanet --------------------------------------------------

    def _linear_attn(self, p, x, state, ctx):
        with jax.named_scope(ctx["scope"] + "linear_attn"):
            s0, tail = state
            b, t, _ = x.shape
            seg = ctx["seg"]
            kd, vd, hv, hk = self.key_dim, self.value_dim, self.v_heads, self.k_heads
            qkvz = self._dot(x, p["in_proj_qkvz"])
            mixed, z = qkvz[..., : 2 * kd + vd], qkvz[..., 2 * kd + vd :]
            ba = jnp.dot(x, p["in_proj_ba"], precision=_HI)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])

            mixed, new_tail = _causal_conv(tail, mixed, seg, p["conv"])

            q = mixed[..., :kd].reshape(b, t, hk, self.dk)
            k = mixed[..., kd : 2 * kd].reshape(b, t, hk, self.dk)
            v = mixed[..., 2 * kd :].reshape(b, t, hv, self.dv)
            q = _l2norm(q) * (self.dk ** -0.5)
            k = _l2norm(k)
            q = jnp.repeat(q, hv // hk, axis=2)
            k = jnp.repeat(k, hv // hk, axis=2)
            if t == 1:
                s1, o = deltanet.gated_delta_step(
                    s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
                )
                o = o[:, None]
            else:
                o, s1 = deltanet.gated_delta_chunked(
                    s0, q, k, v, g, beta,
                    resets=ctx["fresh"].astype(jnp.float32),
                    chunk=self.chunk,
                )
            o = _rms(o, p["gdn_norm"], self.eps, centred=False)
            o = o * jax.nn.silu(z.reshape(b, t, hv, self.dv))
            return self._dot(o.reshape(b, t, vd), p["out_proj"]), (s1, new_tail)

    # -- softmax attention -----------------------------------------------

    def _attention(self, p, x, state, ctx, a: AttentionLayer):
        """A softmax-attention layer of the geometry ``a``: GQA over the
        layer's own head count, ``q`` and ``k`` RMS-normed over the head
        where it is gated, RoPE on the head's leading ``a.rotary``
        dimensions (none: no positions), the cache the episode's rows or
        a ring of ``a.window``, the output times a sigmoid gate (a number
        a dimension out of ``q_proj``, or a number a head from
        ``g_proj``). Projections, norms and RoPE run under the scope
        ``attn`` (``swa`` for a window layer), the cached attention's
        parts under its ``/scatter``, ``/scores`` and ``/out``, the gate
        under ``/gate``, the output projection under ``/out``. A window
        layer hands back, beside its ring, the number of rows its queries
        saw."""
        scope = ctx["scope"] + a.scope
        b, t, _ = x.shape
        h, hkv, d = a.heads, a.kv_heads, a.head_dim
        positions = ctx["positions"]
        metrics.inc_attention_layer_lowering(a.kind, h, a.rope)
        if a.window is not None:
            metrics.inc_window_cache_lowering("step" if t == 1 else "fragment")
        with jax.named_scope(scope):
            q = self._dot(x, p["q_proj"])
            if a.gate == "element":
                q = q.reshape(b, t, h, 2 * d)
                q, gate = q[..., :d], q[..., d:]
            else:
                q = q.reshape(b, t, h, d)
            k = self._dot(x, p["k_proj"]).reshape(b, t, hkv, d)
            v = self._dot(x, p["v_proj"]).reshape(b, t, hkv, d)

            def normed_and_turned(z, norm):
                if a.qk_norm:
                    z = _rms(z, p[norm], self.eps)
                if a.rotary:
                    z = _rope(z, positions, a.rotary, a.theta, a.yarn, a.rope_factor)
                return z

            q, k = normed_and_turned(q, "q_norm"), normed_and_turned(k, "k_norm")
        o, new, seen = self._cached_attention(
            q, k, v, state, ctx, a.scale, window=a.window, scope=scope)
        if a.gate is not None:
            with jax.named_scope(scope + "/gate"):
                if a.gate == "head":
                    gate = self._dot(x, p["g_proj"])[..., None]  # (B, T, H, 1)
                o = o * jax.nn.sigmoid(gate)
        with jax.named_scope(scope + "/out"):
            y = self._dot(o.reshape(b, t, h * d), p["o_proj"])
        return y, new if a.window is None else new + (seen,)

    def _cached_attention(self, q, k, v, state, ctx, scale, window=None, scope=""):
        """Causal attention of a fragment's ``q`` ``(B, T, heads, D)``
        over the stored keys and values and the fragment's own ``k``,
        ``v`` ``(B, T, kv heads, D)``. Returns ``(o (B, T, heads, D),
        (keys, values) after the fragment, pairs seen)``, its parts
        under ``scope``'s ``/scatter``, ``/scores`` and ``/out``.

        With a ``window`` the cache is a RING of ``R = min(window,
        positions)`` slots, position ``p`` in slot ``p mod R``: the row
        a stream at ``pos0`` holds in slot ``s`` is the one of the
        largest position below ``pos0`` that is ``s mod R`` (none where
        that is negative), a query sees the rows whose position is less
        than ``window`` behind its own, and the masks come from those
        positions, never from slot numbers, and the number of (query,
        key) pairs seen is returned third (None without a window).

        Which form runs where, each chosen by what the call sees in its
        input. A fragment (``T > 1``): the tiled kernel
        ``ops/flash_attention.fragment_attention`` where
        ``fragment_kernel_applies`` says so (a TPU, bfloat16, whole
        blocks), window or none, else the XLA text a block of streams
        at a time. One token over a full-depth cache (``window is
        None``): ``ops/flash_attention.step_attention`` where
        ``step_kernel_applies`` says so, which fetches a stream's key
        blocks below its depth only, else the text. One token over a
        ring: the text, always (every slot under the position's mask:
        past its first turn a ring has no unwritten slot to skip)."""
        k_cache, v_cache = state
        b, t, h, d = q.shape
        hkv = k.shape[2]
        depth = k_cache.shape[1]
        seg, positions, pos0 = ctx["seg"], ctx["positions"], ctx["pos0"]
        k, v = k.astype(self.dtype), v.astype(self.dtype)
        part = lambda name: jax.named_scope(f"{scope}/{name}")

        # the cache after the fragment: the last episode's tokens,
        # each at its position (positions of one episode are
        # distinct; earlier episodes' tokens are dropped); in a ring
        # the last ``depth`` of them, each at its position mod ``depth``
        if window is None:
            slot = jnp.where(seg == seg[:, -1:], positions, depth)
        else:
            kept = (seg == seg[:, -1:]) & (positions > positions[:, -1:] - depth)
            slot = jnp.where(kept, positions % depth, depth)
        rows = jnp.arange(b)[:, None]
        with part("scatter"):
            new_k = k_cache.at[rows, slot].set(
                k.reshape(b, t, hkv * d).astype(k_cache.dtype), mode="drop")
            new_v = v_cache.at[rows, slot].set(
                v.reshape(b, t, hkv * d).astype(v_cache.dtype), mode="drop")

        group = h // hkv
        qh = (q * scale).astype(self.dtype).reshape(b, t, hkv, group, d)
        slots = jnp.arange(depth)

        def attend(qe, ke, ve, kc, vc, sege, pos0e, pose=None):
            kc = kc.reshape(kc.shape[:2] + (hkv, d))
            vc = vc.reshape(vc.shape[:2] + (hkv, d))
            # one block of envs: scores over the stored keys (a
            # stored key is seen by the tokens before the first
            # reset, below the start position) and the fragment's
            # own (causal, same episode)
            with part("scores"):
                old = jnp.einsum(
                    "btngd,bsnd->bngts", qe, kc, preferred_element_type=jnp.float32
                )
                if window is None:
                    see_old = (sege == 0)[:, :, None] & (
                        slots[None, None] < pos0e[:, None, None]
                    )  # (b, t, S)
                else:
                    # the position of the row in each slot
                    last = pos0e[:, None] - 1
                    held = last - (last - slots[None]) % depth  # (b, S)
                    see_old = (sege == 0)[:, :, None] & (held >= 0)[:, None] & (
                        pose[:, :, None] - held[:, None] < window)
                old = jnp.where(see_old[:, None, None], old, -jnp.inf)
                if t == 1:
                    # the step's own key is in the cache already
                    own = jnp.full(old.shape[:-1] + (0,), -jnp.inf)
                else:
                    own = jnp.einsum(
                        "btngd,bsnd->bngts", qe, ke,
                        preferred_element_type=jnp.float32,
                    )
                    see = (steps_t[:, None] >= steps_t[None, :])[None] & (
                        sege[:, :, None] == sege[:, None, :]
                    )
                    if window is not None:
                        see = see & (steps_t[:, None] - steps_t[None, :] < window)[None]
                    own = jnp.where(see[:, None, None], own, -jnp.inf)
                w = jax.nn.softmax(jnp.concatenate([old, own], axis=-1), axis=-1)
                w = w.astype(self.dtype)
            with part("out"):
                out = jnp.einsum(
                    "bngts,bsnd->btngd", w[..., :depth], vc,
                    preferred_element_type=jnp.float32,
                )
                if t > 1:
                    out = out + jnp.einsum(
                        "bngts,bsnd->btngd", w[..., depth:], ve,
                        preferred_element_type=jnp.float32,
                    )
            if window is None:
                return out
            # (query, key) pairs seen, a stream
            seen = jnp.sum(see_old, axis=(1, 2), dtype=jnp.float32)
            if t > 1:
                seen = seen + jnp.sum(see, axis=(1, 2), dtype=jnp.float32)
            return out, seen

        steps_t = jnp.arange(t)
        # a ring's masks need each query's position
        own_positions = () if window is None else (positions,)
        if t == 1 and window is None and flash_attention.step_kernel_applies(
                h, hkv, d, depth, self.dtype):
            # a full-depth cache is half unwritten at the mean: the
            # tiled step kernel fetches a stream's key blocks below its
            # depth only (a ring past its first turn has no unwritten
            # slot: its step stays on the text)
            metrics.inc_attention_step_lowering("kernel")
            with part("scores"):
                o = flash_attention.step_attention(qh, new_k, new_v, pos0 + 1)
        elif t == 1:
            # decode reads the cache it has just written: the own
            # key sits at slot pos0, so the stored range is one longer
            metrics.inc_attention_step_lowering("xla")
            o = attend(qh, k, v, new_k, new_v, seg, pos0 + 1, *own_positions)
        elif flash_attention.fragment_kernel_applies(
                t, h, hkv, d, depth, self.dtype):
            # one tiled kernel, forward and backward: no score matrix
            # is written, and no block of streams is needed to hold one
            metrics.inc_attention_fragment_lowering("kernel")
            with part("scores"):
                o = flash_attention.fragment_attention(
                    qh, k, v, k_cache, v_cache, pos0, seg, positions,
                    window=window)
                if window is not None:
                    o = o, flash_attention.fragment_pairs_seen(
                        pos0, seg, positions, depth, window)
        else:
            metrics.inc_attention_fragment_lowering("xla")
            nb = max(1, b // _attn_env_block(h, t, depth + t))
            if b % nb:
                nb = 1
            args = (qh, k, v, k_cache, v_cache, seg, pos0) + own_positions
            blocked = tuple(
                a.reshape((nb, b // nb) + a.shape[1:]) for a in args
            )
            o = jax.lax.map(
                lambda xs: jax.checkpoint(attend)(*xs), blocked
            )
            o = jax.tree_util.tree_map(
                lambda a: a.reshape((b,) + a.shape[2:]), o)
        if window is None:
            return o.reshape(b, t, h, d), (new_k, new_v), None
        o, seen = o
        return o.reshape(b, t, h, d), (new_k, new_v), jnp.sum(seen)

    # -- latent attention ------------------------------------------------

    def _latent_attn(self, p, x, state, ctx):
        with jax.named_scope(ctx["scope"] + "mla"):
            (cache,) = state
            b, t, _ = x.shape
            h, dn, c = self.heads, self.nope, self.kv_latent
            seg, positions, pos0 = ctx["seg"], ctx["positions"], ctx["pos0"]
            c_q = _rms(self._dot(x, p["q_a"]), p["q_a_norm"], self.eps)
            q = self._dot(c_q, p["q_b"]).reshape(b, t, h, dn + self.rope_dim)
            q_nope = q[..., :dn]
            q_pe = latent_attention.rope(q[..., dn:], positions, self.inv_freq)
            kv = self._dot(x, p["kv_a"])
            k_pe = latent_attention.rope(
                kv[:, :, None, c:], positions, self.inv_freq)[:, :, 0]
            rows_new = jnp.concatenate(
                [_rms(kv[..., :c], p["kv_a_norm"], self.eps), k_pe], axis=-1
            ).astype(cache.dtype)

            # the cache after the fragment, as ``_attn`` writes it: the
            # last episode's rows, each at its position
            slot = jnp.where(seg == seg[:, -1:], positions, self.positions)
            new_cache = cache.at[jnp.arange(b)[:, None], slot].set(
                rows_new, mode="drop")
            if t == 1:
                # reads what the cache holds, its own row included
                metrics.inc_mla_decode_lowering("absorbed")
                o = latent_attention.absorbed_step(
                    q_nope[:, 0], q_pe[:, 0], new_cache, p["kv_b"], pos0,
                    self.softmax_scale, self.dtype,
                )[:, None]
            elif flash_attention.fragment_kernel_applies(
                    t, h, 1, self.latent_row, cache.shape[1], self.dtype):
                # the same absorbed product on the tiled kernel: one key
                # head, the latent rows as they lie
                metrics.inc_mla_decode_lowering("absorbed_fragment")
                metrics.inc_attention_fragment_lowering("kernel")
                o = latent_attention.absorbed_fragment(
                    q_nope, q_pe, rows_new, cache, p["kv_b"], seg, positions,
                    pos0, self.softmax_scale, self.dtype,
                )
            else:
                metrics.inc_mla_decode_lowering("expanded")
                metrics.inc_attention_fragment_lowering("xla")
                o = latent_attention.expanded_fragment(
                    q_nope, q_pe, rows_new, cache, p["kv_b"], seg, pos0,
                    self.softmax_scale, self.dtype, block=_LATENT_ENV_BLOCK,
                )
            return (
                self._dot(o.reshape(b, t, h * self.v_head), p["o_proj"]),
                (new_cache,),
            )

    # -- feed-forward ----------------------------------------------------

    def _mlp(self, p, x, ctx):
        with jax.named_scope(ctx["scope"] + "mlp"):
            b, t, d = x.shape
            out = moe.gated_mlp(
                x.reshape(b * t, d), p["mlp_gate"], p["mlp_up"], p["mlp_down"],
                dtype=self.dtype,
            )
        return out.reshape(b, t, d), None, None

    def _route(self, p, flat):
        """Every token's ``(indices, weights)`` from ``flat`` ``(tokens,
        D)``: the expert layer's input, or the block's where the router
        stands before the mixer."""
        return moe.route_top_k(
            flat, p["router"], self.top_k, self.norm_topk,
            scoring=self.scoring, select_bias=p.get("select_bias"),
            scale=self.route_scale,
        )

    def _moe(self, p, x, ctx):
        b, t, d = x.shape
        flat = x.reshape(b * t, d)
        scope = ctx["scope"]
        held = self.experts_held
        # a token of each stream: dense; a fragment of each: grouped
        lowering = moe.product_lowering(b * t, self.top_k, self.router_outputs)
        metrics.inc_moe_product_lowering(lowering)
        with jax.named_scope(scope + "moe/route"):
            # routed already where the router reads the block's input
            indices, weights = ctx.get("route") or self._route(p, flat)
            if lowering == "dense":
                combine = moe.held_combine_weights(
                    indices, weights, self.first_expert, held
                )
            per_expert, absent = moe.expert_load(indices, self.first_expert, held)
            # tokens each held expert got at each place of the fragment,
            # over the streams: ``(T, held)``
            local = indices.reshape(b, t, -1) - self.first_expert
            by_place = jnp.sum(
                local[..., None] == jnp.arange(held, dtype=jnp.int32),
                axis=(0, 2), dtype=jnp.float32)
            load = (
                per_expert, absent,
                moe.rows_computed(
                    per_expert, b * t, self.top_k, self.router_outputs, lowering),
                by_place,
            )
        experts = (p["experts_gate"], p["experts_up"], p["experts_down"])
        with jax.named_scope(scope + "moe/experts"):
            if lowering == "dense":
                routed = moe.dense_experts_product(
                    flat, *experts, combine, dtype=self.dtype,
                    activation=self.expert_act,
                )
            else:
                routed = moe.grouped_experts_product(
                    flat, *experts, indices, weights, per_expert,
                    self.first_expert, self.router_outputs, dtype=self.dtype,
                    activation=self.expert_act,
                )
        if not self.shared_width:  # the routed sum alone
            return routed.reshape(b, t, d), load, indices
        with jax.named_scope(scope + "moe/shared"):
            shared = moe.gated_mlp(
                flat, p["shared_gate"], p["shared_up"], p["shared_down"],
                dtype=self.dtype,
            )
            if self.shared_gated:
                shared = shared * jax.nn.sigmoid(
                    jnp.dot(flat, p["shared_expert_gate"], precision=_HI))
        return (routed + shared).reshape(b, t, d), load, indices
