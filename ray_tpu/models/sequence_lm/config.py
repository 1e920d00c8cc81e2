"""From a published ``config.json``'s key names to the kinds' frozen
descriptions: THE code that reads a family's key names. Every body
branches on a description's fields, never on a key's presence."""

from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Tuple

import numpy as np

from ray_tpu.models.sequence_lm.generation import Autoregressive, BlockDiffusion
from ray_tpu.models.sequence_lm.kinds import (
    AttentionLayer, DeltaNetLayer, DenseLayer, EvaLayer, ExpertLayer, GatedMemoryLayer,
    HyperResidual, Indexer, KDALayer, LatentLayer, MambaLayer, Norm, NoSublayer,
    PlainResidual, SelectiveScanLayer)
from ray_tpu.ops import deltanet, latent_attention

LINEAR, FULL, LATENT = "linear_attention", "full_attention", "latent_attention"
MAMBA, ATTENTION, SLIDING = "mamba", "attention", "sliding_attention"
EVA = "eva_attention"
KDA = "kimi_delta_attention"
SCAN, MEMORY, CROSS = "selective_scan", "gated_memory", "cross_attention"
# what the SambaY exporters hand on, by name: layer L/2's scan output and
# layer L/2 + 1's key/value cache
_MEMORY, _KV = "memory", "kv"
DENSE, EXPERTS = "dense", "experts"
# the half a block of one sublayer lacks
NONE = "none"
# ``hybrid_override_pattern`` (``nemotron_h``): a character a block of
# ONE sublayer, ``(mixer, feed-forward)``
_PATTERN = {"M": (MAMBA, NONE), "E": (NONE, EXPERTS), "*": (ATTENTION, NONE)}
# families whose every layer is ``qwen3_moe``'s: full attention with q/k
# norms and no gate over an expert layer with no shared expert
_QWEN3_MOE_STACKS = ("sdar_moe", "KeyeVL2")
# families that generate a block a step by masked diffusion: how a
# family generates is not what its layers are
_BLOCK_DIFFUSION = ("sdar_moe",)


def _qwen3_moe_stack(config: Dict) -> bool:
    return config.get("model_type") in _QWEN3_MOE_STACKS


def generation_of(config: Dict):
    """How the family generates. ``model_type: sdar_moe`` is a
    block-diffusion model: ``block_length`` tokens a step in
    ``denoising_steps`` passes, ``mask_token_id`` the row of the
    embedding that stands for ``[MASK]`` (the published config gives
    none of the three: the cell's file states them as assumed). Every
    other family is autoregressive, ``KeyeVL2``, which shares SDAR's
    stack of layers, among them."""
    c = config
    if c.get("model_type") in _BLOCK_DIFFUSION:
        return BlockDiffusion(
            block_length=int(c["block_length"]),
            denoising_steps=int(c["denoising_steps"]),
            mask_token_id=int(c["mask_token_id"]))
    return Autoregressive()


def _pattern_of(config: Dict):
    """``[(mixer, feed-forward)]`` of the first ``num_hidden_layers``
    characters of a ``hybrid_override_pattern``. ``-``, the dense
    feed-forward block of other Nemotron-H models, is refused by name."""
    pattern = str(config["hybrid_override_pattern"])
    pattern = pattern[:int(config.get("num_hidden_layers", len(pattern)))]
    unknown = sorted(set(pattern) - set(_PATTERN))
    if unknown:
        raise ValueError(
            f"hybrid_override_pattern has blocks {unknown} (of M, E, * and "
            "the dense feed-forward block '-', only the first three are read)")
    return [_PATTERN[ch] for ch in pattern]


def _sambay(config: Dict) -> bool:
    return config.get("model_type") == "phi4flash"


def _bailing(config: Dict) -> bool:
    return config.get("model_type") == "bailing_hybrid"


def _held_indices(config: Dict) -> Tuple[Tuple[int, ...], int]:
    """``(the published index of every layer held, the published
    depth)`` of a config whose mixers go by PUBLISHED index
    (``phi4flash``, ``bailing_hybrid``): all ``num_hidden_layers`` of
    them, or where the depth is cut the ``layer_indices`` list (as many
    as ``num_hidden_layers``) out of ``published_num_hidden_layers``."""
    held = int(config["num_hidden_layers"])
    if "layer_indices" not in config:
        return tuple(range(held)), held
    indices = tuple(int(i) for i in config["layer_indices"])
    depth = int(config["published_num_hidden_layers"])
    if len(indices) != held or list(indices) != sorted(set(indices)) or not (
            0 <= indices[0] and indices[-1] < depth):
        raise ValueError(
            f"layer_indices {indices}: num_hidden_layers ({held}) rising indices "
            f"below published_num_hidden_layers ({depth})")
    return indices, depth


def _sambay_kind(i: int, depth: int, period: int) -> str:
    """The mixer of PUBLISHED layer ``i`` of a decoder-hybrid-decoder of
    ``depth`` layers (SambaY, arXiv:2507.06607, figure 1; ``period`` is
    ``mb_per_layer``): the self-decoder, layers up to ``depth / 2``,
    alternates a selective scan (every ``period``-th layer, ``depth / 2``
    among them: the one whose output is the memory) with window
    attention; layer ``depth / 2 + 1`` is the ONE full attention, whose
    cache the cross-decoder reads; after it gated-memory units (where
    the self-decoder has its scans) alternate with cross-attention."""
    scan_place = i % period == 0
    if i <= depth // 2:
        return SCAN if scan_place else SLIDING
    if i == depth // 2 + 1:
        return ATTENTION
    return MEMORY if scan_place else CROSS


def layer_types_of(config: Dict) -> Tuple[str, ...]:
    """The pattern of mixers: by ``hybrid_override_pattern`` where the
    config has one (``M`` ``"mamba"``, ``*`` ``"attention"``, ``E``
    ``"none"``: a block of experts alone); ``layer_types`` if stated;
    all full attention for
    a ``qwen3_moe`` stack; all EVA attention where ``attention_class``
    says ``"eva"`` (``evabyte``); all latent attention
    where the config has a ``kv_lora_rank``; by
    ``sliding_window_layout`` where the config has one (1: a window
    layer, which is also where ``rope_layout`` turns; 0: full depth and
    no positions); else every ``full_attention_interval``-th layer is
    full attention. ``model_type: phi4flash``: :func:`_sambay_kind` of
    each held layer's published index. ``model_type: bailing_hybrid``
    (Ling 3.0): by each held layer's published index too, the LAST layer
    of every ``layer_group_size`` latent attention and the others Kimi
    Delta Attention."""
    if "hybrid_override_pattern" in config:
        return tuple(mixer for mixer, _ in _pattern_of(config))
    if _bailing(config):
        every = int(config["layer_group_size"])
        return tuple(LATENT if (i + 1) % every == 0 else KDA
                     for i in _held_indices(config)[0])
    if _sambay(config):  # by each held layer's PUBLISHED index
        indices, depth = _held_indices(config)
        return tuple(_sambay_kind(i, depth, int(config.get("mb_per_layer", 2)))
                     for i in indices)
    if config.get("layer_types"):
        # a published list: its first ``num_hidden_layers``
        return tuple(config["layer_types"])[:config.get("num_hidden_layers")]
    layers = int(config["num_hidden_layers"])
    if _qwen3_moe_stack(config):
        return (FULL,) * layers
    if config.get("attention_class") == "eva":
        return (EVA,) * layers
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


_YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow")


def _indexer_of(c: Dict, head_dim: int):
    """``sa_config`` (``model_type: KeyeVL2``): ``indexer_num_heads``
    heads of ``indexer_head_dim`` over ``indexer_num_kv_heads`` 1 index
    key a row, ``topk`` rows a query. ``q_chunk_size`` / ``kv_chunk_size``
    are how the release TILES the score product, no width and no pooling
    of rows: not read. ``rope_scaling`` may state ``mrope_section``: the
    three components' shares of the head's ``head_dim / 2`` pairs, which
    over text tokens (equal components) is rotate-half RoPE over the
    whole head; any other scaling is refused. None without the key."""
    sa = c.get("sa_config")
    if not sa:
        return None
    if int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError("sa_config: the index has ONE key a row for all its heads")
    scaling = c.get("rope_scaling") or {}
    section = scaling.get("mrope_section")
    if scaling.get("rope_type", scaling.get("type", "default")) != "default" or (
            section is not None and 2 * sum(int(n) for n in section) != head_dim):
        raise ValueError(
            f"rope_scaling {scaling}: only M-RoPE sections that fill the head's "
            f"{head_dim // 2} pairs, unscaled, are read")
    return Indexer(
        heads=int(sa["indexer_num_heads"]), head_dim=int(sa["indexer_head_dim"]),
        top_k=int(sa["topk"]))


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
      the head, and so does a ``qwen3_moe`` stack's, which has no gate;
    - mask: by blocks of the generation's ``block_length`` where the
      family generates by block diffusion (``generation_of``), causal
      otherwise;
    - scale: ``attention_multiplier`` on an ``"attention"`` layer that
      states one, else ``head^-1/2``;
    - ``model_type: phi4flash``: NO positions on any layer, the
      differential form and biases on every one, ``index`` the layer's
      published index; the ``"attention"`` layer exports its cache and a
      ``"cross_attention"`` layer reads it;
    - ``sa_config``: a learned index on every layer (:func:`_indexer_of`)."""
    c = config
    out = {}
    plain = _qwen3_moe_stack(c)
    sambay = _sambay(c)
    indices = _held_indices(c)[0] if sambay else ()
    block = generation_of(c).tokens_per_step
    per_layer = c.get("num_attention_heads_per_layer")
    for i, kind in enumerate(layer_types):
        if kind not in (FULL, ATTENTION, SLIDING, CROSS):
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
        elif sambay:
            pass  # the state-space layers carry the order
        elif kind == FULL:
            rotary = int(head_dim * float(c.get("partial_rotary_factor", 1.0)))
        elif kind == SLIDING:
            rotary = head_dim
        elif c.get("position_embedding_type", "nope") != "nope":
            raise ValueError('an "attention" layer takes no positions')
        gate = "head" if c.get("gating") else (
            "element" if kind == FULL and not plain else None)
        if sambay and (heads % 2 or int(c["num_key_value_heads"]) % 2):
            raise ValueError("differential attention pairs adjacent heads")
        scale = head_dim ** -0.5
        if kind == ATTENTION:
            scale = float(c.get("attention_multiplier", scale))
        out[i] = AttentionLayer(
            kind=kind, heads=heads, kv_heads=int(c["num_key_value_heads"]),
            head_dim=head_dim, scale=scale, window=window, rotary=rotary,
            theta=theta, yarn=yarn, rope_factor=factor, gate=gate,
            qk_norm=plain or gate is not None, block=block,
            diff=sambay, bias=sambay, index=indices[i] if sambay else 0,
            exports_as=_KV if sambay and kind == ATTENTION else None,
            source=_KV if kind == CROSS else None,
            indexer=_indexer_of(c, head_dim))
        if out[i].indexer and (sambay or window is not None or block > 1):
            raise ValueError("sa_config: an index over a full-depth causal cache only")
    return out


def _head_gate(c: Dict) -> bool:
    """``gated_attention_proj_granularity_type``: only ``head_wise`` (a
    number a head and token on a mixer's output) is read."""
    kind = c.get("gated_attention_proj_granularity_type")
    if kind not in (None, "head_wise"):
        raise ValueError(f"an output gate of granularity {kind!r} is not supported")
    return kind == "head_wise"


def _latent_layer(c: Dict) -> LatentLayer:
    """``q_lora_rank`` null (or no such key): NO query latent."""
    rope_dim, nope = int(c["qk_rope_head_dim"]), int(c["qk_nope_head_dim"])
    scaling = c.get("rope_scaling")
    inv_freq = latent_attention.yarn_inv_freq(
        rope_dim, float(c.get("rope_theta", 10000.0)), scaling)
    q_latent = c.get("q_lora_rank")
    return LatentLayer(
        heads=int(c["num_attention_heads"]),
        q_latent=None if q_latent is None else int(q_latent),
        kv_latent=int(c["kv_lora_rank"]), nope=nope, rope_dim=rope_dim,
        v_head=int(c["v_head_dim"]), inv_freq=tuple(float(f) for f in inv_freq),
        softmax_scale=latent_attention.yarn_softmax_scale(nope + rope_dim, scaling),
        interleave=bool(c.get("rope_interleave", False)), gate=_head_gate(c))


def _kda_layer(c: Dict) -> KDALayer:
    """``model_type: bailing_hybrid``: heads of ``head_dim`` for keys and
    values alike (``num_kv_heads_for_linear_attn`` 0: as many key heads
    as heads), ``short_conv_kernel_size``, the bounded gate
    (``kda_safe_gate`` with ``kda_lower_bound``) from ONE matrix
    (``no_kda_lora``), L2-normed ``q`` and ``k`` (``use_qk_norm``), no
    value norm, the head-wise output gate. What the kind has no field
    for is refused by name, and so is a ``kda_lower_bound`` under which
    the chunked rule's factors leave float32
    (``ops/deltanet.CHANNEL_LOG_DECAY_FLOOR``)."""
    heads, head = int(c["num_attention_heads"]), int(c["head_dim"])
    lower = float(c["kda_lower_bound"])
    if not deltanet.CHANNEL_LOG_DECAY_FLOOR < lower < 0.0:
        raise ValueError(
            f"Kimi Delta Attention: kda_lower_bound {lower} is not inside "
            f"({deltanet.CHANNEL_LOG_DECAY_FLOOR:.3f}, 0): the chunked rule's "
            "sub-blocks would overflow float32")
    unread = [k for k, want in (
        ("kda_safe_gate", True), ("no_kda_lora", True), ("use_kda_lora", False),
        ("linear_silu", True), ("use_qk_norm", True), ("value_norm", False),
        ("group_norm_size", 1), ("num_kv_heads_for_linear_attn", 0),
    ) if c.get(k, want) != want]
    if unread or not _head_gate(c):
        raise ValueError(
            f"Kimi Delta Attention: {unread or 'no head-wise output gate'} is not "
            "what the layer computes")
    return KDALayer(
        heads=heads, dk=head, dv=head, conv=int(c["short_conv_kernel_size"]),
        lower=lower)


def _eva_layer(c: Dict) -> EvaLayer:
    """``model_type: evabyte`` with ``attention_class: "eva"``:
    ``window_size`` and ``chunk_size``, RoPE by ``rope_theta`` with no
    scaling, as many key heads as query heads. ``num_attention_heads``
    is what this chip holds, ``heads_held`` ``[first, held]`` which of
    the layer's they are (none: all of them, from 0), so the head's size
    is ``head_dim`` where a share is held and ``hidden_size /
    num_attention_heads`` of the whole layer."""
    heads = int(c["num_attention_heads"])
    first, held = c.get("heads_held") or (0, heads)
    window, chunk = int(c["window_size"]), int(c["chunk_size"])
    if int(c.get("num_key_value_heads", heads)) != heads or int(held) != heads:
        raise ValueError(
            "EVA attention pools a key head a query head: num_key_value_heads "
            "and heads_held's count are num_attention_heads")
    if c.get("rope_scaling") or window % chunk:
        raise ValueError("EVA attention: no rope_scaling, whole chunks a window")
    return EvaLayer(
        heads=heads, head_dim=int(c.get("head_dim") or int(c["hidden_size"]) // heads),
        window=window, chunk=chunk, theta=float(c.get("rope_theta", 10000.0)),
        first=int(first))


def _deltanet_layer(c: Dict) -> DeltaNetLayer:
    return DeltaNetLayer(
        k_heads=int(c["linear_num_key_heads"]), v_heads=int(c["linear_num_value_heads"]),
        dk=int(c["linear_key_head_dim"]), dv=int(c["linear_value_head_dim"]),
        conv=int(c["linear_conv_kernel_dim"]))


def _mamba_layer(c: Dict) -> MambaLayer:
    """The state-space layer under either family's names.
    ``granitemoehybrid`` (Granite 4.0-H): ``mamba_n_heads``,
    ``mamba_d_head``, ``mamba_d_state``, ``mamba_d_conv``,
    ``mamba_conv_bias``, ``mamba_chunk_size``, ``mamba_n_groups``, and
    an inner width that is also ``mamba_expand x hidden_size``.
    ``nemotron_h`` (``mamba_num_heads``): ``mamba_head_dim``,
    ``ssm_state_size``, ``conv_kernel``, ``use_conv_bias``,
    ``chunk_size``, ``n_groups``; the inner width is ``mamba_num_heads x
    mamba_head_dim`` whatever ``expand`` says (4,096 beside a hidden
    size of 2,688)."""
    if "mamba_num_heads" in c:
        return MambaLayer(
            heads=int(c["mamba_num_heads"]), head=int(c["mamba_head_dim"]),
            state=int(c["ssm_state_size"]), conv=int(c["conv_kernel"]),
            conv_bias=bool(c.get("use_conv_bias", True)),
            chunk=int(c.get("chunk_size", 256)), groups=int(c.get("n_groups", 1)))
    layer = MambaLayer(
        heads=int(c["mamba_n_heads"]), head=int(c["mamba_d_head"]),
        state=int(c["mamba_d_state"]), conv=int(c["mamba_d_conv"]),
        conv_bias=bool(c.get("mamba_conv_bias", True)),
        chunk=int(c.get("mamba_chunk_size", 256)),
        groups=int(c.get("mamba_n_groups", 1)))
    if layer.inner != int(c.get("mamba_expand", 2)) * int(c["hidden_size"]):
        raise ValueError("mamba_n_heads x mamba_d_head is not the inner width")
    return layer


def _selective_scan_layer(c: Dict) -> SelectiveScanLayer:
    """``model_type: phi4flash`` states none of the state-space sizes:
    Mamba's defaults (``mamba_d_state`` 16, ``mamba_d_conv`` 4,
    ``mamba_expand`` 2, ``mamba_dt_rank`` ``ceil(hidden / 16)``) unless
    the config has the key."""
    d = int(c["hidden_size"])
    return SelectiveScanLayer(
        inner=int(c.get("mamba_expand", 2)) * d, state=int(c.get("mamba_d_state", 16)),
        dt_rank=int(c.get("mamba_dt_rank", -(-d // 16))),
        conv=int(c.get("mamba_d_conv", 4)))


def _expert_layer(c: Dict, experts: int) -> ExpertLayer:
    """The expert layer under whichever family's names the config has.
    SmallThinker (``moe_num_primary_experts``, its own key names): the
    router on the layer's input, ReGLU, no shared expert. A config with
    ``moe_routed_scaling_factor`` (Laguna) names its experts as
    ``qwen3_next`` does and routes as DeepSeek-V3 does without the
    selection bias. The shared expert: ``qwen3_next`` states its width
    and gates it; DeepSeek-V3 counts shared experts of the routed
    width; a ``qwen3_moe`` stack has none. ``nemotron_h`` (a config with
    ``moe_shared_expert_intermediate_size``): DeepSeek-V3's router
    (sigmoid, the selection bias ``e_score_correction_bias``) though it
    states neither ``scoring_func`` nor ``topk_method``; experts and
    shared expert ungated, the activation ``mlp_hidden_act``, the shared
    expert's width stated and its output not gated (``n_groups`` is its
    state-space layer's). ``bailing_hybrid`` (Ling 3.0): DeepSeek-V3's
    names, ``num_shared_experts`` shared experts of
    ``moe_shared_expert_intermediate_size``, gated SwiGLU. ``n_group``
    and ``topk_group`` are the ROUTER's groups under every family that
    states them (1 and 1, or neither key: the plain top-k)."""
    bailing = _bailing(c)
    nemotron = "moe_shared_expert_intermediate_size" in c and not bailing
    primary = "moe_num_primary_experts" in c
    if primary and not c.get("moe_primary_router_apply_softmax", True):
        raise ValueError("a primary router without its softmax is not supported")
    scaled = "moe_routed_scaling_factor" in c
    stated = "shared_expert_intermediate_size" in c
    width = int(c["moe_ffn_hidden_size" if primary else "moe_intermediate_size"])
    # the router scores all experts, this chip holds some
    first, held = c.get("experts_held") or (0, experts)
    return ExpertLayer(
        router_outputs=int(c.get("router_outputs", experts)),
        first=int(first), held=int(held),
        top_k=int(c["moe_num_active_primary_experts" if primary
                    else "num_experts_per_tok"]),
        norm_topk=bool(c.get("norm_topk_prob", True)),
        width=width,
        route_on="input" if primary else "stream",
        activation=str(c["mlp_hidden_act"]) if nemotron else (
            "relu" if primary else "silu"),
        scoring=str(c.get(
            "scoring_func", "sigmoid" if scaled or nemotron else "softmax")),
        select_bias=nemotron or c.get("topk_method") == "noaux_tc",
        scale=float(c.get(
            "routed_scaling_factor", c.get("moe_routed_scaling_factor", 1.0))),
        shared_width=int(
            c["moe_shared_expert_intermediate_size"] if nemotron
            else int(c.get("num_shared_experts", 1))
            * int(c["moe_shared_expert_intermediate_size"]) if bailing
            else c["shared_expert_intermediate_size"] if stated
            else int(c.get(
                "n_shared_experts",
                0 if primary or _qwen3_moe_stack(c) else 1)
            ) * width),
        shared_gated=stated and not scaled,
        gated=not nemotron,
        # every masked position of a pass routes alike
        alone=3 if generation_of(c).tokens_per_step > 1 else 0,
        n_group=int(c.get("n_group", 1)), topk_group=int(c.get("topk_group", 1)),
    )


class Segment(NamedTuple):
    """One group of the parameter tree: a layer, or a run of ``layers``
    stacked ones, with its kinds' descriptions."""

    name: str
    mixer: object
    ffn: object
    layers: int

    @property
    def sublayers(self) -> Tuple[str, ...]:
        """The halves its blocks have (``"mixer"``, ``"ffn"``), in order:
        both, or the one of a block of one sublayer."""
        return tuple(sub for sub, kind in (("mixer", self.mixer), ("ffn", self.ffn))
                     if not kind.absent)


def describe(config: Dict) -> Dict:
    """What ``SequenceLM`` holds of a config, by attribute. Mixers by
    :func:`layer_types_of`. A config with a ``hybrid_override_pattern``
    (``nemotron_h``) has blocks of ONE sublayer: the other half is
    ``"none"`` in ``layer_types`` / ``ffn_types`` and a
    :class:`~ray_tpu.models.sequence_lm.kinds.NoSublayer` in the
    segment. Otherwise feed-forwards by ``mlp_layer_types``
    (``"dense"`` or ``"sparse"`` a layer) where the config states them,
    else ``"dense"`` for the first ``first_k_dense_replace`` layers and
    ``"experts"`` after; a config that counts no experts
    (``num_local_experts`` 0, or no such key) has NO expert layer. The
    residual by ``hc_mult``. ``segments``: a run of consecutive layers
    of one ``stacked`` description is one group ``"layers_<first>_<last>"``,
    every other layer its own ``"layer_<n>"``. The Granite multipliers,
    each 1 where the config states none. ``generation``: how the family
    generates (:func:`generation_of`). ``norm``: the zero-centred
    RMSNorm, or ``phi4flash``'s LayerNorm with a bias. A mixer that
    ``imports`` what no earlier layer ``exports`` is refused by name."""
    c = config
    generation = generation_of(c)
    layer_types = layer_types_of(c)
    layers = len(layer_types)
    attention = attention_layers_of(c, layer_types)
    others = {LINEAR: _deltanet_layer, LATENT: _latent_layer, KDA: _kda_layer,
              MAMBA: _mamba_layer,
              EVA: _eva_layer, NONE: lambda c: NoSublayer(),
              SCAN: _selective_scan_layer,
              MEMORY: lambda c: GatedMemoryLayer(
                  inner=_selective_scan_layer(c).inner, source=_MEMORY)}
    made = {kind: others[kind](c) for kind in set(layer_types) & set(others)}
    # experts the config counts, under whichever family's key
    experts = int(next(
        (c[k] for k in ("num_experts", "n_routed_experts", "num_local_experts",
                        "moe_num_primary_experts")
         if k in c), 0))
    if "hybrid_override_pattern" in c:  # a block's one sublayer
        ffn_types = tuple(ffn for _, ffn in _pattern_of(c))
    elif experts and "mlp_layer_types" in c:  # stated a layer
        ffn_types = tuple(
            DENSE if kind == DENSE else EXPERTS for kind in c["mlp_layer_types"][:layers])
    else:
        dense_first = int(c.get("first_k_dense_replace", 0)) if experts else layers
        ffn_types = tuple(DENSE if i < dense_first else EXPERTS for i in range(layers))
    ffn_of = {NONE: NoSublayer()}
    if DENSE in ffn_types:
        ffn_of[DENSE] = DenseLayer(
            int(c.get("shared_intermediate_size", c.get("intermediate_size"))))
    if EXPERTS in ffn_types:
        ffn_of[EXPERTS] = _expert_layer(c, experts)
    lanes = int(c.get("hc_mult", 1))
    residual = PlainResidual(float(c.get("residual_multiplier", 1.0)))
    if lanes > 1:
        if residual.scale != 1.0:
            raise ValueError("residual_multiplier with hc_mult lanes is not defined")
        if NONE in layer_types + ffn_types:
            raise ValueError("hc_mult lanes around a block of one sublayer")
        if EXPERTS in ffn_of and ffn_of[EXPERTS].route_on == "input":
            raise ValueError("a router on the layer's input with hc_mult lanes")
        residual = HyperResidual(
            lanes=lanes, rounds=int(c.get("hc_sinkhorn_iters", 20)),
            eps=float(c.get("hc_eps", 1e-6)),
            clamp=(float(c.get("mhc_h_res_clamp_min", -30.0)),
                   float(c.get("mhc_h_res_clamp_max", 30.0))))
    if generation.tokens_per_step > 1 and any(
            i not in attention or attention[i].window is not None
            for i in range(layers)):
        raise ValueError(
            "a block a step needs every mixer to be full-depth attention")
    mixers = [attention.get(i) or made[kind] for i, kind in enumerate(layer_types)]
    if _sambay(c):
        # the LAST scan of the self-decoder is the one whose output is
        # the memory (published layer depth / 2)
        indices, depth = _held_indices(c)
        mixers = [
            dataclasses.replace(m, exports_as=_MEMORY)
            if kind == SCAN and i == depth // 2 else m
            for m, kind, i in zip(mixers, layer_types, indices)]
    exported = set()
    for i, mixer in enumerate(mixers):
        missing = [name for name in mixer.imports if name not in exported]
        if missing:
            raise ValueError(
                f"layer {i} ({layer_types[i]}) reads {missing}, which no layer "
                "before it exports: the cut leaves an importer without its exporter")
        exported.update(mixer.exports)
    runs = []
    for i, kind in enumerate(layer_types):
        mixer, ffn = mixers[i], ffn_of[ffn_types[i]]
        if mixer.stacked and runs and runs[-1][1:3] == [mixer, ffn]:
            runs[-1][3] += 1
        else:
            runs.append([i, mixer, ffn, 1])
    return dict(
        layer_types=layer_types, ffn_types=ffn_types, residual=residual,
        generation=generation,
        segments=tuple(
            Segment(f"layers_{i}_{i + n - 1}" if mixer.stacked else f"layer_{i}",
                    mixer, ffn, n) for i, mixer, ffn, n in runs),
        hidden=int(c["hidden_size"]), positions=int(c["max_position_embeddings"]),
        eps=float(next(
            (c[k] for k in ("rms_norm_eps", "layer_norm_epsilon", "norm_eps",
                            "layer_norm_eps") if k in c),
            1e-6)),
        norm=Norm(bias=_sambay(c)),
        embed_scale=float(c.get("embedding_multiplier", 1.0)),
        logits_scale=float(c.get("logits_scaling", 1.0)),
        tied_head=bool(c.get("tie_word_embeddings", False)))
