"""The kinds of layer: the protocol (:class:`Kind`), what every kind is
written in (the norms, the causal convolution, RoPE, the bfloat16
product), the reductions a kind's statistics declare, and the mixers,
the feed-forwards and the residuals themselves, each with its published
source and arithmetic on its description."""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import (
    cached_attention, deltanet, eva_attention, hyper_connection, latent_attention, moe,
    selective_scan, ssd)
from ray_tpu.telemetry import metrics

HI = jax.lax.Precision.HIGHEST


class Kind:
    """One kind of mixer, feed-forward or residual: a frozen description
    of one layer, made by ``config.describe`` (hashable: a checkpointed
    block takes it as a static argument), that answers the questions
    ``SequenceLM`` asks of any kind; here, what one with nothing of its
    own answers. A mixer or feed-forward also has ``apply(p, x, state,
    ctx) -> (y, new state, stats)`` on the normed stream ``x`` ``(B, T,
    D)``; ``ctx`` holds the fragment's rows (``seg``, ``fresh``,
    ``positions`` ``(B, T)``, ``pos0`` ``(B,)``), the ``scope`` prefix,
    the operands' ``dtype``, the norms' ``eps`` and the DeltaNet
    ``chunk``. Where the model's generation commits a block a step
    (``generation.py``) the rows may say ``step`` (the ``T`` tokens are
    one block of the lane's, not a fragment), ``keep`` (the mixer hands
    the rows of its own that a later pass over the same fragment reads
    back after its state leaves: an attention layer's keys and values)
    or ``clean`` (those rows of the clean pass, handed to a noisy one)."""

    # a run of consecutive such layers is ONE group of the parameter
    # tree, its leaves on a leading layer axis
    stacked = False
    # whether a new episode zeroes the state leaves (a recurrent matrix
    # is; a cache is not: only slots below the position are ever read)
    cleared_on_reset = False
    # ``{leaf: f(key, shape)}`` where the shared ladder is wrong for a
    # leaf; ``shape`` carries a stacked run's leading layer axis
    init_rules = {}
    # the keys of ``apply``'s statistics, each with its ``REDUCTIONS``
    stats = {}
    # the half a block of ONE sublayer lacks (:class:`NoSublayer`)
    absent = False
    # where a feed-forward's router reads: None, no router
    route_on = None
    # the name under which a mixer hands something it made to LATER
    # layers, and the name of an earlier layer's export it reads
    # (``SequenceLM._stack`` carries them: docs/policy_state.md, "State
    # that one layer makes and others read"). A mixer with ``exports``
    # hands them as the LAST element of its new state, ``{name: value}``,
    # every leaf a row a stream; one with ``imports`` finds them under
    # ``ctx["imports"]``
    exports_as = None
    source = None

    @property
    def exports(self):
        return (self.exports_as,) if self.exports_as else ()

    @property
    def imports(self):
        return (self.source,) if self.source else ()

    def param_shapes(self, hidden: int):
        """The leaves of the layer's group that are this kind's."""
        return {}

    def state_shapes(self, streams: int, positions: int, dtype):
        """``[(shape, dtype)]`` of its leaves of the state tuple."""
        return []


def dot(x, w, dtype):
    """``x @ w`` on ``dtype`` operands, accumulated in float32."""
    return jnp.dot(x.astype(dtype), w.astype(dtype), preferred_element_type=jnp.float32)


def rms(x, weight, eps, centred=True):
    x = x.astype(jnp.float32)
    y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    return y * ((1.0 + weight) if centred else weight)


@dataclasses.dataclass(frozen=True)
class Norm:
    """The model's norm, of every sublayer's input and of the stack's
    output: the zero-centred RMSNorm :func:`rms`, or with ``bias`` a
    LayerNorm ``(x - mean) * rsqrt(var + eps) * (1 + w) + b`` (the weight
    stored zero-centred like every other norm's: with seeded weights a
    reparametrisation of the published ``w``). Float32."""

    bias: bool = False

    def leaves(self, name: str, d: int):
        """``{leaf: shape}`` of the norm called ``name`` in its group:
        the weight under the name itself, the bias beside it."""
        shapes = {name: (d,)}
        if self.bias:
            shapes[_bias_of(name)] = (d,)
        return shapes

    def __call__(self, x, p, name: str, eps: float):
        if not self.bias:
            return rms(x, p[name], eps)
        x = x.astype(jnp.float32)
        x = x - jnp.mean(x, axis=-1, keepdims=True)
        y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
        return y * (1.0 + p[name]) + p[_bias_of(name)]


def _bias_of(name: str) -> str:
    return "bias" if name == "weight" else name + "_bias"


def l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def causal_conv(tail, x, seg, kernel, bias=None):
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


def rope(x, positions, rotary: int, theta: float, yarn=(), factor: float = 1.0):
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


# -- statistics -----------------------------------------------------------
#
# A kind hands ``apply`` a dict of statistics and declares, a key, how
# the values of several calls become one: ``(over groups of streams,
# over layers)``. The first is what ``run_block``'s ``lax.map`` and
# ``reduce_group_stats`` apply to values stacked on a leading axis, the
# second what the layer loop applies to the values of the layers that
# reported the key (a run's scan has stacked them already).

_keep = lambda a: a
REDUCTIONS = {
    "sum": (lambda a: a.sum(0), lambda a: a.sum(0)),
    "max": (lambda a: a.max(0), lambda a: a.max(0)),
    "mean": (lambda a: a.mean(0), lambda a: a.mean(0)),
    # a row a layer: added up over the streams, kept a layer
    "layer": (lambda a: a.sum(0), _keep),
    # a row a token: the groups' tokens one after another, kept a layer
    "tokens": (lambda a: a.reshape((-1,) + a.shape[2:]), _keep),
}


def over_streams(stats, declared):
    """``stats`` stacked over groups of streams -> the batch's. A key no
    kind declares (``reduce_group_stats`` is handed the loss's own beside
    the model's, and the ratios ``apply`` made) is averaged, an
    ``..._max`` kept at its largest."""
    return {k: REDUCTIONS[declared.get(
        k, "max" if k.endswith("_max") else "mean")][0](v) for k, v in stats.items()}


def over_layers(stats, declared):
    """``{key: [(layers, ...) a segment]}`` -> the stack's."""
    return {k: REDUCTIONS[declared[k]][1](jnp.concatenate(v))
            for k, v in stats.items()}


# -- mixers ---------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Indexer:
    """A learned index over an attention layer's cache (``sa_config``):
    ``heads`` index heads of ``head_dim`` over ONE index key a row, and
    the ``top_k`` rows a query attends to (``ops/sparse_index``)."""

    heads: int
    head_dim: int
    top_k: int


# the index key's norm: a LayerNorm with a bias, whatever the model's is
_INDEX_KEY_NORM = Norm(bias=True)


@dataclasses.dataclass(frozen=True)
class AttentionLayer(Kind):
    """One softmax-attention layer: what the ``"full_attention"``,
    ``"attention"`` and ``"sliding_attention"`` kinds of every family
    differ in (``config.attention_layers_of``). GQA over the layer's own
    head count, ``q`` and ``k`` RMS-normed over the head where it is
    gated, RoPE on the head's leading ``rotary`` dimensions (none: no
    positions), the cache the episode's rows or a ring of ``window``
    (``ops/cached_attention.py``), the output times a sigmoid gate (a
    number a dimension out of ``q_proj``, or a number a head from
    ``g_proj``). No biases.

    - ``"full_attention"`` (Hugging Face ``qwen3_next``,
      ``Qwen3NextAttention``): one projection gives each head its query
      and an output gate, ``q`` and ``k`` normed, RoPE on the first
      ``partial_rotary_factor`` of the head;
    - ``"attention"`` (``GraniteMoeHybridAttention`` with
      ``position_embedding_type: nope``): NO positions, no q/k norm, no
      gate, scores scaled by ``attention_multiplier``;
    - ``"sliding_attention"`` (SmallThinker's window layers): RoPE
      (rotate-half) over the WHOLE head, no norm, no gate; a query at
      position ``p`` sees the keys at ``p - window + 1 .. p`` of its
      episode and nothing older;
    - Laguna's layers (``model_type: laguna``): a head count a layer
      over the same KV heads, a ``rope_parameters`` block a kind (YaRN
      on part of a full layer's head, ``cos`` and ``sin`` times its
      ``attention_factor``), and ``gating`` puts a gate a head
      (``g_proj``) and the q/k norms on EVERY layer, so a gated layer
      stands on a ring;
    - SDAR's layers (``model_type: sdar_moe``; arXiv:2510.06303): the
      ``qwen3_moe`` layer (q/k norm, RoPE over the whole head, no gate)
      under a BLOCK-causal mask: a key at position ``p_k`` is seen from
      ``p_q`` iff ``p_k // block <= p_q // block`` (``block`` 1 is
      causal, every other family's);
    - Phi-4-mini-flash's layers (``model_type: phi4flash``; SambaY, Ren
      et al., arXiv:2507.06607, with the differential attention of Ye
      et al., arXiv:2410.05258, as the release's ``modeling_phi4flash.py``
      pairs it): NO positions, biases on ``W_qkv`` and ``W_o`` (``bias``),
      and with ``diff`` ADJACENT heads pair: query pair ``j`` is ``(q_2j,
      q_2j+1)``, key pair ``g = j // (group)`` is ``(k_2g, k_2g+1)`` with
      the value ``V_g = [v_2g | v_2g+1]``, twice a head wide;
      ``o_j = softmax(s q_2j k_2g^T) V_g - lambda softmax(s q_2j+1
      k_2g+1^T) V_g`` under the layer's mask, ``lambda = exp(lq1 . lk1) -
      exp(lq2 . lk2) + lambda0``, four learned vectors a LAYER,
      ``lambda0 = 0.8 - 0.6 exp(-0.3 index)`` with ``index`` the layer's
      PUBLISHED index; ``o_j <- rms(o_j) * w * (1 - lambda0)`` over its
      ``2 x head`` numbers (a plain weight). Both maps and both value
      halves come from ONE pass over the cache: a key pair is one key
      head of ``2 x head`` lanes as it lies in the row, ``q_2j`` enters
      as ``[q_2j | 0]`` and ``q_2j+1`` as ``[0 | q_2j+1]`` (the zeros
      select the pair's half in the score product), and the value
      product is over the pair's whole row: the geometry
      ``ops/cached_attention`` and its kernels already serve, at twice
      the score product's operations. With ``exports_as`` the layer's
      cache is handed to later layers; with ``source`` the layer is a
      CROSS layer: it has ``W_q`` and ``W_o`` only, reads the cache
      exported under that name (the token's own row among its rows) and
      writes nothing;
    - Keye-VL-2.0's decoder layers (``model_type: KeyeVL2``): the
      ``qwen3_moe`` layer under the causal mask with an ``indexer``, the
      lightning indexer of DeepSeek Sparse Attention. With ``x`` the
      layer's normed input: ``qI_j = RoPE(x W_qI)[j]`` for the index's
      ``heads`` heads, ONE index key ``kI = RoPE(LN(x W_kI))`` a row
      (a LayerNorm with weight and bias, the weight stored zero-centred
      like every norm's; RoPE over the whole index head at the layer's
      theta), ``w = x W_w`` a number a head; ``I[t, s] = sum_j w[t, j]
      relu(qI[t, j] . kI[s])`` over the rows ``s <= t`` of the episode,
      and the query attends to the ``min(t + 1, top_k)`` rows of the
      largest ``I``, ties to the lower position, by an EXACT top-k. The
      index is read through ``stop_gradient``: its five leaves lie in
      the parameter tree and take a gradient of exactly zero (no
      alignment loss is stated for it), and the PPO gradient reaches
      ``W_q``, ``W_k``, ``W_v``, ``W_o`` through the chosen rows only.
      bfloat16 operands with float32 accumulation in ``x W_qI``, ``x
      W_kI`` and ``qI . kI``; float32 for ``x W_w`` (precision highest),
      LN, RoPE, the relu, the sum over heads and the top-k. M-RoPE with
      ``mrope_section`` over text tokens, whose three components are
      equal, IS this RoPE.

    State: keys and values, ``(rows, kv heads x head)`` in the operands'
    type, one row a position, flat, so that the device tiles (rows, row)
    without padding 2 heads to 8; keys stored after norm and RoPE (YaRN's
    factor included); a window layer a RING of ``min(window, positions)``
    rows whatever the episode's depth (docs/policy_state.md, "The ring").
    With an ``indexer`` a THIRD leaf, the index keys ``(rows, index
    head)`` in the operands' type, stored after LN and RoPE at the slot
    of the row's position, written by the same scatter and, like the
    other two, left by a reset (docs/policy_state.md, "The index").

    A cross layer has none.

    Scopes: projections, norms and RoPE under ``attn`` (``swa`` for a
    window layer, ``xattn`` for a cross layer), the cached attention's
    parts under its ``/scatter``, ``/scores`` and ``/out``, the gate
    under ``/gate``, the subtraction, its norm and factor under
    ``/diff``, the output projection under ``/out``; an index's three
    projections, LN and RoPE under ``/index/proj``, its scores under
    ``/index/scores``, the top-k under ``/index/topk`` (``/select`` is
    kept for a lowering that fetches the chosen rows alone: none does).

    Statistics of an index (means over queries and layers):
    ``index_rows_scored_mean`` (rows a query saw),
    ``index_rows_selected_mean`` (rows it attended to, counted from the
    choice itself), ``index_selected_share_mean`` (the second over the
    first, a query), ``index_dense_query_share`` (queries that saw no
    more than ``top_k`` rows), and as ``index_decode_*`` what the
    one-token form scores and keeps at each of the fragment's
    positions."""

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
    # the mask's rule: positions a block (1: causal)
    block: int = 1
    # the differential form over pairs of adjacent heads, and the
    # layer's published index (its ``lambda0``)
    diff: bool = False
    index: int = 0
    # biases on the q/k/v and output projections
    bias: bool = False
    # the name its cache is exported under, and the name of the export
    # a cross layer reads instead of a cache of its own
    exports_as: Optional[str] = None
    source: Optional[str] = None
    # a learned index that chooses each query's rows (None: every row
    # the mask allows)
    indexer: Optional[Indexer] = None

    init_rules = {
        **{leaf: lambda key, shape: 0.1 * jax.random.normal(key, shape, jnp.float32)
           for leaf in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")},
        "diff_norm": lambda key, shape: jnp.ones(shape, jnp.float32),
    }
    stats = {
        # rows inside the window a query of a window layer saw
        "window_rows_seen_mean": "mean",
        "attn_key_blocks_skipped": "sum", "attn_key_blocks_walked": "sum",
        "attn_decode_key_blocks_skipped": "sum",
        "attn_decode_key_blocks_walked": "sum",
        "diff_lambda_mean": "mean",
        # layer-passes that read a cache they do not own, the rows a
        # query of such a layer saw, and its key blocks in both forms
        "shared_cache_reads": "sum", "xattn_rows_seen_mean": "mean",
        "xattn_key_blocks_skipped": "sum", "xattn_key_blocks_walked": "sum",
        "xattn_decode_key_blocks_skipped": "sum",
        "xattn_decode_key_blocks_walked": "sum",
        # a learned index's choice, in the fragment form and as the
        # one-token form makes it at the same positions; on request
        # (``ctx["choices"]``) the choice itself, a row a stream
        "index_choices": "tokens",
        **{f"index_{form}{stat}": "mean" for form in ("", "decode_") for stat in (
            "rows_scored_mean", "rows_selected_mean", "selected_share_mean",
            "dense_query_share")},
    }

    @property
    def scope(self) -> str:
        return "xattn" if self.source else "swa" if self.window else "attn"

    @property
    def lambda0(self) -> float:
        return 0.8 - 0.6 * float(np.exp(-0.3 * self.index))

    @property
    def rope(self) -> str:
        return "none" if not self.rotary else "yarn" if self.yarn else "default"

    def cache_rows(self, positions: int) -> int:
        return positions if self.window is None else min(self.window, positions)

    def param_shapes(self, d: int):
        wide = self.heads * self.head_dim
        shapes = dict(
            # every head's [q | gate] where the gate is a dimension's
            q_proj=(d, wide * (2 if self.gate == "element" else 1)),
            k_proj=(d, self.kv_heads * self.head_dim),
            v_proj=(d, self.kv_heads * self.head_dim),
            o_proj=(wide, d),
        )
        if self.qk_norm:
            shapes.update(q_norm=(self.head_dim,), k_norm=(self.head_dim,))
        if self.gate == "head":
            shapes["g_proj"] = (d, self.heads)
        if self.bias:
            shapes.update({leaf[0] + "_bias": (shapes[leaf][1],)
                           for leaf in ("q_proj", "k_proj", "v_proj", "o_proj")})
        if self.diff:
            shapes.update({f"lambda_{v}": (self.head_dim,)
                           for v in ("q1", "k1", "q2", "k2")})
            shapes["diff_norm"] = (2 * self.head_dim,)
        if self.source:  # a cross layer makes no keys and no values
            for leaf in ("k_proj", "v_proj", "k_bias", "v_bias"):
                shapes.pop(leaf, None)
        if self.indexer:
            ix = self.indexer
            shapes.update(
                index_q_proj=(d, ix.heads * ix.head_dim), index_k_proj=(d, ix.head_dim),
                index_w_proj=(d, ix.heads),
                **_INDEX_KEY_NORM.leaves("index_k_norm", ix.head_dim))
        return shapes

    def state_shapes(self, streams: int, positions: int, dtype):
        if self.source:
            return []
        rows = self.cache_rows(positions)
        shape = (streams, rows, self.kv_heads * self.head_dim)
        index = [((streams, rows, self.indexer.head_dim), dtype)] if self.indexer else []
        return [(shape, dtype), (shape, dtype)] + index

    def selection(self, p, x, index_cache, ctx, scope: str):
        """The index's operands of this call
        (``ops/cached_attention.Selection``), nothing of them
        differentiated."""
        ix, dtype = self.indexer, ctx["dtype"]
        b, t, _ = x.shape
        x = jax.lax.stop_gradient(x)
        p = {leaf: jax.lax.stop_gradient(p[leaf])
             for leaf in p if leaf.startswith("index_")}
        turned = lambda z: rope(z, ctx["positions"], ix.head_dim, self.theta)
        with jax.named_scope(scope + "/index/proj"):
            q = turned(dot(x, p["index_q_proj"], dtype).reshape(
                b, t, ix.heads, ix.head_dim))
            k = _INDEX_KEY_NORM(
                dot(x, p["index_k_proj"], dtype), p, "index_k_norm", ctx["eps"])
            k = turned(k[:, :, None])[:, :, 0]
            w = jnp.dot(x.astype(jnp.float32), p["index_w_proj"], precision=HI)
        return cached_attention.Selection(
            q.astype(dtype), w, k.astype(dtype), index_cache, ix.top_k)

    def _index_stats(self, ctx, selected=None):
        """The index's statistics of a fragment at ``ctx``'s positions:
        a query at position ``p`` saw ``p + 1`` rows; ``selected`` ``(B,
        T)`` the rows each attended to as the choice itself counted them
        (None: every row seen was chosen, ``top_k`` out of reach)."""
        seen = ctx["positions"].astype(jnp.float32) + 1.0
        kept = jnp.minimum(seen, float(self.indexer.top_k))
        dense = jnp.mean(seen <= self.indexer.top_k, dtype=jnp.float32)
        out = {}
        for form, got in (("", kept if selected is None else selected),
                          ("decode_", kept)):
            out.update({
                f"index_{form}rows_scored_mean": jnp.mean(seen),
                f"index_{form}rows_selected_mean": jnp.mean(got),
                f"index_{form}selected_share_mean": jnp.mean(got / seen),
                f"index_{form}dense_query_share": dense})
        return out

    def apply(self, p, x, state, ctx):
        scope = ctx["scope"] + self.scope
        dtype, eps = ctx["dtype"], ctx["eps"]
        b, t, _ = x.shape
        h, hkv, d = self.heads, self.kv_heads, self.head_dim
        metrics.inc_attention_layer_lowering(self.kind, h, self.rope)
        if self.window is not None:
            metrics.inc_window_cache_lowering("step" if t == 1 else "fragment")

        def projected(leaf, heads):
            z = dot(x, p[leaf + "_proj"], dtype)
            if self.bias:
                z = z + p[leaf + "_bias"]
            return z.reshape(b, t, heads, -1)

        with jax.named_scope(scope):
            q = projected("q", h)
            if self.gate == "element":
                q, gate = q[..., :d], q[..., d:]
            if self.source:
                # the owner's caches and its keys and values of these tokens
                state, (k, v) = ctx["imports"][self.source]
            else:
                k, v = projected("k", hkv), projected("v", hkv)
            own = (k, v)

            def normed_and_turned(z, norm):
                if self.qk_norm:
                    z = rms(z, p[norm], eps)
                if self.rotary:
                    z = rope(z, ctx["positions"], self.rotary, self.theta,
                             self.yarn, self.rope_factor)
                return z

            q = normed_and_turned(q, "q_norm")
            if not self.source:
                k = normed_and_turned(k, "k_norm")
            if self.diff:
                # a pair of heads as ONE key head twice as wide, the pair's
                # two queries as its heads, each zero outside its own half
                q = q.reshape(b, t, h // 2, 2, d)
                none = jnp.zeros_like(q[..., 0, :])
                q = jnp.stack(
                    [jnp.concatenate([q[..., 0, :], none], axis=-1),
                     jnp.concatenate([none, q[..., 1, :]], axis=-1)],
                    axis=3).reshape(b, t, h, 2 * d)
                k, v = (z.reshape(b, t, hkv // 2, 2 * d) for z in (k, v))
        select = None
        if self.indexer:
            select = self.selection(p, x, state[2], ctx, scope)
        o, new, stats = cached_attention.cached_attention(
            q, k, v, state[:2], ctx, scale=self.scale, window=self.window,
            dtype=dtype, scope=scope, block=self.block, scatter=not self.source,
            select=select)
        if ctx.get("keep"):
            new = new + (k, v)
        selected = stats.pop("index_rows_selected", None)
        if self.indexer and t > 1:  # the learn form's: the lane reads no statistics
            stats.update(self._index_stats(ctx, selected))
        if "pairs_seen" in stats:
            stats["window_rows_seen_mean"] = stats.pop("pairs_seen") / (b * t)
        if self.source:
            new = ()
            stats = {"x" + k: v for k, v in stats.items()}
            stats["shared_cache_reads"] = jnp.float32(1.0)
            stats["xattn_rows_seen_mean"] = jnp.mean(
                ctx["positions"].astype(jnp.float32)) + 1.0
        if self.gate is not None:
            with jax.named_scope(scope + "/gate"):
                if self.gate == "head":
                    gate = dot(x, p["g_proj"], dtype)[..., None]  # (B, T, H, 1)
                o = o * jax.nn.sigmoid(gate)
        if self.diff:
            with jax.named_scope(scope + "/diff"):
                lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
                       - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
                       + self.lambda0)
                o = o.reshape(b, t, h // 2, 2, 2 * d)
                o = o[..., 0, :] - lam * o[..., 1, :]
                o = rms(o, p["diff_norm"], eps, centred=False) * (1.0 - self.lambda0)
                stats["diff_lambda_mean"] = lam
        if self.exports_as:
            # the one-token form: the cache AFTER this token's row was
            # written; a fragment: the stored rows, and the fragment's own
            # keys and values beside them
            step = ctx.get("step", t == 1)
            new = new + ({self.exports_as: (new[:2] if step else tuple(state), own)},)
        with jax.named_scope(scope + "/out"):
            out = dot(o.reshape(b, t, h * d), p["o_proj"], dtype)
            return (out + p["o_bias"] if self.bias else out), new, stats


@dataclasses.dataclass(frozen=True)
class LatentLayer(Kind):
    """``"latent_attention"`` (DeepSeek-V3's ``DeepseekV3Attention``,
    arXiv:2412.19437, with YaRN as that file applies it): ``c_q = rms(x
    W_qa)``, ``q = c_q W_qb`` per head ``[q_nope | q_pe]``; ``[c_kv |
    k_pe] = x W_kva``, ``c_kv = rms(c_kv)``, one roped ``k_pe`` for all
    heads; ``[k_nope | v]`` per head ``= c_kv W_kvb``; ``score = (q_nope .
    k_nope + rope(q_pe) . k_pe) * s``, ``s = (nope + rope)^-1/2 * m^2``,
    causal softmax, ``o = P v``, output projection. The query/key product
    is ``nope + rope`` wide, the value product ``v_head``
    (``ops/latent_attention.py`` holds its forms and picks). A config
    whose ``q_lora_rank`` is null has NO query latent (``q_latent``
    None): ``q = x W_q``, one matrix and no query norm
    (``bailing_hybrid``, which also turns ADJACENT pairs in RoPE,
    ``interleave``, and multiplies each head's output by ``sigmoid(x
    W_g)``, a number a head and token, ``gate``).

    State: ONE leaf of latent rows, ``(positions, kv_latent + rope_dim)``
    in the operands' type: the normed latent and the roped key part,
    whatever the head count. Scopes: ``mla`` and its ``/absorb``,
    ``/scores``, ``/out``."""

    heads: int
    q_latent: Optional[int]  # None: no query latent, ``q = x W_q``
    kv_latent: int
    nope: int
    rope_dim: int
    v_head: int
    inv_freq: Tuple[float, ...]  # ``yarn_inv_freq`` of the rope part
    softmax_scale: float
    # RoPE turns adjacent pairs (``rope_interleave``), not the halves
    interleave: bool = False
    # the output times ``sigmoid(x W_g)``, a number a head (``g_proj``)
    gate: bool = False

    stats = {
        "attn_key_blocks_skipped": "sum", "attn_key_blocks_walked": "sum",
        "attn_decode_key_blocks_skipped": "sum",
        "attn_decode_key_blocks_walked": "sum",
    }

    def param_shapes(self, d: int):
        h, row = self.heads, self.kv_latent + self.rope_dim
        q_width = h * (self.nope + self.rope_dim)
        shapes = dict(
            kv_a=(d, row),
            kv_a_norm=(self.kv_latent,),
            kv_b=(self.kv_latent, h * (self.nope + self.v_head)),
            o_proj=(h * self.v_head, d),
        )
        if self.q_latent is None:
            shapes["q_proj"] = (d, q_width)
        else:
            shapes.update(q_a=(d, self.q_latent), q_a_norm=(self.q_latent,),
                          q_b=(self.q_latent, q_width))
        if self.gate:
            shapes["g_proj"] = (d, h)
        return shapes

    def state_shapes(self, streams: int, positions: int, dtype):
        return [((streams, positions, self.kv_latent + self.rope_dim), dtype)]

    def apply(self, p, x, state, ctx):
        with jax.named_scope(ctx["scope"] + "mla"):
            (cache,) = state
            dtype, eps, positions = ctx["dtype"], ctx["eps"], ctx["positions"]
            b, t, _ = x.shape
            h, dn, c = self.heads, self.nope, self.kv_latent
            inv_freq = np.asarray(self.inv_freq, np.float32)
            rope = lambda r: latent_attention.rope(
                r, positions, inv_freq, self.interleave)
            if self.q_latent is None:
                q = dot(x, p["q_proj"], dtype)
            else:
                q = dot(rms(dot(x, p["q_a"], dtype), p["q_a_norm"], eps), p["q_b"], dtype)
            q = q.reshape(b, t, h, dn + self.rope_dim)
            q_pe = rope(q[..., dn:])
            kv = dot(x, p["kv_a"], dtype)
            k_pe = rope(kv[:, :, None, c:])[:, :, 0]
            rows_new = jnp.concatenate(
                [rms(kv[..., :c], p["kv_a_norm"], eps), k_pe], axis=-1
            ).astype(cache.dtype)
            o, new_cache, stats = latent_attention.latent_attention(
                q[..., :dn], q_pe, rows_new, cache, p["kv_b"], ctx,
                scale=self.softmax_scale, dtype=dtype)
            if self.gate:
                with jax.named_scope("gate"):
                    o = o * jax.nn.sigmoid(dot(x, p["g_proj"], dtype))[..., None]
            return (dot(o.reshape(b, t, h * self.v_head), p["o_proj"], dtype),
                    (new_cache,), stats)


@dataclasses.dataclass(frozen=True)
class EvaLayer(Kind):
    """``"eva_attention"`` (``model_type: evabyte``, ``attention_class:
    "eva"``; Zheng, Yuan, Wang, Kong, "Efficient Attention via Control
    Variates", ICLR 2023, arXiv:2302.04542, in the causal,
    learned-proposal parameterisation of the EvaByte release: ``eva.py``,
    ``eva_prep_kv_kernel.py``, ``eva_agg_kernel.py`` beside the published
    ``config.json``). ``q, k, v = h W_q, h W_k, h W_v``, as many key
    heads as query heads, no bias; RoPE (rotate-half, ``theta``, the
    whole head) on ``q`` and ``k``; ``s = head^-1/2``. Per head, with
    learned ``phi``, ``mu`` in ``R^head`` (``eva_phi``, ``eva_mu``),
    window ``W`` and chunk ``c``:

    - chunk ``j`` = positions ``c j .. c j + c - 1``, summarised when its
      last token is written: ``kbar_j = sum_i softmax_i(phi . k_i) k_i``,
      ``vbar_j = sum_i softmax_i(mu . k_i) v_i`` (``k_i`` after RoPE);
    - ``S_t = {i : i // W == t // W, i <= t}`` (the query's own window,
      exact); ``R_t = {j : c j + c - 1 < W (t // W)}`` (every chunk of
      every earlier window);
    - ``o_t = [sum_S e^{s q_t.k_i} v_i + sum_R e^{s q_t.kbar_j} vbar_j] /
      [sum_S e^{s q_t.k_i} + sum_R e^{s q_t.kbar_j}]``: ONE softmax;
    - ``y = W_o o``.

    ``mu`` and ``phi`` are parameters of the CACHE WRITE: the learn form
    differentiates through the summaries a fragment makes. The chip
    holds ``heads`` of the layer's heads, from ``first`` on
    (``heads_held``): its heads' part of ``W_o o``, the shares adding up
    to the uncut layer's (:meth:`share_of`).

    State: TWO pairs of leaves on TWO clocks (docs/policy_state.md, "Two
    stores on two clocks"): keys and values of the window store, one row
    a token, ``min(W, positions)`` rows, position ``p`` in slot ``p mod
    W``; keys and values of the summary store, one row a chunk,
    ``ceil(positions / c)`` rows. Not cleared on a reset: the masks
    follow the position. Scopes: ``eva`` and its ``/scatter``,
    ``/summarise``, ``/scores``, ``/out``."""

    heads: int
    head_dim: int
    window: int
    chunk: int
    theta: float
    # of the uncut layer's heads, those from ``first`` on
    first: int = 0

    init_rules = {
        leaf: lambda key, shape: jnp.clip(
            jax.random.normal(key, shape, jnp.float32), -1.0, 1.0) * shape[-1] ** -0.5
        for leaf in ("eva_mu", "eva_phi")}
    stats = {
        # rows inside each mask a query saw
        "eva_window_rows_seen_mean": "mean", "eva_summary_rows_seen_mean": "mean",
        "eva_chunks_summarised": "sum", "eva_fragments_crossing_a_window": "sum",
        # of a one-token step at each of the fragment's positions
        "eva_window_key_blocks_skipped": "sum", "eva_window_key_blocks_walked": "sum",
        "eva_summary_key_blocks_skipped": "sum", "eva_summary_key_blocks_walked": "sum",
        # spans the step kernel fetches (a copy a leaf each), and their rows
        "eva_step_copies": "sum", "eva_step_rows_fetched_mean": "mean",
    }

    def param_shapes(self, d: int):
        wide = self.heads * self.head_dim
        return dict(
            q_proj=(d, wide), k_proj=(d, wide), v_proj=(d, wide), o_proj=(wide, d),
            eva_mu=(self.heads, self.head_dim), eva_phi=(self.heads, self.head_dim))

    def state_shapes(self, streams: int, positions: int, dtype):
        row = self.heads * self.head_dim
        exact = (streams, min(self.window, positions), row)
        pooled = (streams, -(-positions // self.chunk), row)
        return [(exact, dtype), (exact, dtype), (pooled, dtype), (pooled, dtype)]

    def share_of(self, p):
        """This share's leaves out of an uncut layer's ``p``: its heads'
        columns of ``W_q``, ``W_k``, ``W_v``, rows of ``W_o`` and vectors."""
        lo, hi = self.first * self.head_dim, (self.first + self.heads) * self.head_dim
        cut = {"o_proj": p["o_proj"][lo:hi]}
        cut.update({leaf: p[leaf][:, lo:hi] for leaf in ("q_proj", "k_proj", "v_proj")})
        cut.update({leaf: p[leaf][self.first:self.first + self.heads]
                    for leaf in ("eva_mu", "eva_phi")})
        return {**p, **cut}

    def apply(self, p, x, state, ctx):
        scope = ctx["scope"] + "eva"
        dtype = ctx["dtype"]
        b, t, _ = x.shape
        h, d = self.heads, self.head_dim
        with jax.named_scope(scope):
            q, k, v = (dot(x, p[leaf], dtype).reshape(b, t, h, d)
                       for leaf in ("q_proj", "k_proj", "v_proj"))
            q, k = (rope(z, ctx["positions"], d, self.theta) for z in (q, k))
        o, new, stats = eva_attention.eva_attention(
            q, k, v, p["eva_phi"], p["eva_mu"], state, ctx, scale=d ** -0.5,
            window=self.window, chunk=self.chunk, dtype=dtype, scope=scope)
        with jax.named_scope(scope + "/out"):
            return dot(o.reshape(b, t, h * d), p["o_proj"], dtype), new, stats


_ones = lambda key, shape: jnp.ones(shape, jnp.float32)


@dataclasses.dataclass(frozen=True)
class DeltaNetLayer(Kind):
    """``"linear_attention"`` (``Qwen3NextGatedDeltaNet``; Yang et al.,
    arXiv:2412.06464): one projection gives ``q, k, v, z``, another ``b,
    a``; a causal depthwise convolution (width ``conv``) and SiLU over
    the channels of ``(q, k, v)``; ``beta = sigmoid(b)``, ``g =
    -exp(A_log) * softplus(a + dt_bias)``; ``q`` and ``k`` L2-normalised;
    the gated delta rule (``ops/deltanet.py``: one token the step, a
    fragment in chunks) per value head; ``rms(o) * w * silu(z)`` per
    head, then the output projection. Starts with ``A`` uniform in (1,
    16) and ``dt_bias`` one.

    State: the ``(value heads, dk, dv)`` float32 matrix and the last
    ``conv - 1`` inputs of the convolution. Scopes: ``linear_attn`` and,
    around the rule alone in both forms, its ``/rule``."""

    k_heads: int
    v_heads: int
    dk: int
    dv: int
    conv: int

    cleared_on_reset = True
    init_rules = {
        "A_log": lambda key, shape: jnp.log(
            jax.random.uniform(key, shape, minval=1.0, maxval=16.0)),
        "dt_bias": _ones, "gdn_norm": _ones}

    @property
    def widths(self):
        """Of the keys, the values and the convolution's channels."""
        kd, vd = self.k_heads * self.dk, self.v_heads * self.dv
        return kd, vd, 2 * kd + vd

    def param_shapes(self, d: int):
        kd, vd, conv_dim = self.widths
        return dict(
            in_proj_qkvz=(d, 2 * kd + 2 * vd),
            in_proj_ba=(d, 2 * self.v_heads),
            conv=(conv_dim, self.conv),
            A_log=(self.v_heads,),
            dt_bias=(self.v_heads,),
            gdn_norm=(self.dv,),
            out_proj=(vd, d),
        )

    def state_shapes(self, streams: int, positions: int, dtype):
        return [((streams, self.v_heads, self.dk, self.dv), jnp.float32),
                ((streams, self.conv - 1, self.widths[2]), jnp.float32)]

    def apply(self, p, x, state, ctx):
        with jax.named_scope(ctx["scope"] + "linear_attn"):
            s0, tail = state
            dtype = ctx["dtype"]
            b, t, _ = x.shape
            (kd, vd, _), hv, hk = self.widths, self.v_heads, self.k_heads
            qkvz = dot(x, p["in_proj_qkvz"], dtype)
            mixed, z = qkvz[..., : 2 * kd + vd], qkvz[..., 2 * kd + vd :]
            ba = jnp.dot(x, p["in_proj_ba"], precision=HI)
            beta = jax.nn.sigmoid(ba[..., :hv])
            g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])

            mixed, new_tail = causal_conv(tail, mixed, ctx["seg"], p["conv"])

            q = mixed[..., :kd].reshape(b, t, hk, self.dk)
            k = mixed[..., kd : 2 * kd].reshape(b, t, hk, self.dk)
            v = mixed[..., 2 * kd :].reshape(b, t, hv, self.dv)
            q = l2norm(q) * (self.dk ** -0.5)
            k = l2norm(k)
            q = jnp.repeat(q, hv // hk, axis=2)
            k = jnp.repeat(k, hv // hk, axis=2)
            with jax.named_scope("rule"):
                if t == 1:
                    s1, o = deltanet.gated_delta_step(
                        s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0]
                    )
                    o = o[:, None]
                else:
                    o, s1 = deltanet.gated_delta_chunked(
                        s0, q, k, v, g, beta,
                        resets=ctx["fresh"].astype(jnp.float32),
                        chunk=ctx["chunk"],
                    )
            o = rms(o, p["gdn_norm"], ctx["eps"], centred=False)
            o = o * jax.nn.silu(z.reshape(b, t, hv, self.dv))
            return dot(o.reshape(b, t, vd), p["out_proj"], dtype), (s1, new_tail), {}


@dataclasses.dataclass(frozen=True)
class KDALayer(Kind):
    """``"kimi_delta_attention"`` (Kimi Delta Attention: Kimi Linear,
    arXiv:2510.26692, over the gated delta rule of arXiv:2412.06464; as
    ``model_type: bailing_hybrid`` configures it): ``q~ = x W_q``, ``k~ =
    x W_k``, ``v~ = x W_v``, each through its OWN causal depthwise
    convolution (width ``conv``, no bias) and SiLU; per head ``q =
    l2norm(q~) dk^-1/2``, ``k = l2norm(k~)``, ``v = v~``. The decay is a
    number a head AND KEY CHANNEL from one matrix (``no_kda_lora``): ``a
    = x W_f``, ``g = lower * sigmoid(exp(A_log_h) (a + dt_bias))``, so
    every log-decay lies in ``(lower, 0)`` (``kda_safe_gate``,
    ``kda_lower_bound`` -5: what lets ``ops/deltanet.py``'s chunked form
    keep sub-blocks of 16 in float32; the layer adds no clip). ``beta =
    sigmoid(x W_b)``, one a head. The rule, per head with a ``(dk, dv)``
    float32 state: ``S <- diag(exp(g_t)) S; d = beta_t (v_t - S^T k_t);
    S <- S + k_t d^T; o_t = S^T q_t`` (one token the step, a fragment in
    chunks). ``o <- rms(o) * w`` per head, times ``sigmoid(x W_g)``, a
    number a head and token (``head_wise``), then ``W_o``. No positions.
    As many key heads as heads. Starts with ``A`` uniform in (1, 16) and
    ``dt_bias`` zero.

    State: the ``(heads, dk, dv)`` float32 matrix and the last ``conv -
    1`` inputs of each of the three convolutions. Scopes: ``kda`` and
    its ``/gate`` (the decay, ``beta``), ``/conv``, ``/rule``, ``/out``
    (norm, output gate, ``W_o``); the three projections under ``kda``
    itself."""

    heads: int
    dk: int
    dv: int
    conv: int
    lower: float  # the log-decay's bound, below 0

    cleared_on_reset = True
    init_rules = {
        "A_log": lambda key, shape: jnp.log(
            jax.random.uniform(key, shape, minval=1.0, maxval=16.0)),
        "kda_norm": _ones}

    def param_shapes(self, d: int):
        kd, vd, h = self.heads * self.dk, self.heads * self.dv, self.heads
        return dict(
            q_proj=(d, kd), k_proj=(d, kd), v_proj=(d, vd),
            q_conv=(kd, self.conv), k_conv=(kd, self.conv), v_conv=(vd, self.conv),
            f_proj=(d, kd), A_log=(h,), dt_bias=(kd,),
            b_proj=(d, h), g_proj=(d, h),
            kda_norm=(self.dv,), out_proj=(vd, d),
        )

    def state_shapes(self, streams: int, positions: int, dtype):
        kd, vd = self.heads * self.dk, self.heads * self.dv
        return [((streams, self.heads, self.dk, self.dv), jnp.float32)] + [
            ((streams, self.conv - 1, width), jnp.float32) for width in (kd, kd, vd)]

    def operands(self, p, x, tails, ctx):
        """What the rule reads of ``x`` ``(B, T, D)``: ``((q, k, v, g,
        beta), the convolutions' new tails)``, ``g`` a number a head and
        key channel inside ``(lower, 0)``. Under the caller's ``kda``
        scope; a comparison of the rule alone calls it too
        (``perf/checks/kda_rule.py``)."""
        dtype = ctx["dtype"]
        b, t, _ = x.shape
        h, dk, dv = self.heads, self.dk, self.dv
        with jax.named_scope("gate"):
            a = dot(x, p["f_proj"], dtype) + p["dt_bias"]
            g = self.lower * jax.nn.sigmoid(
                jnp.exp(p["A_log"])[:, None] * a.reshape(b, t, h, dk))
            beta = jax.nn.sigmoid(jnp.dot(x, p["b_proj"], precision=HI))
        mixed, new_tails = [], []
        for name, tail in zip("qkv", tails):
            y = dot(x, p[name + "_proj"], dtype)
            with jax.named_scope("conv"):
                y, tail = causal_conv(tail, y, ctx["seg"], p[name + "_conv"])
            mixed.append(y)
            new_tails.append(tail)
        with jax.named_scope("rule"):
            q = l2norm(mixed[0].reshape(b, t, h, dk)) * (dk ** -0.5)
            k = l2norm(mixed[1].reshape(b, t, h, dk))
            v = mixed[2].reshape(b, t, h, dv)
        return (q, k, v, g, beta), new_tails

    def apply(self, p, x, state, ctx):
        with jax.named_scope(ctx["scope"] + "kda"):
            s0, *tails = state
            dtype = ctx["dtype"]
            b, t, _ = x.shape
            (q, k, v, g, beta), new_tails = self.operands(p, x, tails, ctx)
            with jax.named_scope("rule"):
                if t == 1:
                    s1, o = deltanet.gated_delta_step(
                        s0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
                    o = o[:, None]
                else:
                    o, s1 = deltanet.gated_delta_chunked(
                        s0, q, k, v, g, beta,
                        resets=ctx["fresh"].astype(jnp.float32), chunk=ctx["chunk"])
            with jax.named_scope("out"):
                o = rms(o, p["kda_norm"], ctx["eps"], centred=False)
                o = o * jax.nn.sigmoid(dot(x, p["g_proj"], dtype))[..., None]
                out = dot(o.reshape(b, t, self.heads * self.dv), p["out_proj"], dtype)
            return out, (s1, *new_tails), {}


@dataclasses.dataclass(frozen=True)
class NoSublayer(Kind):
    """The half a block of ONE sublayer does not have (``nemotron_h``:
    every character of ``hybrid_override_pattern`` is ``h <- h +
    f(rms(h))`` with ONE ``f``, a mixer or a feed-forward, under ONE
    norm). No leaves, no state, no scope, and no norm and no residual
    around it: ``SequenceLM`` gives a block the norms of the halves it
    has and runs those."""

    absent = True


def _inverse_softplus_of_a_step(key, shape):
    dt = jnp.exp(jax.random.uniform(
        key, shape, minval=np.log(1e-3), maxval=np.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


@dataclasses.dataclass(frozen=True)
class MambaLayer(Kind):
    """``"mamba"`` (Mamba-2 as Hugging Face ``granitemoehybrid`` and
    ``nemotron_h`` hold it; Dao & Gu, arXiv:2405.21060): ``[z | x | B | C
    | dt] = h W_in`` (no bias); a causal depthwise convolution (width
    ``conv``) WITH a bias and SiLU over the channels of ``(x, B, C)``;
    ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; per head
    ``S <- exp(dt A) S + dt x B^T``, ``y = S C + D x`` (``ops/ssd.py``);
    ``rms(y * silu(z)) * w``, then the output projection. ``B`` and ``C``
    are ``groups`` rows of ``state`` numbers, group-major, head ``h``
    reading row ``h // (heads / groups)``, and the gated norm is an RMS
    over each group's ``inner / groups`` numbers: Granite 4.0-H has ONE
    group (rows every head shares, a norm over the whole inner width),
    Nemotron-H 8 (of 8 heads and 512 numbers each). Starts as the
    families' do: ``A`` 1..heads, ``D`` one, ``dt_bias`` the inverse
    softplus of a log-uniform step in (0.001, 0.1).

    A run of consecutive layers is one stacked group (a layer between
    other kinds' blocks a run of one). State, a RUN: two
    leaves with the layer axis after the stream's, the ``(layers, heads,
    head, state)`` float32 matrices and the last ``conv - 1`` inputs of
    each convolution (the one-token form is handed the run's matrices
    whole with the layer's index and updates that layer where it lies,
    the fragment form scans over them). Scopes: ``ssm/in``, ``ssm/conv``,
    ``ssm/step``, ``ssm/out``."""

    heads: int
    head: int
    state: int
    conv: int
    conv_bias: bool
    # how the published code computes a fragment, not a width
    chunk: int
    # rows of ``B`` and of ``C``, and groups of the gated norm
    groups: int = 1

    stacked = True
    cleared_on_reset = True
    init_rules = {
        "A_log": lambda key, shape: jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape),
        "D": _ones, "dt_bias": _inverse_softplus_of_a_step,
        "conv": lambda key, shape: (
            jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-1])),
    }
    # the largest step size a stream saw
    stats = {"ssm_dt_max": "max"}

    @property
    def inner(self) -> int:
        return self.heads * self.head

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.groups * self.state

    def param_shapes(self, d: int):
        shapes = dict(
            # columns [z | x | B | C | dt]
            in_proj=(d, self.inner + self.conv_dim + self.heads),
            conv=(self.conv_dim, self.conv),
            dt_bias=(self.heads,),
            A_log=(self.heads,),
            D=(self.heads,),
            ssm_norm=(self.inner,),
            out_proj=(self.inner, d),
        )
        if self.conv_bias:
            shapes["conv_bias"] = (self.conv_dim,)
        return shapes

    def state_shapes(self, streams: int, positions: int, dtype):
        return [((streams, self.heads, self.head, self.state), jnp.float32),
                ((streams, self.conv - 1, self.conv_dim), jnp.float32)]

    def apply(self, p, x, state, ctx):
        scope = ctx["scope"] + "ssm"
        dtype = ctx["dtype"]
        s0, tail = state
        b, t, _ = x.shape
        inner, n, heads = self.inner, self.groups * self.state, self.heads
        with jax.named_scope(scope + "/in"):
            zxbcdt = dot(x, p["in_proj"], dtype)
            z = zxbcdt[..., :inner]
            mixed = zxbcdt[..., inner : inner + self.conv_dim]
            dt = jax.nn.softplus(zxbcdt[..., inner + self.conv_dim :] + p["dt_bias"])
            a = -jnp.exp(p["A_log"])
        with jax.named_scope(scope + "/conv"):
            mixed, new_tail = causal_conv(
                tail, mixed, ctx["seg"], p["conv"], p.get("conv_bias"))
        with jax.named_scope(scope + "/step"):
            xs = mixed[..., :inner].reshape(b, t, heads, self.head)
            bt, ct = mixed[..., inner : inner + n], mixed[..., inner + n :]
            if self.groups > 1:  # a row a group (``ops/ssd.py``)
                bt, ct = (v.reshape(b, t, self.groups, self.state) for v in (bt, ct))
            if t == 1:  # the run's stacked matrices and this layer's index
                stacked, layer = s0
                s1, y = ssd.ssd_step(
                    stacked, xs[:, 0], dt[:, 0], a, bt[:, 0], ct[:, 0], layer=layer)
                y = y[:, None]
            else:
                y, s1 = ssd.ssd_chunked(
                    s0, xs, dt, a, bt, ct,
                    resets=ctx["fresh"].astype(jnp.float32), chunk=self.chunk,
                )
            y = (y + p["D"][:, None] * xs).reshape(b, t, inner)
        with jax.named_scope(scope + "/out"):
            y, weight = y * jax.nn.silu(z), p["ssm_norm"]
            if self.groups > 1:  # an RMS over each group's numbers
                y = rms(y.reshape(b, t, self.groups, -1),
                        weight.reshape(self.groups, -1), ctx["eps"]).reshape(b, t, inner)
            else:
                y = rms(y, weight, ctx["eps"])
            return (dot(y, p["out_proj"], dtype), (s1, new_tail),
                    {"ssm_dt_max": jnp.max(dt)})


@dataclasses.dataclass(frozen=True)
class SelectiveScanLayer(Kind):
    """``"selective_scan"`` (Mamba-1: Gu & Dao, arXiv:2312.00752, as the
    state-space layers of Samba, arXiv:2406.07522, and of SambaY /
    ``model_type: phi4flash``, arXiv:2507.06607, hold it): ``[u | z] = h
    W_in`` (no bias); ``u <- silu(conv(u) + b_conv)``, causal, depthwise,
    width ``conv``, per episode; ``[r | B | C] = u W_x`` (``dt_rank + 2
    state`` columns, no bias); ``dt = softplus(r W_dt + b_dt)``, a number
    a CHANNEL; ``A = -exp(A_log)``, a number a (state, channel); per
    channel ``c`` and state ``n`` ``S_t[n, c] = exp(dt_t[c] A[n, c])
    S_{t-1}[n, c] + dt_t[c] B_t[n] u_t[c]``, ``y_t[c] = sum_n S_t[n, c]
    C_t[n] + D[c] u_t[c]`` (``ops/selective_scan.py``); ``F = (y *
    silu(z)) W_out``. What Mamba-2 (:class:`MambaLayer`) has and this
    has not: heads, ONE decay a head, ``B`` / ``C`` groups, a gated norm;
    what this has and Mamba-2 has not: a low-rank ``dt`` and a decay a
    (state, channel). Starts as the family's does: ``A`` 1..state a
    channel, ``D`` one, ``b_dt`` the inverse softplus of a log-uniform
    step in (0.001, 0.1), ``W_dt`` uniform in ``+-dt_rank^-1/2``.

    With ``exports_as`` the layer hands ``m_t = y_t`` (after the ``D``
    skip, BEFORE the gate ``silu(z)``, float32) to later layers: SambaY's
    memory, an ACTIVATION of the same pass, not state.

    State: the ``(state, inner)`` float32 matrix (the channels on the
    lanes) and the last ``conv - 1`` inputs of the convolution; zeroed at
    an episode's start. Scopes: ``scan/in`` (``W_in``, and ``W_x``,
    ``W_dt`` and the softplus after the convolution), ``scan/conv``,
    ``scan/step`` (the recurrence and the skip), ``scan/out``."""

    inner: int
    state: int
    dt_rank: int
    conv: int
    exports_as: Optional[str] = None

    cleared_on_reset = True
    init_rules = {
        # (state, inner): 1..state down every channel
        "A_log": lambda key, shape: jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[-2] + 1, dtype=jnp.float32))[:, None], shape),
        "D": _ones, "dt_bias": _inverse_softplus_of_a_step,
        "dt_proj": lambda key, shape: jax.random.uniform(
            key, shape, jnp.float32, -1.0, 1.0) * shape[-2] ** -0.5,
        "conv": lambda key, shape: (
            jax.random.normal(key, shape, jnp.float32) / np.sqrt(shape[-1])),
    }
    # the largest step size a stream saw
    stats = {"scan_dt_max": "max"}

    def param_shapes(self, d: int):
        i, n, r = self.inner, self.state, self.dt_rank
        return dict(
            in_proj=(d, 2 * i),  # columns [u | z]
            conv=(i, self.conv), conv_bias=(i,),
            x_proj=(i, r + 2 * n),  # columns [r | B | C]
            dt_proj=(r, i), dt_bias=(i,),
            A_log=(n, i), D=(i,),
            out_proj=(i, d),
        )

    def state_shapes(self, streams: int, positions: int, dtype):
        return [((streams, self.state, self.inner), jnp.float32),
                ((streams, self.conv - 1, self.inner), jnp.float32)]

    def apply(self, p, x, state, ctx):
        scope = ctx["scope"] + "scan"
        dtype = ctx["dtype"]
        s0, tail = state
        t = x.shape[1]
        i, n, r = self.inner, self.state, self.dt_rank
        with jax.named_scope(scope + "/in"):
            uz = dot(x, p["in_proj"], dtype)
            u, z = uz[..., :i], uz[..., i:]
        with jax.named_scope(scope + "/conv"):
            u, new_tail = causal_conv(tail, u, ctx["seg"], p["conv"], p["conv_bias"])
        with jax.named_scope(scope + "/in"):
            rbc = dot(u, p["x_proj"], dtype)
            bt, ct = rbc[..., r : r + n], rbc[..., r + n :]
            dt = jax.nn.softplus(dot(rbc[..., :r], p["dt_proj"], dtype) + p["dt_bias"])
            a = -jnp.exp(p["A_log"])
        with jax.named_scope(scope + "/step"):
            if t == 1:
                s1, y = selective_scan.selective_step(
                    s0, u[:, 0], dt[:, 0], a, bt[:, 0], ct[:, 0])
                y = y[:, None]
            else:
                y, s1 = selective_scan.selective_scan(
                    s0, u, dt, a, bt, ct, ctx["fresh"].astype(jnp.float32))
            y = y + p["D"] * u
        new = (s1, new_tail)
        if self.exports_as:
            new = new + ({self.exports_as: y},)
        with jax.named_scope(scope + "/out"):
            return (dot(y * jax.nn.silu(z), p["out_proj"], dtype), new,
                    {"scan_dt_max": jnp.max(dt)})


@dataclasses.dataclass(frozen=True)
class GatedMemoryLayer(Kind):
    """``"gated_memory"`` (SambaY's Gated Memory Unit, Ren et al.,
    arXiv:2507.06607, section 2): ``F = (m * silu(h W_1)) W_2`` with
    ``m`` the memory of the SAME token that the layer named ``source``
    exported (a :class:`SelectiveScanLayer`'s scan output, float32):
    token-wise, two matrices, no bias, no scan and NO state. Scope:
    ``gmu``."""

    inner: int
    source: str

    def param_shapes(self, d: int):
        return dict(gmu_in=(d, self.inner), gmu_out=(self.inner, d))

    def apply(self, p, x, state, ctx):
        with jax.named_scope(ctx["scope"] + "gmu"):
            gate = jax.nn.silu(dot(x, p["gmu_in"], ctx["dtype"]))
            return dot(ctx["imports"][self.source] * gate, p["gmu_out"],
                       ctx["dtype"]), (), {}


# -- feed-forwards --------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DenseLayer(Kind):
    """``"dense"``: SwiGLU of width ``intermediate_size``
    (``shared_intermediate_size`` where the config states one). Scope:
    ``mlp``."""

    width: int

    def param_shapes(self, d: int):
        w = self.width
        return dict(mlp_gate=(d, w), mlp_up=(d, w), mlp_down=(w, d))

    def apply(self, p, x, state, ctx):
        with jax.named_scope(ctx["scope"] + "mlp"):
            b, t, d = x.shape
            out = moe.gated_mlp(
                x.reshape(b * t, d), p["mlp_gate"], p["mlp_up"], p["mlp_down"],
                dtype=ctx["dtype"],
            )
        return out.reshape(b, t, d), (), {}


@dataclasses.dataclass(frozen=True)
class ExpertLayer(Kind):
    """``"experts"``: a router over ALL ``router_outputs`` experts, the
    ``held`` experts this chip holds from ``first`` on (``ops/moe.py``)
    and a shared expert of ``shared_width`` (0: none, the result is the
    routed sum alone). What the families differ in is the fields:

    - ``qwen3_next``: softmax, top-k, renormalised, the shared expert
      times ``sigmoid(x w_s)`` (``shared_gated``);
    - DeepSeek-V3 (``scoring_func: sigmoid``, ``topk_method:
      noaux_tc``): a sigmoid each, the top-k of ``score + select_bias``
      chosen, weights the scores without the bias over their sum times
      ``scale``, the shared expert ungated. ``select_bias`` is a buffer:
      it lies in the parameter tree, starts small and not zero, and the
      model reads it through ``stop_gradient``;
    - SmallThinker: softmax, top-k, renormalised like ``qwen3_next``,
      but the router reads the LAYER'S INPUT, un-normed and before the
      mixer runs (``route_on: "input"``; the experts still take ``rms``
      of the stream after the mixer), the experts gate with ReLU
      (ReGLU) where the others gate with SiLU, and there is NO shared
      expert;
    - Laguna: a sigmoid each, top-k with no selection bias, the chosen
      scores over their sum times ``scale``, the shared expert UNGATED;
    - SDAR (``qwen3_moe``'s layer): softmax, top-k, renormalised, NO
      shared expert; the model generates by masked diffusion, so every
      ``[MASK]`` of a noisy pass meets the first layer's router as the
      same vector and ``alone`` of the held experts may take a whole
      pass's tokens;
    - ``bailing_hybrid`` (Ling 3.0): DeepSeek-V3's router WITH its
      groups: the ``router_outputs`` are ``n_group`` groups of
      consecutive experts, a token keeps the ``topk_group`` groups whose
      two best ``score + select_bias`` add up highest and takes its
      top-k among their experts only (``n_group`` 1, every other
      family's: the plain top-k). Where the held experts lie in one
      group, the tokens that did not choose it send nothing here;
      ``moe_held_group_chosen_share`` is the share that did.

    Scopes: ``moe/route`` (entered before the mixer where the router
    reads the input), ``moe/experts``, ``moe/shared``."""

    router_outputs: int
    first: int
    held: int
    top_k: int
    norm_topk: bool
    width: int
    route_on: str = "stream"  # or "input": the block's, before the mixer
    activation: str = "silu"
    scoring: str = "softmax"
    select_bias: bool = False
    scale: float = 1.0
    shared_width: int = 0
    shared_gated: bool = False
    # an expert, routed or shared, is ``(act(x Wg) * (x Wu)) Wd``; False:
    # ``act(x Wu) Wd``, no gate matrix
    gated: bool = True
    # held experts of a grouped call that may outgrow their buffers and
    # run over every token before the call goes dense (``ops/moe.py``)
    alone: int = 0
    # the router's groups of consecutive experts, and those a token
    # keeps before its top-k (1 and 1: no groups)
    n_group: int = 1
    topk_group: int = 1

    init_rules = {
        "select_bias": lambda key, shape: 0.01 * jax.random.normal(key, shape)}
    stats = {
        # tokens each held expert got: ``(held,)`` a layer, and at each
        # place of the fragment over the streams, ``(T, held)``
        "moe_held_load": "layer", "moe_place_load": "layer",
        "moe_slots_on_absent_experts": "sum",
        # of the dense form's (token, held expert) rows, those the
        # experts' products computed (the grouped form: its buffers)
        "moe_rows_computed_share": "mean",
        # every token's expert set
        "moe_routes": "tokens",
        # of the tokens, those whose chosen groups hold a held expert (a
        # group-limited router's layers only)
        "moe_held_group_chosen_share": "mean",
    }

    def param_shapes(self, d: int):
        e, f, fs = self.held, self.width, self.shared_width
        shapes = dict(
            router=(d, self.router_outputs),
            experts_up=(e, d, f),
            experts_down=(e, f, d),
        )
        if fs:
            shapes.update(shared_up=(d, fs), shared_down=(fs, d))
        if self.gated:  # a gate matrix beside every up matrix
            shapes["experts_gate"] = (e, d, f)
            if fs:
                shapes["shared_gate"] = (d, fs)
        if self.shared_gated:
            shapes["shared_expert_gate"] = (d, 1)
        if self.select_bias:
            shapes["select_bias"] = (self.router_outputs,)
        return shapes

    def route(self, p, flat):
        """Every token's ``(indices, weights, the groups it chose or
        None)`` from ``flat`` ``(tokens, D)``: the expert layer's input,
        or the block's where the router stands before the mixer."""
        return moe.route(
            flat, p["router"], self.top_k, self.norm_topk,
            scoring=self.scoring, select_bias=p.get("select_bias"),
            scale=self.scale, n_group=self.n_group, topk_group=self.topk_group,
        )

    def apply(self, p, x, state, ctx):
        b, t, d = x.shape
        flat = x.reshape(b * t, d)
        scope, dtype = ctx["scope"], ctx["dtype"]
        held = self.held
        # a token of each stream: dense; a fragment of each: grouped
        lowering = moe.product_lowering(b * t, self.top_k, self.router_outputs)
        metrics.inc_moe_product_lowering(lowering)
        with jax.named_scope(scope + "moe/route"):
            # routed already where the router reads the block's input
            indices, weights, groups = ctx.get("route") or self.route(p, flat)
            if lowering == "dense":
                combine = moe.held_combine_weights(indices, weights, self.first, held)
            per_expert, absent = moe.expert_load(indices, self.first, held)
            local = indices.reshape(b, t, -1) - self.first
            stats = {
                "moe_held_load": per_expert,
                "moe_place_load": jnp.sum(
                    local[..., None] == jnp.arange(held, dtype=jnp.int32),
                    axis=(0, 2), dtype=jnp.float32),
                "moe_slots_on_absent_experts": absent,
                "moe_rows_computed_share": moe.rows_computed(
                    per_expert, b * t, self.top_k, self.router_outputs, lowering,
                    self.alone,
                ) / (b * t * held),
                "moe_routes": indices,
            }
            if groups is not None:
                # the groups this chip's experts lie in (static)
                size = self.router_outputs // self.n_group
                mine = sorted({e // size for e in range(self.first, self.first + held)})
                stats["moe_held_group_chosen_share"] = jnp.mean(
                    jnp.any(groups[:, mine], axis=-1), dtype=jnp.float32)
        # no gate matrix where the experts are ungated
        experts = (p["experts_gate"] if self.gated else None,
                   p["experts_up"], p["experts_down"])
        with jax.named_scope(scope + "moe/experts"):
            if lowering == "dense":
                routed = moe.dense_experts_product(
                    flat, *experts, combine, dtype=dtype, activation=self.activation)
            else:
                routed = moe.grouped_experts_product(
                    flat, *experts, indices, weights, per_expert,
                    self.first, self.router_outputs, dtype=dtype,
                    activation=self.activation, alone=self.alone,
                )
        if not self.shared_width:  # the routed sum alone
            return routed.reshape(b, t, d), (), stats
        with jax.named_scope(scope + "moe/shared"):
            shared = moe.gated_mlp(
                flat, p["shared_gate"] if self.gated else None,
                p["shared_up"], p["shared_down"],
                dtype=dtype, activation=self.activation)
            if self.shared_gated:
                shared = shared * jax.nn.sigmoid(
                    jnp.dot(flat, p["shared_expert_gate"], precision=HI))
        return (routed + shared).reshape(b, t, d), (), stats


# -- residuals ------------------------------------------------------------

NORM_OF = {"mixer": "input_norm", "ffn": "post_norm"}


@dataclasses.dataclass(frozen=True)
class PlainResidual(Kind):
    """``"plain"``: ``x <- x + scale * F(norm(x))`` (``scale``: Granite's
    ``residual_multiplier``; ``norm`` the model's, :class:`Norm`). A block's saved input is one hidden row a
    token and the batch's fit, so the learn form groups the streams
    inside each block, and there only for the MIXER'S half, whose
    activations are what does not fit: the feed-forward's half is
    token-wise and runs once over all the streams, for one more saved
    row a token (``SequenceLM._stack``)."""

    scale: float = 1.0

    groups_the_loss = False
    learn_streams = 16

    def enter(self, x):
        return x

    def leave(self, x):
        return x

    def around(self, x, p, sub, f, ctx):
        y, new, stats = f(ctx["norm"](x, p, NORM_OF[sub], ctx["eps"]))
        return x + (y if self.scale == 1.0 else y * self.scale), new, stats


@dataclasses.dataclass(frozen=True)
class HyperResidual(Kind):
    """``"hyper_connection"`` (``hc_mult`` lanes; manifold-constrained
    hyper-connections, arXiv:2512.24880; ``ops/hyper_connection.py``):
    the stream is ``lanes`` lanes, held flat, ``X <- H_res X + H_post^T
    F(rms(H_pre X))`` with the maps made from the token's own stream and
    ``H_res`` through ``rounds`` Sinkhorn rounds. The embedding is copied
    into the lanes and the lanes are summed before the final norm. A
    layer starts near the plain residual (``a`` 0.01, ``b_res`` twice the
    identity). A token's saved row is ``lanes`` times as wide and the
    batch's no longer fit beside the weights, so the policy groups the
    streams around the whole loss, half as many at a time. Scope:
    ``hc``."""

    lanes: int
    rounds: int
    eps: float
    clamp: Tuple[float, float]

    groups_the_loss = True
    learn_streams = 8
    # how far H_res is from doubly stochastic, over tokens and sublayers
    stats = {"hc_res_row_sum_err_max": "max", "hc_res_col_sum_err_max": "max"}

    @property
    def init_rules(self):
        n = self.lanes
        small = lambda key, shape: jnp.full(shape, 0.01, jnp.float32)
        near_plain = lambda key, shape: jnp.concatenate(
            [jnp.zeros((2 * n,)), 2.0 * jnp.eye(n).ravel()]).astype(jnp.float32)
        return {f"hc_{sub}_{leaf}": rule for sub in NORM_OF
                for leaf, rule in (("a", small), ("b", near_plain))}

    def param_shapes(self, d: int):
        n = self.lanes
        shapes = {}
        for sub in NORM_OF:
            shapes[f"hc_{sub}_norm"] = (n * d,)
            shapes[f"hc_{sub}_phi"] = (n * d, 2 * n + n * n)
            shapes[f"hc_{sub}_a"] = (3,)
            shapes[f"hc_{sub}_b"] = (2 * n + n * n,)
        return shapes

    def enter(self, x):
        return jnp.tile(x, (1, 1, self.lanes))

    def leave(self, x):
        return sum(hyper_connection.lanes_of(x, self.lanes))

    def around(self, x, p, sub, f, ctx):
        hc = lambda: jax.named_scope(ctx["scope"] + "hc")
        with hc():
            pre, post, res = hyper_connection.maps(
                x, p[f"hc_{sub}_norm"], p[f"hc_{sub}_phi"], p[f"hc_{sub}_a"],
                p[f"hc_{sub}_b"], self.lanes, ctx["eps"], self.rounds, self.eps,
                *self.clamp, unroll=x.shape[1] == 1,
            )
            h = hyper_connection.mix_in(x, pre)
        y, new, stats = f(rms(h, p[NORM_OF[sub]], ctx["eps"]))
        with hc():
            stats = dict(
                stats,
                hc_res_row_sum_err_max=jnp.max(jnp.abs(res.sum(-1) - 1.0)),
                hc_res_col_sum_err_max=jnp.max(jnp.abs(res.sum(-2) - 1.0)))
            return hyper_connection.mix_out(x, y, post, res), new, stats
