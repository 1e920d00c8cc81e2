"""Decoder-style transformer policy torso, tensor-parallel over the
mesh's ``"model"`` axis.

The concrete proof that the learner path is architecture-agnostic
(ROADMAP item 4): a policy whose params need NOT fit replicated on one
device. Observations are chunked into a short token sequence, run
through pre-LN causal decoder blocks — attention through the tested
``ops/flash_attention`` core — and read out at the last token into
policy-logits and value heads.

Partitioning is megatron-style and happens at two cooperating layers:

  - **placement**: ``partition_rules()`` (the
    ``sharding.specs.default_partition_rules`` grammar) split the QKV
    projections on the head dim, the output projection on its input
    dim, and the MLP up/down kernels on their wide dim; embeddings,
    layernorms, heads, and reduced-output biases replicate.
  - **compute**: inside a ``shard_map``-lowered learn program the
    model sees its LOCAL param slices, so :meth:`apply` inserts the
    Megatron f/g boundary collectives itself — ``copy_to_model_shards``
    (identity forward, ``psum`` backward) entering each parallel
    region, ``lax.psum`` leaving each row-parallel projection. Whether
    the model axis is bound is probed at trace time, so the SAME apply
    serves three regimes: the partitioned learn program (manual
    collectives over local slices), plain jit inference over globally
    shaped sharded arrays (GSPMD inserts the collectives), and the
    legacy replicated path (no collectives at all). On a size-1 model
    axis every collective is an exact identity, which is what makes
    ``model_parallel=1`` bit-identical to the replicated path (the
    tests/test_model_parallel.py parity contract).

Not a flax module on purpose: flax validates param shapes against the
module config at apply time, which would reject the local slices a
``shard_map`` body sees. Params are a plain nested dict; every head /
width is derived from the param shapes actually passed in, so global
and local shapes flow through the same code.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops.flash_attention import flash_attention
from ray_tpu.sharding.mesh import MODEL_AXIS
from ray_tpu.sharding.specs import varying


def _bound_parallel_axis(name: Optional[str]) -> Optional[str]:
    """Trace-time probe: ``name`` if it is a bound mesh axis here
    (i.e. we are inside a shard_map over it) AND its size exceeds 1 —
    else None. A size-1 axis returns None on purpose: its collectives
    would be exact no-ops, and emitting none keeps the
    ``model_parallel=1`` program literally the replicated program (the
    bitwise-parity geometry)."""
    if not name:
        return None
    try:
        size = jax.lax.axis_size(name)
    except NameError:  # unbound: not inside a shard_map over it
        return None
    return name if size > 1 else None


def copy_to_model_shards(x, axis):
    """Megatron's *f* operator: identity forward into a tensor-parallel
    region, all-reduce backward — collects each model shard's partial
    gradient contribution to the (replicated) activations feeding a
    column-parallel projection. Under ``shard_map``'s varying-axes
    typing that is exactly the cast of a replicated value to a
    per-shard one: its transpose is the ``psum``."""
    return varying(x, axis)


def reduce_from_model_shards(x, axis):
    """Megatron's *g* operator: all-reduce forward out of a
    row-parallel projection, identity backward (``psum`` transposes to
    the per-shard cast)."""
    return jax.lax.psum(x, axis)


def _layer_norm(x, p, eps=1e-5):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


class TransformerPolicyNet:
    """Duck-typed :class:`~ray_tpu.models.base.RTModel` surface
    (``init`` / ``apply`` / ``initial_state`` / ``is_recurrent``) over
    plain-dict params. Registered via
    ``model_config["use_transformer"]`` (models/catalog.py)."""

    is_recurrent = False
    supports_stored_train_state = False
    _partition_rules_override = None

    def __init__(
        self,
        num_outputs: int,
        d_model: int = 64,
        num_layers: int = 2,
        num_heads: int = 4,
        head_dim: Optional[int] = None,
        ff_dim: Optional[int] = None,
        seq_len: int = 8,
        dtype_: str = "float32",
    ):
        self.num_outputs = int(num_outputs)
        self.d_model = int(d_model)
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim or self.d_model // self.num_heads)
        self.ff_dim = int(ff_dim or 4 * self.d_model)
        self.seq_len = int(seq_len)
        self.dtype_ = dtype_

    # -- RTModel surface -------------------------------------------------

    def initial_state(self, batch_size: int = 1) -> Sequence:
        return ()

    def partition_rules(self):
        if self._partition_rules_override is not None:
            return tuple(self._partition_rules_override)
        from ray_tpu.sharding.specs import default_partition_rules

        return default_partition_rules()

    @classmethod
    def with_logical_rules(cls, rules):
        return type(
            cls.__name__ + "WithRules",
            (cls,),
            {"_partition_rules_override": tuple(rules)},
        )

    # -- params ----------------------------------------------------------

    def _tokens(self, x):
        """Chunk a flat (B, F) feature row into (B, S, ceil(F/S))
        tokens (zero-padded tail) — the decoder's input sequence."""
        B, F = x.shape
        S = self.seq_len
        tok = -(-F // S)
        if S * tok != F:
            x = jnp.pad(x, ((0, 0), (0, S * tok - F)))
        return x.reshape(B, S, tok)

    def init(self, rng, obs):
        """Global-shape param tree (leaf names are what the partition
        rules pattern-match)."""
        obs = jnp.asarray(obs)
        F = int(np.prod(obs.shape[1:]))
        tok = -(-F // self.seq_len)
        D, H, Dh, FF = (
            self.d_model, self.num_heads, self.head_dim, self.ff_dim,
        )
        lecun = jax.nn.initializers.lecun_normal()
        small = jax.nn.initializers.variance_scaling(
            0.01, "fan_in", "truncated_normal"
        )
        keys = iter(jax.random.split(rng, 4 + 8 * self.num_layers))

        def ln():
            return {
                "scale": jnp.ones((D,), jnp.float32),
                "bias": jnp.zeros((D,), jnp.float32),
            }

        params = {
            "in_proj": {
                "kernel": lecun(next(keys), (tok, D), jnp.float32),
                "bias": jnp.zeros((D,), jnp.float32),
            },
            "pos": small(next(keys), (self.seq_len, D), jnp.float32),
        }
        for i in range(self.num_layers):
            params[f"layer_{i}"] = {
                "ln1": ln(),
                "attn": {
                    "wq": lecun(
                        next(keys), (D, H * Dh), jnp.float32
                    ).reshape(D, H, Dh),
                    "wk": lecun(
                        next(keys), (D, H * Dh), jnp.float32
                    ).reshape(D, H, Dh),
                    "wv": lecun(
                        next(keys), (D, H * Dh), jnp.float32
                    ).reshape(D, H, Dh),
                    "bq": jnp.zeros((H, Dh), jnp.float32),
                    "bk": jnp.zeros((H, Dh), jnp.float32),
                    "bv": jnp.zeros((H, Dh), jnp.float32),
                    "wo": lecun(
                        next(keys), (H * Dh, D), jnp.float32
                    ).reshape(H, Dh, D),
                    "bo": jnp.zeros((D,), jnp.float32),
                },
                "ln2": ln(),
                "mlp": {
                    "w_up": lecun(next(keys), (D, FF), jnp.float32),
                    "b_up": jnp.zeros((FF,), jnp.float32),
                    "w_down": lecun(next(keys), (FF, D), jnp.float32),
                    "b_down": jnp.zeros((D,), jnp.float32),
                },
            }
        params["ln_f"] = ln()
        params["logits"] = {
            "kernel": small(
                next(keys), (D, self.num_outputs), jnp.float32
            ),
            "bias": jnp.zeros((self.num_outputs,), jnp.float32),
        }
        params["value"] = {
            "kernel": jax.nn.initializers.variance_scaling(
                1.0, "fan_in", "truncated_normal"
            )(next(keys), (D, 1), jnp.float32),
            "bias": jnp.zeros((1,), jnp.float32),
        }
        return params

    # -- forward ---------------------------------------------------------

    def _attn(self, ap, x, axis):
        # local head count comes off the param slice, not config: the
        # same einsums serve global arrays and shard_map-local slices
        if axis:
            x = copy_to_model_shards(x, axis)
        q = jnp.einsum("bsd,dhk->bhsk", x, ap["wq"]) + ap["bq"][
            None, :, None, :
        ]
        k = jnp.einsum("bsd,dhk->bhsk", x, ap["wk"]) + ap["bk"][
            None, :, None, :
        ]
        v = jnp.einsum("bsd,dhk->bhsk", x, ap["wv"]) + ap["bv"][
            None, :, None, :
        ]
        o = flash_attention(q, k, v, causal_offset=0)
        y = jnp.einsum("bhsk,hkd->bsd", o, ap["wo"])
        if axis:
            y = reduce_from_model_shards(y, axis)
        return y + ap["bo"]

    def _mlp(self, mp, x, axis):
        if axis:
            x = copy_to_model_shards(x, axis)
        h = jax.nn.gelu(x @ mp["w_up"] + mp["b_up"])
        y = h @ mp["w_down"]
        if axis:
            y = reduce_from_model_shards(y, axis)
        return y + mp["b_down"]

    def apply(self, params, obs, state=(), seq_lens=None):
        axis = _bound_parallel_axis(MODEL_AXIS)
        dtype = jnp.dtype(self.dtype_)
        x = jnp.asarray(obs).astype(dtype)
        x = x.reshape(x.shape[0], -1)
        t = self._tokens(x)
        h = (
            t @ params["in_proj"]["kernel"]
            + params["in_proj"]["bias"]
            + params["pos"]
        )
        for i in range(self.num_layers):
            lp = params[f"layer_{i}"]
            h = h + self._attn(lp["attn"], _layer_norm(h, lp["ln1"]), axis)
            h = h + self._mlp(lp["mlp"], _layer_norm(h, lp["ln2"]), axis)
        feat = _layer_norm(h, params["ln_f"])[:, -1]
        logits = feat @ params["logits"]["kernel"] + params["logits"]["bias"]
        value = (
            feat @ params["value"]["kernel"] + params["value"]["bias"]
        ).squeeze(-1)
        return (
            logits.astype(jnp.float32),
            value.astype(jnp.float32),
            (),
        )

    def num_params(self) -> int:
        """Static param count at the configured geometry (bench
        reporting)."""
        D, H, Dh, FF, S = (
            self.d_model,
            self.num_heads,
            self.head_dim,
            self.ff_dim,
            self.seq_len,
        )
        per_layer = (
            3 * (D * H * Dh + H * Dh)  # qkv
            + H * Dh * D + D           # out proj
            + D * FF + FF + FF * D + D  # mlp
            + 4 * D                    # 2 layernorms
        )
        return (
            self.num_layers * per_layer
            + S * D + 2 * D            # pos + final ln
            + D * self.num_outputs + self.num_outputs
            + D + 1                    # value head
        )
