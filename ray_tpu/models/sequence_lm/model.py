"""``SequenceLM``: what is the model's and not a kind's (see the
package's docstring)."""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.sequence_lm.config import Segment, describe
from ray_tpu.models.sequence_lm.kinds import (
    HI, NORM_OF, NoSublayer, dot, over_layers, over_streams)
from ray_tpu.telemetry import metrics

# a stacked run's leaves that enter a bfloat16 product
_RUN_PRODUCT_LEAVES = ("in_proj", "out_proj", "mlp_gate", "mlp_up", "mlp_down")


def _shared_init(key, leaf: str, shape, rank: int):
    """The ladder every kind shares: vectors zero (zero-centred norm
    weights, biases), the embedding normal, matrices normal of variance
    1 / rows. ``rank``: the leaf's own, without a run's layer axis."""
    if rank == 1:
        return jnp.zeros(shape, jnp.float32)
    x = jax.random.normal(key, shape, jnp.float32)
    return x if leaf == "embedding" else x / np.sqrt(shape[-2])


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
    # tokens a DeltaNet chunk solves at once (a constant; tests shrink it)
    chunk = 64

    def __init__(self, num_outputs: int, config: Dict, dtype: str = "bfloat16"):
        self.config = dict(config)
        self.vocab = int(num_outputs)
        # layer_types, ffn_types, segments, residual, generation, hidden,
        # positions, eps, norm, embed_scale, logits_scale, tied_head
        vars(self).update(describe(self.config))
        # streams of a fragment batch the learn form runs at once (tests
        # shrink it)
        self.learn_streams = self.residual.learn_streams
        # operands of the projections, the expert products, the head
        # and the attention products; the cache's dtype
        self.dtype = jnp.dtype(dtype)

    def partition_rules(self):
        return None

    @property
    def _reductions(self) -> Dict[str, str]:
        """Every statistic a kind of this model declares."""
        kinds = [self.residual, self.generation] + [
            k for s in self.segments for k in (s.mixer, s.ffn)]
        return {k: v for kind in kinds for k, v in kind.stats.items()}

    # -- state -----------------------------------------------------------

    def _state_shapes(self, seg: Segment, streams: int):
        """A segment's leaves; a stacked run's with the layer axis after
        the stream's."""
        shapes = seg.mixer.state_shapes(streams, self.positions, self.dtype)
        if seg.mixer.stacked:
            shapes = [(s[:1] + (seg.layers,) + s[1:], t) for s, t in shapes]
        return shapes

    def _by_segment(self, state):
        """``(segment, its leaves of state)`` in order."""
        lo = 0
        for seg in self.segments:
            hi = lo + len(self._state_shapes(seg, 1))
            yield seg, tuple(state[lo:hi])
            lo = hi

    def initial_state(self, batch_size: int = 1):
        state = [jnp.zeros(shape, dtype) for seg in self.segments
                 for shape, dtype in self._state_shapes(seg, int(batch_size))]
        state.append(jnp.zeros((int(batch_size),), jnp.int32))
        return tuple(state)

    def reset_state(self, state, mask):
        """Open a new episode on the rows of ``mask``: the leaves a kind
        clears on a reset (the DeltaNet and state-space matrices, the
        convolution inputs) and the position go to zero; a key/value or
        latent cache is left as it is, since only slots below the
        position are ever read (of a ring: slots whose row's position,
        recovered from the slot and the stream's position, is not
        negative; what an earlier episode left in the others is written
        over before it is read)."""
        out = []
        for seg, leaves in self._by_segment(state):
            for leaf in leaves:
                if seg.mixer.cleared_on_reset:
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
            "final_norm": self.norm.leaves("weight", d),
            "head": {"kernel": (d, v)},
            "value": {"kernel": (d, 1), "bias": (1,)},
        }
        if self.tied_head:
            del shapes["head"]
        for seg in self.segments:
            # a norm for each half the block has
            layer = {}
            for sub in seg.sublayers:
                layer.update(self.norm.leaves(NORM_OF[sub], d))
            for kind in (seg.ffn, self.residual, seg.mixer):
                layer.update(kind.param_shapes(d))
            if seg.mixer.stacked:
                layer = {k: (seg.layers,) + shape for k, shape in layer.items()}
            shapes[seg.name] = layer
        return shapes

    def init(self, rng, obs=None, state=None, **_):
        """Each leaf by its kind's ``init_rules`` where it has one, else
        by the shared ladder: the published initialisation's forms at a
        scale that keeps the activations of a random model of order
        one."""
        shapes = self.param_shapes()
        rules = {seg.name: {**seg.ffn.init_rules, **self.residual.init_rules,
                            **seg.mixer.init_rules} for seg in self.segments}
        stacked = {seg.name for seg in self.segments if seg.mixer.stacked}

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
                    rule = rules.get(group, {}).get(leaf)
                    out[group][leaf] = rule(k, shape) if rule else _shared_init(
                        k, leaf, shape, len(shape) - (group in stacked))
            return out

        return make(rng)

    # -- the learn form's grouping ---------------------------------------

    def loss_groups(self, unrolls: int) -> Optional[int]:
        """Groups of unrolls the learn program takes through the WHOLE
        stack, the loss and the backward pass one at a time, its
        gradient accumulated (``JaxPolicy`` asks). ``None`` for a
        residual whose learn form groups the streams inside each block
        instead (``apply``; the residual's description says which and why)."""
        if not self.residual.groups_the_loss:
            return None
        s = self.learn_streams
        return unrolls // s if unrolls > s and unrolls % s == 0 else 1

    def reduce_group_stats(self, stats: Dict) -> Dict:
        """``stats`` stacked over the groups of ``loss_groups`` -> one
        update's, each key by the reduction its kind declares: loads add
        up over groups before their mean and max are taken, an error's
        largest is kept; the ratios and the loss's own keys are
        averaged."""
        out = over_streams(stats, self._reductions)
        if "moe_held_load" in out:
            out.update(_held_load_stats(out.pop("moe_held_load")))
        return out

    # -- forward ---------------------------------------------------------

    @property
    def tokens_per_step(self) -> int:
        """Tokens a lane step commits (``generation.py``)."""
        return self.generation.tokens_per_step

    def apply(self, params, obs, state, resets=None, scope: str = "",
              stats_out: Optional[Dict] = None, commit: Optional[bool] = None,
              trace=None, **_):
        """``obs`` ``(B, T[, 1])`` token ids; ``state`` as
        ``initial_state``; ``resets`` ``(B, T)`` (1.0 where a token
        opens an episode). Returns ``(logits (B*T, vocab), value
        (B*T,), state)``. ``scope`` prefixes the named scopes;
        ``stats_out`` receives the kinds' statistics.

        A model that generates a block a step has two forms more.
        ``commit`` (False or True): the ``T`` tokens are ONE BLOCK of
        each stream at its position, every token of it seeing the whole
        block and the cache below it; with ``commit`` the position moves
        past the block and its rows stay, without it the position stays
        and the next forward writes over them. ``trace`` ``(B, T)``: the
        update's form, :meth:`_replay`."""
        tokens = obs.reshape(obs.shape[0], -1).astype(jnp.int32)
        b, t = tokens.shape
        # the one-token form opens an episode before the token it
        # consumes; without ``resets`` nothing is reset here (the
        # rollout lane has reset the stream after its last step) and
        # no pass over the state is made for it
        if t == 1 and resets is not None:
            state = self.reset_state(state, resets.reshape(b) > 0.5)
        prefix = (scope + "/") if scope else ""
        rows_ctx = self._rows(state, b, t, resets)
        if trace is not None:
            return self._replay(
                params, tokens, trace.reshape(b, t).astype(jnp.int32), state,
                rows_ctx, prefix, stats_out)
        x, state_out, stats, _ = self._stack(
            params, tokens, state, rows_ctx, prefix,
            step=t == 1 or commit is not None,
            choices=stats_out is not None and "index_choices" in stats_out)
        state_out.append(
            rows_ctx["pos0"] if commit is False else rows_ctx["positions"][:, -1] + 1)
        logits, value = self._head(params, x, prefix)
        if stats_out is not None:
            self._report(stats_out, over_layers(stats, self._reductions), b * t)
        return logits, value, tuple(state_out)

    def _rows(self, state, b: int, t: int, resets):
        """The fragment's rows: each token's episode number inside the
        fragment, whether it opens one, its position, and the streams'
        start positions."""
        if t == 1 or resets is None:
            resets = jnp.zeros((b, t), jnp.float32)
        fresh = resets.reshape(b, t) > 0.5
        seg = jnp.cumsum(fresh.astype(jnp.int32), axis=1)  # (B, T)
        steps = jnp.arange(t, dtype=jnp.int32)[None]
        opened = jax.lax.cummax(jnp.where(fresh, steps, -1), axis=1)
        pos0 = state[-1]
        positions = jnp.where(seg == 0, pos0[:, None] + steps, steps - opened)
        return {"seg": seg, "fresh": fresh, "positions": positions, "pos0": pos0}

    def _stack(self, params, tokens, state, rows_ctx, prefix: str, *, step: bool,
               keep: bool = False, clean=None, choices: bool = False):
        """The embedding and the blocks. ``step``: the lane's form (one
        token, or one block of a model that commits a block a step);
        ``keep`` / ``clean``: the passes of :meth:`_replay`; ``choices``:
        a caller's ``stats_out`` asks for ``index_choices``, every query's
        chosen rows of the layers with a learned index. Returns
        ``(x, [state leaves], {key: [a segment's (layers, ...)]}, [a
        segment's kept rows])``."""
        b, t = tokens.shape
        residual, declared = self.residual, self._reductions
        flags = (("step", step), ("keep", keep))
        if choices:  # only where asked: every other program's flags are as they were
            flags += (("choices", True),)

        x = jnp.take(params["embed"]["embedding"], tokens, axis=0)  # (B, T, D)
        if self.embed_scale != 1.0:
            x = x * self.embed_scale
        x = residual.enter(x)

        def of_halves(stats, more):
            """A block's statistics of its two halves': what both report
            (the residual's own) as two layers'."""
            both = over_layers(
                {k: [jnp.stack([stats[k], more[k]])]
                 for k in sorted(set(stats) & set(more))},
                declared)
            return {**stats, **more, **both}

        def block(x, p, layer_state, rows, imports, mixer, ffn, flags, before=None):
            # ``imports``: what earlier layers exported, of the names this
            # mixer reads; an ARGUMENT of the block, so that a
            # checkpointed block saves it and its gradient flows back to
            # the exporter
            ctx = dict(rows, scope=prefix, dtype=self.dtype, eps=self.eps,
                       norm=self.norm, chunk=self.chunk, imports=imports,
                       **dict(flags))
            if ffn.route_on == "input":
                # the router reads the layer's input, before the mixer
                # (``before``: where the mixer's half ran apart, below)
                source = x if before is None else before
                with jax.named_scope(prefix + "moe/route"):
                    ctx["route"] = ffn.route(p, source.reshape(-1, x.shape[-1]))
            # a block of one sublayer runs the half it has
            new, stats, more, exported = (), {}, {}, {}
            if not mixer.absent:
                x, new, stats = residual.around(
                    x, p, "mixer", lambda h: mixer.apply(p, h, layer_state, ctx), ctx)
                if mixer.exports:  # the last element of its new state
                    new, exported = tuple(new[:-1]), new[-1]
            if not ffn.absent:
                x, _, more = residual.around(
                    x, p, "ffn", lambda h: ffn.apply(p, h, (), ctx), ctx)
            return x, new, of_halves(stats, more), exported

        # the residual says whether the streams are grouped inside each
        # block, here, or around the whole loss (``loss_groups``)
        groups = b // self.learn_streams if (
            not residual.groups_the_loss
            and not step and b > self.learn_streams and b % self.learn_streams == 0
        ) else 1
        # the learn form keeps a block's input and recomputes the block
        # in the backward pass
        whole = block if step else jax.checkpoint(block, static_argnums=(5, 6, 7))

        def run_block(x, p, layer_state, rows, imports, mixer, ffn, flags):
            if groups == 1 or mixer.absent:
                return whole(x, p, layer_state, rows, imports, mixer, ffn, flags)
            # more streams than the learn form runs at once: the MIXER'S
            # half of the block ``learn_streams`` streams at a time (one
            # group's activations of one mixer are alive, not the
            # batch's), then the feed-forward's half, which is token-wise,
            # ONCE over all of them under a checkpoint of its own: a held
            # expert's weights are read and their gradient written once a
            # layer, for one more saved hidden row a token
            no_half = NoSublayer()
            split = lambda a: a.reshape((groups, b // groups) + a.shape[1:])
            merge = lambda a: a.reshape((b,) + a.shape[2:])
            before = x
            x, new, stats, exported = jax.lax.map(
                lambda xs: whole(xs[0], p, *xs[1:], mixer, no_half, flags),
                jax.tree_util.tree_map(split, (x, layer_state, rows, imports)),
            )
            x, new, exported = merge(x), *jax.tree_util.tree_map(merge, (new, exported))
            stats = over_streams(stats, declared)
            if ffn.absent:
                return x, new, stats, exported
            x, _, more, _ = whole(x, p, (), rows, {}, no_half, ffn, flags, before)
            return x, new, of_halves(stats, more), exported

        # what a layer made for later layers to read, by name: the ONE
        # channel between layers besides the stream (a kind declares
        # ``exports`` / ``imports``; a stacked run has neither)
        shared = {}
        state_out, stats, kept = [], {}, []
        for i, (s, leaves) in enumerate(self._by_segment(state)):
            rows = rows_ctx if clean is None else dict(rows_ctx, clean=clean[i])
            imports = {name: shared[name] for name in s.mixer.imports}
            args = (params[s.name], leaves, rows, imports, s.mixer, s.ffn, flags)
            if s.mixer.stacked:
                x, new, seen = self._run_of_layers(run_block, prefix, x, *args)
            else:
                x, new, seen, exported = run_block(x, *args)
                seen = {k: v[None] for k, v in seen.items()}
                shared.update(exported)
                for name in exported:
                    metrics.inc_shared_state_lowering(name, sum(
                        name in other.mixer.imports for other in self.segments))
            state_out.extend(new[:len(leaves)])
            kept.append(tuple(new[len(leaves):]))
            for k, v in seen.items():
                stats.setdefault(k, []).append(v)
        return x, state_out, stats, kept

    def _head(self, params, x, prefix: str):
        """``(logits (rows, vocab), value (rows,))`` of the stack's
        output ``x`` ``(B, T, lanes x D)``."""
        with jax.named_scope(prefix + "head"):
            x = self.residual.leave(x)
            feat = self.norm(x, params["final_norm"], "weight", self.eps)
            feat = feat.reshape(-1, feat.shape[-1])
            if self.tied_head:  # the embedding, contracted over the hidden axis
                logits = jax.lax.dot_general(
                    feat.astype(self.dtype),
                    params["embed"]["embedding"].astype(self.dtype),
                    (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
                )
            else:
                logits = dot(feat, params["head"]["kernel"], self.dtype)
            if self.logits_scale != 1.0:
                logits = logits / self.logits_scale
            value = (
                jnp.dot(feat, params["value"]["kernel"], precision=HI)
                + params["value"]["bias"]
            )[:, 0]
        return logits, value

    def _replay(self, params, tokens, trace, state, rows_ctx, prefix: str,
                stats_out: Optional[Dict]):
        """The update's form of a model that commits a block a step: the
        fragment's ``tokens`` ``(B, T)`` with their ``trace``, from the
        stored start ``state``. A CLEAN pass over the tokens under the
        block-causal mask, whose attention layers hand out their keys
        and values; then, one after another, a NOISY pass for each
        denoising step ``s`` whose input is ``generation.noisy_inputs``
        and whose queries see the stored rows, the clean pass's rows of
        strictly earlier blocks and their own pass's of their own block.
        Token ``i``'s logits and value are the noisy pass ``trace[i]``'s
        at ``i``: what the lane stored when it committed the token. The
        gradient reaches the clean pass through its keys. The passes'
        scopes: ``clean`` and ``noisy`` around the kinds' own."""
        gen = self.generation
        b, t = tokens.shape
        declared = self._reductions
        with jax.named_scope(prefix + "clean"):
            _, _, stats, kept = self._stack(
                params, tokens, state, rows_ctx, prefix, step=False, keep=True)
        stats = over_layers(stats, declared)
        routes = stats.pop("moe_routes", None)

        def noisy(inputs):
            with jax.named_scope(prefix + "noisy"):
                x, _, seen, _ = self._stack(
                    params, inputs, state, rows_ctx, prefix, step=False, clean=kept)
            seen = over_layers(seen, declared)
            seen.pop("moe_routes", None)
            return x, seen

        xs, seen = jax.lax.map(noisy, gen.noisy_inputs(tokens, trace))
        # each token from the pass that committed it
        x = jnp.take_along_axis(
            xs, jnp.clip(trace, 0, xs.shape[0] - 1)[None, :, :, None], axis=0)[0]
        logits, value = self._head(params, x, prefix)
        if stats_out is not None:
            # the S + 1 passes' counts as one update's
            stats = over_streams(
                {k: jnp.concatenate([stats[k][None], seen[k]]) for k in stats},
                declared)
            if routes is not None:
                stats["moe_routes"] = routes  # the clean pass's
            self._report(stats_out, stats, b * t)
            confidence = jnp.exp(jnp.take_along_axis(
                jax.nn.log_softmax(logits), tokens.reshape(-1, 1), axis=1))
            passes = gen.token_passes(b * t)
            stats_out.update(
                diffusion_commit_confidence_mean=jnp.mean(confidence),
                diffusion_clean_token_passes=jnp.float32(passes["clean"]),
                diffusion_noisy_token_passes=jnp.float32(passes["noisy"]))
        return logits, value, state

    def _report(self, stats_out: Dict, stats: Dict, tokens: int):
        """The stack's statistics into ``stats_out``: the kinds' own
        keys as they are, and the few ratios made of their sums."""
        routes = stats.pop("moe_routes", None)
        if "moe_routes" in stats_out:
            # asked for every token's expert set; where no layer routes:
            # the one feed-forward there is, for every token
            stats_out["moe_routes"] = routes if routes is not None else jnp.zeros(
                (1, tokens, 1), jnp.int32)
        # key blocks a kind counted as ``<name>_skipped`` of ``<name>_walked``
        for name in [k[:-len("_walked")] for k in stats if k.endswith("_key_blocks_walked")]:
            stats_out[name + "_skipped_share"] = stats.pop(
                name + "_skipped") / jnp.maximum(stats.pop(name + "_walked"), 1)
        place = stats.pop("moe_place_load", None)
        if place is not None and not self.residual.groups_the_loss:
            # a group of ``loss_groups`` hands out ``moe_held_load`` as it
            # is and ``reduce_group_stats`` makes the update's numbers
            stats_out.update(_held_load_stats(stats.pop("moe_held_load")))
            # of the held experts, those that some stream's token at
            # the same place of its fragment reached: where a
            # fragment is one stream's rollout (the fused lane) a
            # place is a decode step, and this is the share of the
            # held experts' weights a step's tokens chose
            stats_out["moe_decode_held_experts_touched_share"] = jnp.mean(place > 0)
        stats_out.update(stats)

    # -- a run of stacked layers -----------------------------------------

    def _run_of_layers(self, run_block, scope, x, p, run_state, rows, imports,
                       *kinds):
        """A run of identical layers whose leaves are stacked on a
        leading layer axis, as ONE ``lax.scan``: the layer is traced
        once (ten layers unrolled compiled for longer than a run of the
        benchmark may take). ``run_state``'s leaves carry the layer axis
        after the stream's. Returns ``(x, state leaves, statistics a
        layer)``."""
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
                x, (matrices, tail), stats, _ = run_block(
                    x, p_l, ((matrices, layer), tail), rows, imports, *kinds)
                with jax.named_scope(scope + "ssm/carry"):
                    tails = jax.lax.dynamic_update_index_in_dim(
                        tails, tail.astype(tails.dtype), layer, 1)
                return (x, matrices, tails), stats

            (x, *leaves), stats = jax.lax.scan(step, (x, *run_state), (p, layers))
            return x, tuple(leaves), stats

        def fragment(x, xs):
            p_l, mine = xs
            x, new, stats, _ = run_block(x, p_l, mine, rows, imports, *kinds)
            return x, (new, stats)

        x, (new, stats) = jax.lax.scan(
            fragment, x, (p, tuple(jnp.moveaxis(s, 1, 0) for s in run_state)))
        return x, tuple(jnp.moveaxis(s, 0, 1) for s in new), stats


def _held_load_stats(load) -> Dict:
    """``load`` ``(expert layers, held)``, an update's."""
    return {"moe_tokens_per_held_expert": jnp.mean(load),
            "moe_max_tokens_per_held_expert": jnp.max(load)}
