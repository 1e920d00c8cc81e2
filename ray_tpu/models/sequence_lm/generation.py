"""How a model generates, as a frozen description beside its kinds
(``config.describe`` makes it; ``SequenceLM``, the policy and the lane
ask it and compare nothing against a name).

``Autoregressive``: one token a lane step, the model's one-token form;
every family but one. ``BlockDiffusion`` (SDAR, arXiv:2510.06303; the
masks are BD3-LM's, arXiv:2503.09573): a lane step commits a BLOCK of
``block_length`` tokens. Block ``b`` (positions ``bB .. bB + B - 1``)
starts as ``[MASK]^B``; each of ``denoising_steps`` passes is ONE
forward of the block's ``B`` tokens over the cache of the blocks before
it under the block-causal mask, nothing kept; at every still-masked
position a candidate is drawn from the pass's softmax (temperature 1)
and the ``B / S`` masked positions whose candidate is most probable are
committed (SDAR's ``low_confidence_static`` schedule: fixed shapes, ties
to the lower position). A last forward of the committed block writes
its key and value rows. ``S + 1`` forwards a block.

The TRACE ``u`` (the batch's ``unmask_step`` column, one int a token) says in which pass a
token was committed; whether a position is masked is read from it
(``u < 0``: not yet) and never from the token's id, so a committed
``mask_token_id`` is a token like any other. What the lane stores for
token ``i`` (logits, log-probability, value) is the pass ``u_i``'s at
``i``, and the update replays exactly those: a clean pass over the
fragment's tokens, then for each ``s`` a noisy pass whose input at ``i``
is the token where ``u_i < s`` and ``[MASK]`` elsewhere
(:meth:`BlockDiffusion.noisy_inputs`; TraceRL's token-level objective,
arXiv:2509.06949).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Autoregressive:
    tokens_per_step = 1
    stats = {}


@dataclasses.dataclass(frozen=True)
class BlockDiffusion:
    block_length: int
    denoising_steps: int
    mask_token_id: int

    # of the committed tokens, the mean probability their pass gave
    # them; tokens through the stack in the update's two kinds of pass
    stats = {"diffusion_commit_confidence_mean": "mean",
             "diffusion_clean_token_passes": "sum",
             "diffusion_noisy_token_passes": "sum"}

    def __post_init__(self):
        if self.block_length % self.denoising_steps:
            raise ValueError(
                f"{self.denoising_steps} denoising steps do not divide a block "
                f"of {self.block_length}")

    @property
    def tokens_per_step(self) -> int:
        return self.block_length

    @property
    def commits_per_pass(self) -> int:
        return self.block_length // self.denoising_steps

    def token_passes(self, tokens: int):
        """Tokens through the stack, by form, for ``tokens`` generated
        (the rollout's forms) or trained once (the update's)."""
        s = self.denoising_steps
        return {"denoise": s * tokens, "commit": tokens,
                "clean": tokens, "noisy": s * tokens}

    def commit(self, logits, trace, key, step: int):
        """One pass's commit rule. ``logits`` ``(N, B, V)``, ``trace``
        ``(N, B)`` (below 0: still masked). Returns ``(candidates,
        chosen, log-probabilities, trace after)``: a candidate drawn at
        every position, ``chosen`` the ``B / S`` MASKED positions whose
        candidate's probability is highest (``lax.top_k`` keeps the
        lower position of a tie)."""
        masked = trace < 0
        candidates = jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits), candidates[..., None], axis=-1)[..., 0]
        confidence = jnp.where(masked, jnp.exp(logp), -1.0)
        _, top = jax.lax.top_k(confidence, self.commits_per_pass)
        chosen = masked & jnp.any(
            top[..., None] == jnp.arange(self.block_length), axis=-2)
        return candidates, chosen, logp, jnp.where(chosen, step, trace)

    def generate(self, forward, state, key):
        """One block of every stream. ``forward(tokens (N, B), state,
        commit) -> (logits (N * B, V), values (N * B,), state)`` is the
        model's block form (``commit``: the position moves on and the
        rows stay). Returns ``(tokens (N, B), state after the block,
        {"logits" (N, B, V), "logp", "value", "trace" (N, B)})``, each
        token's from the pass that committed it."""
        n, b = state[-1].shape[0], self.block_length
        tokens = jnp.full((n, b), self.mask_token_id, jnp.int32)
        trace = jnp.full((n, b), -1, jnp.int32)
        kept = None
        for s in range(self.denoising_steps):
            # the state goes on with the pass's rows in it (the position
            # has not moved: the next forward writes over them), so that
            # the cache is written where it lies and never copied
            with jax.named_scope("denoise"):
                logits, value, state = forward(tokens, state, False)
            logits = logits.reshape(n, b, -1)
            candidates, chosen, logp, trace = self.commit(
                logits, trace, jax.random.fold_in(key, s), s)
            tokens = jnp.where(chosen, candidates, tokens)
            new = {"logits": logits, "logp": logp, "value": value.reshape(n, b)}
            kept = new if kept is None else {
                k: jnp.where(chosen.reshape(chosen.shape + (1,) * (v.ndim - 2)),
                             new[k], v) for k, v in kept.items()}
        with jax.named_scope("commit"):
            _, _, state = forward(tokens, state, True)
        return tokens, state, dict(kept, trace=trace)

    def first_value(self, forward, state):
        """The value a stream's NEXT block starts from: position 0 of
        its first, all-mask forward (a fragment's tail and a truncated
        episode bootstrap from it)."""
        n, b = state[-1].shape[0], self.block_length
        with jax.named_scope("denoise"):
            _, value, _ = forward(
                jnp.full((n, b), self.mask_token_id, jnp.int32), state, False)
        return value.reshape(n, b)[:, 0]

    def noisy_inputs(self, tokens, trace):
        """``(S, N, T)``: pass ``s`` reads the token where it was
        committed before ``s`` and ``[MASK]`` elsewhere."""
        steps = jnp.arange(self.denoising_steps).reshape(-1, 1, 1)
        return jnp.where(trace[None] < steps, tokens[None], self.mask_token_id)
