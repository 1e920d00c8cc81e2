"""Device rollout engine: act → step → postprocess as ONE mesh program.

The device half of the two rollout lanes (docs/pipeline.md). For a
:class:`~ray_tpu.env.jax_env.JaxVectorEnv`, the whole rollout —
policy forward + exploration sampling, vmapped env step, auto-reset,
GAE postprocess, advantage standardization — lowers into one
``sharded_jit`` program over the learner mesh, with the env-state tree
row-sharded like a batch (``sharding/specs.py``) and the policy's rng
threaded in the host-visible split order (one split per env step — the
exact stream the actor lane's local worker consumes), so a fixed seed
produces the actor lane's trajectories bit for bit
(tests/test_jax_env.py).

Two consumption modes:

- :meth:`JaxRolloutEngine.rollout` — the key schedule (one tiny
  program) and ONE dispatch of the rollout program produce a
  device-resident trajectory batch (``(N·T, ...)`` columns, env-major
  row order like the host lane's concat). On-policy algorithms learn
  from it in place; off-policy algorithms insert the rows into a
  :class:`~ray_tpu.execution.replay_buffer.DeviceReplayBuffer` via
  ``add_device_tree`` — rollout rows never touch the host either way.
- :meth:`JaxRolloutEngine.superstep_feed` — the feed descriptor for
  ``JaxPolicy.learn_rollout_superstep``: K × [rollout + SGD-nest
  update] fuse into ONE dispatched program
  (``sharding/superstep.build_superstep_fn``'s rollout feed), zero
  batch bytes over H2D.

Auto-reset follows the terminal-observation contract of
``env/jax_env.py``: NEXT_OBS is the final (pre-reset) observation, the
successor row's OBS the reset observation; GAE bootstraps 0 across
``terminated`` and V(final obs) across ``truncated``
(``ops/gae.compute_gae_fragment``) — matching the host sampler +
``evaluation/postprocessing.py`` exactly.

Episode returns/lengths accumulate in the carry and drain with the
stats readback as ``(T, N)`` masked arrays — the lane's RolloutMetrics
come back without any per-step host work. The host reads nothing back
that the round is not waiting for: :meth:`JaxRolloutEngine.rollout`
starts the metrics' copy to the host and returns, and the read is
finished when somebody asks for what it holds (``get_metrics``,
``last_actions``) or behind the next rollout's dispatch.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.data.sample_batch import SampleBatch
from ray_tpu.env.jax_env import JaxVectorEnv, env_keys, tree_where
from ray_tpu.evaluation.metrics import RolloutMetrics
from ray_tpu.telemetry import metrics as telemetry_metrics
from ray_tpu.util import tracing

# columns the PPO-family learn feed keeps (mirrors
# ``_batch_to_train_tree`` semantics: NEXT_OBS dropped when the loss
# never reads it — JaxPolicy._ship_next_obs)
_LEARN_DROP = (SampleBatch.NEXT_OBS, SampleBatch.AGENT_INDEX, SampleBatch.T)


class RolloutSuperstepFeed:
    """Descriptor handing the engine's per-shard rollout body + env
    carry to ``JaxPolicy.learn_rollout_superstep`` (the rollout feed
    of ``build_superstep_fn``)."""

    def __init__(self, carry, body, steps: int, key):
        self.carry = carry
        self.body = body
        self.steps = int(steps)
        self.key = key


def supports_jax_rollout_lane(policy, env) -> Tuple[bool, str]:
    """(ok, reason): whether (policy, env) can run on the device
    rollout lane. Callers fail fast at config time with ``reason``."""
    if not isinstance(env, JaxVectorEnv):
        return False, f"env {type(env).__name__} is not a JaxVectorEnv"
    if not getattr(policy, "supports_jax_rollout", False):
        return False, (
            f"policy {type(policy).__name__} cannot lower its act "
            "path (stateful exploration, a model fed the previous "
            "action or reward, or a non-mesh backend)"
        )
    return True, ""


class JaxRolloutEngine:
    """One policy + one JaxVectorEnv, N env slots on the learner mesh.

    ``postprocess="gae"`` computes advantages/value targets in-program
    (on-policy); ``postprocess="none"`` emits raw transition rows
    (replay fill). ``seed`` follows the actor lane's worker
    convention (config seed; env ``i`` keyed ``seed + i``)."""

    def __init__(
        self,
        policy,
        env: JaxVectorEnv,
        num_envs: int,
        rollout_length: int,
        *,
        seed: Optional[int] = None,
        postprocess: str = "gae",
        standardize_advantages: bool = True,
    ):
        import jax

        from ray_tpu import sharding as sharding_lib

        ok, reason = supports_jax_rollout_lane(policy, env)
        if not ok:
            raise ValueError(f"jax rollout lane unavailable: {reason}")
        self.policy = policy
        self.env = env
        self.N = int(num_envs)
        self.T = int(rollout_length)
        self.mesh = policy.mesh
        self.n_shards = sharding_lib.num_shards(self.mesh)
        if self.N % self.n_shards:
            raise ValueError(
                f"num_envs {self.N} must divide the {self.n_shards} "
                "data shards (row-sharded env states)"
            )
        if postprocess not in ("gae", "none"):
            raise ValueError(f"unknown postprocess {postprocess!r}")
        self.postprocess = postprocess
        self.standardize = bool(standardize_advantages)
        self.gamma = float(policy.config.get("gamma", 0.99))
        self.lambda_ = float(policy.config.get("lambda", 1.0))
        self._seed = seed
        self._metrics: List[RolloutMetrics] = []
        # ``env.report_actions``: the last dispatch's actions, host
        # numpy ``([K,] T, N)``, and of a model that commits a block a
        # step the pass that committed each (its trace)
        self._last_actions = None
        self._last_trace = None
        # the last ``rollout()``'s metrics, still on the device with
        # their copy to the host started, and ``dispatch_count`` as
        # that rollout left it: at most one read is ever outstanding
        self._pending = None
        self._rollout_fn = None
        self._body = None
        self.batch_size = self.N * self.T

        # a model with per-stream state: its state rides the carry,
        # and the learner gets the state at the start of every
        # ``unroll``-step chunk of a fragment (its learn form's length)
        self.stateful = bool(policy.model.is_recurrent)
        self.unroll = int(policy._unroll_T) if self.stateful else self.T
        if self.T % self.unroll:
            raise ValueError(
                f"rollout_fragment_length {self.T} is not a multiple of "
                f"the model's max_seq_len {self.unroll}: the device lane "
                "trains a model with state on whole unrolls"
            )

        # a model that commits a block of tokens a lane step: every
        # length the lane counts in env steps is whole blocks, so that a
        # fragment, an unroll and an episode all start on a block's first
        # token (``T`` stays the count of ENV steps)
        self.tokens_per_step = int(getattr(policy.model, "tokens_per_step", 1))
        if self.tokens_per_step > 1:
            lengths = {
                "rollout_fragment_length": self.T, "max_seq_len": self.unroll,
                **{k: int(env.config[k]) for k in ("episode_length", "phase_stride")
                   if k in env.config},
            }
            for name, n in lengths.items():
                if n % self.tokens_per_step:
                    raise ValueError(
                        f"{name} {n} is not a multiple of the {self.tokens_per_step} "
                        "tokens the model commits a step"
                    )

        # initial env carry, resident and row-sharded from step zero
        keys = env_keys(seed, self.N)
        if hasattr(env, "init_at"):
            # an env that places each slot by its index (a phase offset)
            state = jax.jit(jax.vmap(env.init_at))(
                keys, jax.numpy.arange(self.N)
            )
        else:
            state = jax.jit(jax.vmap(env.init))(keys)
        state, obs = jax.jit(jax.vmap(env.reset))(state)
        carry = {
            "env": state,
            "obs": obs,
            "ep_ret": jax.numpy.zeros(self.N, jax.numpy.float32),
            "ep_len": jax.numpy.zeros(self.N, jax.numpy.int32),
        }
        if self.stateful:
            with tracing.start_span("rollout:state_init", num_envs=self.N):
                carry["state"] = tuple(policy.model.initial_state(self.N))
        self._carry = jax.device_put(
            carry, sharding_lib.batch_sharded(self.mesh)
        )

    # -- the per-shard rollout body --------------------------------------

    def _rollout_body(self):
        """``fn(params, carry, ro_rngs (T, 2), coeffs) -> (carry,
        batch, metrics)`` over THIS SHARD's env rows; runs inside
        ``shard_map`` (superstep scan slot or the standalone rollout
        program — same body, same numerics)."""
        if self._body is not None:
            return self._body
        import jax
        import jax.numpy as jnp

        from ray_tpu import sharding as sharding_lib
        from ray_tpu.ops.gae import compute_gae_fragment

        policy = self.policy
        env = self.env
        axis = sharding_lib.data_axis(self.mesh)
        n_loc = self.N // self.n_shards
        T = self.T
        step_b = jax.vmap(env.step)
        reset_b = jax.vmap(env.reset)
        gamma, lam = self.gamma, self.lambda_
        mode = self.postprocess
        standardize = self.standardize and mode == "gae"
        value_fwd = policy.model_forward

        stateful = self.stateful
        unroll = self.unroll
        report_actions = bool(env.report_actions)
        stored = stateful and bool(
            getattr(policy.model, "supports_stored_train_state", False)
        )

        block = self.tokens_per_step

        def body(params, carry, ro_rngs, coeffs):
            if block > 1:
                # V of the state a stream's next block starts from
                next_value = lambda obs, state: policy.block_first_value(
                    params, state)
            else:
                next_value = lambda obs, state: value_fwd(
                    params, obs[:, None], state)[1]

            def step(c, key_t):
                env_state, obs, ep_ret, ep_len, mstate = c
                # pin each sub-program's fusion boundary so it
                # compiles like the actor lane's standalone jitted
                # programs (action fn / vmapped env step / reset) —
                # the lane parity contract (docs/data_plane.md)
                with jax.named_scope("rollout/act"):
                    params_b, obs_b, key_t = (
                        jax.lax.optimization_barrier(
                            (params, obs, key_t)
                        )
                    )
                    actions, mstate2, extra, _ = policy._action_step_body(
                        params_b, obs_b, key_t, coeffs,
                        explore=True, expl_state=(), state=mstate,
                    )
                    # pin the OUTPUTS as well: the value head's result
                    # feeds the in-program GAE below, and without a
                    # barrier XLA fuses it differently than the actor
                    # lane's standalone action program (last-ulp drift)
                    actions, extra = jax.lax.optimization_barrier(
                        (actions, extra)
                    )
                return env_steps(c[:4], actions, extra, mstate2, params_b)

            def block_step(c, key_t):
                """A lane step of a model that commits a block: ONE
                block action of every stream (the model's denoise and
                commit forwards), then the block's tokens through the
                env one after another, in position order. The model
                reads no observation: the last token is a row of its
                cache."""
                with jax.named_scope("rollout/act"):
                    params_b, key_t = jax.lax.optimization_barrier(
                        (params, key_t)
                    )
                    actions, mstate2, extra = policy.action_block_body(
                        params_b, key_t, c[4]
                    )
                    actions, extra = jax.lax.optimization_barrier(
                        (actions, extra)
                    )
                outs = []
                for j in range(block):
                    c, out = env_steps(
                        c[:4], actions[:, j],
                        {k: v[:, j] for k, v in extra.items()}, mstate2,
                        params_b, last=j == block - 1,
                    )
                    outs.append(out)
                return c, jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *outs
                )

            def env_steps(c, actions, extra, mstate2, params_b, last=True):
                """What follows the act: the env's step and auto-reset,
                the row, the metrics and the model state's reset.
                ``last``: the token closes the lane step's action (of a
                block, only its last token can end an episode or need
                a bootstrap: the engine refused other lengths)."""
                env_state, obs, ep_ret, ep_len = c
                with jax.named_scope("rollout/env_step"):
                    env_state_b, actions_b = (
                        jax.lax.optimization_barrier(
                            (env_state, actions)
                        )
                    )
                    env_state2, obs2, rew, term, trunc = step_b(
                        env_state_b, actions_b
                    )
                    done = term | trunc
                    env_state2b = jax.lax.optimization_barrier(
                        env_state2
                    )
                    env_state3, obs3 = reset_b(env_state2b)
                rew = rew.astype(jnp.float32)
                ep_ret2 = ep_ret + rew
                ep_len2 = ep_len + 1
                row = {
                    SampleBatch.OBS: obs,
                    SampleBatch.NEXT_OBS: obs2,
                    SampleBatch.ACTIONS: actions,
                    SampleBatch.REWARDS: rew,
                    SampleBatch.TERMINATEDS: term,
                    SampleBatch.TRUNCATEDS: trunc,
                    SampleBatch.T: ep_len,
                    **extra,
                }
                if stateful:
                    # the learn form opens a new episode where the
                    # rollout reset the state: at a row that starts one
                    row["resets"] = (ep_len == 0).astype(jnp.float32)
                if mode == "gae" and stateful and not last:
                    row["_v_next"] = sharding_lib.varying(
                        jnp.zeros(rew.shape, jnp.float32), axis
                    )
                elif mode == "gae" and stateful:
                    # V(final obs) needs a forward from the advanced
                    # state, which the next step's act computes anyway
                    # unless the episode was cut: only a truncation
                    # pays for a forward of its own
                    with jax.named_scope("rollout/act"):
                        row["_v_next"] = jax.lax.cond(
                            jnp.any(trunc & ~term),
                            lambda: sharding_lib.varying(
                                next_value(obs2, mstate2), axis
                            ),
                            lambda: sharding_lib.varying(
                                jnp.zeros(rew.shape, jnp.float32), axis
                            ),
                        )
                elif mode == "gae":
                    # fresh V(final obs) for boundary/tail bootstraps
                    # — same (N,) forward shape as the act-path value,
                    # so the two lanes' bootstraps agree
                    with jax.named_scope("rollout/act"):
                        obs2_b = jax.lax.optimization_barrier(obs2)
                        _, v_next, _ = value_fwd(params_b, obs2_b)
                    row["_v_next"] = v_next
                with jax.named_scope("rollout/env_step"):
                    metrics = {
                        "ep_return": jnp.where(done, ep_ret2, 0.0),
                        "ep_length": jnp.where(done, ep_len2, 0),
                        "done": done,
                    }
                    if report_actions:
                        metrics["actions"] = actions
                        if block > 1:  # with the pass that committed each
                            metrics[SampleBatch.UNMASK_STEP] = extra[
                                SampleBatch.UNMASK_STEP
                            ]
                    env_state = tree_where(done, env_state3, env_state2)
                    obs_next = tree_where(done, obs3, obs2)
                    ep_ret = jnp.where(done, 0.0, ep_ret2)
                    ep_len = jnp.where(done, 0, ep_len2)
                if stateful and last:
                    # only a step on which some stream ended pays for
                    # the pass over the state (a language model's is
                    # hundreds of MB; an episode ends once in thousands
                    # of steps)
                    with jax.named_scope("rollout/state_reset"):
                        mstate2 = jax.lax.cond(
                            jnp.any(done),
                            lambda s: policy.reset_model_state(s, done),
                            lambda s: s,
                            mstate2,
                        )
                return (
                    (env_state, obs_next, ep_ret, ep_len, mstate2),
                    (row, metrics),
                )

            def scan_steps(c, keys):
                """``keys``' env steps: one scan step each, or one a
                block (the first of the block's keys), its tokens' rows
                then laid out one a step like every other model's."""
                if block == 1:
                    return jax.lax.scan(step, c, keys)
                c, ys = jax.lax.scan(block_step, c, keys[::block])
                return c, jax.tree_util.tree_map(
                    lambda x: x.reshape((-1,) + x.shape[2:]), ys
                )

            c0 = (
                carry["env"],
                carry["obs"],
                carry["ep_ret"],
                carry["ep_len"],
                tuple(carry["state"]) if stateful else (),
            )
            starts = None
            if not stored or unroll == T:
                if stored:
                    starts = jax.tree_util.tree_map(
                        lambda x: x[None], c0[4]
                    )
                c1, (rows, metrics) = scan_steps(c0, ro_rngs)
            else:
                # the state at the start of every unroll-step chunk
                def chunk(c, keys):
                    c2, ys = scan_steps(c, keys)
                    return c2, (ys, c[4])

                c1, ((rows, metrics), starts) = jax.lax.scan(
                    chunk, c0,
                    ro_rngs.reshape((T // unroll, unroll) + ro_rngs.shape[1:]),
                )
                rows, metrics = jax.tree_util.tree_map(
                    lambda x: x.reshape((T,) + x.shape[2:]), (rows, metrics)
                )
            env_state, obs, ep_ret, ep_len, mstate = c1
            carry = {
                "env": env_state,
                "obs": obs,
                "ep_ret": ep_ret,
                "ep_len": ep_len,
            }
            if stateful:
                carry["state"] = mstate
            with jax.named_scope("rollout/postprocess"):
                # global env index of each local row (host-lane
                # AGENT_INDEX semantics)
                shard0 = jax.lax.axis_index(axis) * n_loc
                rows[SampleBatch.AGENT_INDEX] = jnp.broadcast_to(
                    shard0 + jnp.arange(n_loc, dtype=jnp.int32), (T, n_loc)
                )
                if mode == "gae":
                    with jax.named_scope("gae"):
                        values = rows[SampleBatch.VF_PREDS]  # (T, N)
                        fresh = rows.pop("_v_next")  # (T, N)
                        term = rows[SampleBatch.TERMINATEDS]
                        done = term | rows[SampleBatch.TRUNCATEDS]
                        if stateful:
                            # the tail's bootstrap: one forward from
                            # the final state (not committed)
                            with jax.named_scope("rollout/act"):
                                tail = next_value(obs, mstate)[None]
                        else:
                            tail = fresh[-1:]
                        # interior rows reuse the act-path values
                        # exactly like the host lane's vpred_t[1:];
                        # boundary/tail rows use the fresh
                        # terminal-observation values
                        shifted = jnp.concatenate(
                            [values[1:], tail], axis=0
                        )
                        next_values = jnp.where(done, fresh, shifted)
                        adv, vt = compute_gae_fragment(
                            rows[SampleBatch.REWARDS].T,
                            values.T,
                            next_values.T,
                            term.T,
                            done.T,
                            gamma,
                            lam,
                        )  # (N, T)
                        if standardize:
                            m = jax.lax.pmean(adv.mean(), axis)
                            var = jax.lax.pmean(
                                ((adv - m) ** 2).mean(), axis
                            )
                            adv = (adv - m) / jnp.maximum(
                                1e-4, jnp.sqrt(var)
                            )
                        rows[SampleBatch.ADVANTAGES] = adv.T
                        rows[SampleBatch.VALUE_TARGETS] = vt.T

                # (T, N, ...) -> env-major (N*T, ...) rows, the host
                # lane's concat order
                def to_rows(v):
                    v = jnp.swapaxes(v, 0, 1)
                    return v.reshape((n_loc * v.shape[1],) + v.shape[2:])

                batch = {k: to_rows(v) for k, v in rows.items()}
                if starts is not None:
                    # hand-over: one row per unroll, env-major like the
                    # rows (what the nest's ``__chunk__`` gather reads)
                    with jax.named_scope("rollout/state_handover"):
                        for i, leaf in enumerate(starts):
                            batch[f"__chunk__state_in_{i}"] = to_rows(leaf)
            return carry, batch, metrics

        self._body = body
        return body

    # -- fused rollout+learn feed ----------------------------------------

    def superstep_feed(self) -> RolloutSuperstepFeed:
        self._pre_dispatch()
        return RolloutSuperstepFeed(
            carry=self._carry,
            body=self._learn_feed_body(),
            steps=self.T,
            key=(
                "jax_rollout",
                type(self.env).__name__,
                self.N,
                self.T,
                self.postprocess,
                self.standardize,
            ),
        )

    def _learn_feed_body(self):
        """The superstep-slot body: rollout, then hand the UPDATE the
        learn-column subset (NEXT_OBS etc. stay out of the nest's
        minibatch gathers, mirroring ``_batch_to_train_tree``)."""
        body = self._rollout_body()

        def fn(params, carry, ro_rngs, coeffs):
            carry, batch, metrics = body(params, carry, ro_rngs, coeffs)
            learn = {
                k: v for k, v in batch.items() if k not in _LEARN_DROP
            }
            return carry, learn, metrics

        return fn

    def advance(self, carry, metrics) -> None:
        """Commit the carry a fused superstep returned and absorb its
        drained (host numpy) metrics tree."""
        self._carry = carry
        self._record_metrics(metrics)
        self._count_env_steps(int(np.asarray(metrics["done"]).size))

    # -- standalone rollout (replay fill / per-update lane) --------------

    def _rollout_program(self):
        """The standalone rollout program: the per-shard body under
        ``shard_map`` and ``sharded_jit``, built on first use."""
        if self._rollout_fn is not None:
            return self._rollout_fn
        import jax
        from jax.sharding import PartitionSpec as P

        from ray_tpu import sharding as sharding_lib

        policy = self.policy
        axis = sharding_lib.data_axis(self.mesh)
        body = self._rollout_body()

        def program(params, carry, ro_rngs, coeffs):
            return body(params, carry, ro_rngs, coeffs)

        # params enter per their spec tree (P() = replicated on
        # un-partitioned policies; per-leaf model-axis slices for
        # partitioned ones — the model inserts its own collectives)
        p_ps = getattr(policy, "param_pspecs", None)
        p_ps = (
            P()
            if p_ps is None
            else sharding_lib.manual_pspecs(self.mesh, p_ps)
        )
        sharded = jax.shard_map(
            program,
            mesh=self.mesh,
            in_specs=(p_ps, P(axis), P(), P()),
            out_specs=(
                P(axis),
                P(axis),
                P(None, axis),
            ),
        )
        rep = sharding_lib.replicated(self.mesh)
        p_sh = getattr(policy, "param_shardings", None) or rep
        dat = sharding_lib.batch_sharded(self.mesh)
        met = sharding_lib.batch_sharded(self.mesh, ndim_prefix=2)
        self._rollout_fn = sharding_lib.sharded_jit(
            sharded,
            in_specs=(p_sh, dat, rep, rep),
            out_specs=(dat, dat, met),
            label=(
                f"jax_rollout[{type(self.env).__name__}:"
                f"{self.N}x{self.T}]"
            ),
        )
        return self._rollout_fn

    def rollout_from(self, params, carry, ro_rngs, coeffs):
        """One dispatch of the standalone rollout program from a GIVEN
        carry and key stack: ``(carry, batch, metrics)``, all on the
        device, nothing committed to the engine (a comparison replays
        the lane's body this way)."""
        return self._rollout_program()(params, carry, ro_rngs, coeffs)

    def rollout(self):
        """One dispatched rollout: returns ``(device batch tree,
        batch_size)`` with the env carry advanced. The policy's rng
        advances by T sequential splits, the actor lane's per-step
        stream in its order, composed in one program
        (``JaxPolicy._rollout_keys``), so a rollout is two dispatches:
        the key schedule and the rollout program.

        The host does NOT wait for the program: the episode metrics
        (done flags, returns, lengths, the actions) stay behind as the
        engine's one pending read, their copy to the host started, and
        whatever the caller dispatches next queues behind the rollout
        on a busy chip. The read is finished by the first of
        :meth:`get_metrics`, ``last_actions`` / ``last_trace``, or the
        next ``rollout()`` once that one has dispatched its own
        program, so episodes are recorded in the order they ended."""
        import jax

        from ray_tpu import sharding as sharding_lib

        policy = self.policy
        # host upkeep before the dispatch: exploration coefficients,
        # then the T key splits as one program
        with tracing.start_span(
            "rollout:keys", steps=self.T, dispatches=1
        ):
            coeffs = self._pre_dispatch()
            ro_rngs = policy._rollout_keys(self.T)
        telemetry_metrics.add_h2d_bytes("rollout", int(ro_rngs.nbytes))
        with tracing.start_span(
            "rollout:device", num_envs=self.N, steps=self.T
        ):
            self._carry, batch, metrics = self.rollout_from(
                policy.params, self._carry, ro_rngs, coeffs
            )
        # the previous rollout's read, behind this one's dispatch
        self._finish_drain()
        for leaf in jax.tree_util.tree_leaves(metrics):
            leaf.copy_to_host_async()
        self._pending = (metrics, sharding_lib.dispatch_count())
        self._count_env_steps(self.batch_size)
        return dict(batch), self.batch_size

    def _finish_drain(self) -> None:
        """Finish the pending read, if there is one: the bytes are on
        the host already wherever the chip has run the rollout since,
        and the read costs a copy."""
        if self._pending is None:
            return
        import jax

        from ray_tpu import sharding as sharding_lib

        (metrics, mark), self._pending = self._pending, None
        deferred = sharding_lib.dispatch_count() != mark
        with tracing.start_span("rollout:drain", deferred=deferred) as drain:
            metrics = jax.device_get(metrics)
            drain.set_attribute("bytes", sharding_lib.tree_nbytes(metrics))
        telemetry_metrics.inc_rollout_drain(deferred)
        self._record_metrics(metrics)

    @property
    def last_actions(self):
        self._finish_drain()
        return self._last_actions

    @property
    def last_trace(self):
        self._finish_drain()
        return self._last_trace

    def _count_env_steps(self, steps: int) -> None:
        telemetry_metrics.inc_env_steps_on_device(steps)
        if self.tokens_per_step > 1:
            telemetry_metrics.note_diffusion_rollout(
                self.policy.model.generation.token_passes(steps), steps
            )

    def learn_batch(self, batch: Dict[str, Any]) -> Dict[str, Any]:
        """The learn-column subset of a :meth:`rollout` batch (what
        the fused feed hands the nest)."""
        return {k: v for k, v in batch.items() if k not in _LEARN_DROP}

    def _pre_dispatch(self):
        """Host-side per-dispatch upkeep mirroring compute_actions:
        advance exploration schedules, then snapshot coeffs."""
        policy = self.policy
        policy.exploration.update_coeffs(
            policy.coeff_values, policy.global_timestep
        )
        return policy._coeff_array()

    # -- episode metrics --------------------------------------------------

    def _record_metrics(self, metrics) -> None:
        self._last_actions = metrics.get("actions")
        self._last_trace = metrics.get(SampleBatch.UNMASK_STEP)
        done = np.asarray(metrics["done"]).reshape(-1)
        if not done.any():
            return
        rets = np.asarray(metrics["ep_return"]).reshape(-1)[done]
        lens = np.asarray(metrics["ep_length"]).reshape(-1)[done]
        for r, l in zip(rets, lens):
            self._metrics.append(RolloutMetrics(int(l), float(r)))

    def get_metrics(self) -> List[RolloutMetrics]:
        self._finish_drain()
        out = self._metrics
        self._metrics = []
        return out
