"""JaxPolicy: the TPU-native Policy implementation.

This is the "missing half" the reference sketched but never built: RLlib
supports ``build_policy_class(framework="jax")`` but its parent class is
still TorchPolicy (``rllib/policy/policy_template.py:135,247``). JaxPolicy
replaces the whole TorchPolicy multi-GPU mechanism
(``rllib/policy/torch_policy.py:60``: ``learn_on_batch :467``,
``load_batch_into_buffer :498``, ``_multi_gpu_parallel_grad_calc :1049``)
with a single jitted update:

  - the entire SGD nest — ``num_sgd_iter`` epochs × minibatches, per-device
    shuffling, loss/grad, ICI gradient pmean, optimizer — compiles to ONE
    XLA program via ``jax.shard_map`` over the learner mesh, lowered
    through the ``ray_tpu.sharding`` runtime (``sharded_jit`` with
    replicated-param / row-sharded-batch NamedShardings and opt-state
    donation);
  - no loader threads, no per-device towers, no CPU gradient averaging;
  - schedule-driven scalars (lr, entropy coeff, kl coeff) enter as traced
    scalar args so schedules never trigger recompilation.

The same class serves rollout actors (CPU platform, jitted
``compute_actions``) and the learner (TPU mesh).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu import sharding as sharding_lib
from ray_tpu.data.sample_batch import SampleBatch
from ray_tpu.models.catalog import ModelCatalog
from ray_tpu.ops.framestack import FRAME_IDX as _FRAME_IDX
from ray_tpu.ops.framestack import FRAMES as _FRAMES
from ray_tpu.policy.policy import Policy
from ray_tpu.telemetry import device as device_ledger
from ray_tpu.telemetry import metrics as telemetry_metrics
from ray_tpu.util import tracing
from ray_tpu.utils.metrics import timer_histogram


def _tree_to_device(tree, sharding=None):
    return jax.device_put(tree, sharding) if sharding else jax.device_put(tree)


def _grouped_loss_grad(loss_fn, groups, reduce_stats, params, aux, mb, rng,
                       coeffs, axis):
    """``((loss, stats), grads)`` of the mean loss over a minibatch
    whose rows go through ``loss_fn`` in ``groups`` equal groups of
    whole unrolls, one after another: each group's forward, loss and
    backward end before the next begins, and its gradient is added to
    one accumulator, so one group's activations are alive and one
    gradient tree. The loss is a mean over rows, so the mean of the
    groups' losses is the minibatch's. ``__chunk__`` columns (a row an
    unroll) split like the row columns (rows are unroll-major).
    ``reduce_stats`` makes one update's stats of the groups' stacked."""
    parts = {
        k: v.reshape((groups, v.shape[0] // groups) + v.shape[1:])
        for k, v in mb.items()
    }

    def scaled(p, part, key):
        loss, stats = loss_fn(p, aux, part, key, coeffs)
        return loss / groups, stats

    def one(acc, xs):
        part, i = xs
        (loss, stats), g = jax.value_and_grad(scaled, has_aux=True)(
            params, part, jax.random.fold_in(rng, i)
        )
        return jax.tree_util.tree_map(jnp.add, acc, g), (loss, stats)

    zeros = sharding_lib.varying(
        jax.tree_util.tree_map(jnp.zeros_like, params), axis
    )
    grads, (losses, stats) = jax.lax.scan(
        one, zeros, (parts, jnp.arange(groups))
    )
    return (losses.sum(), reduce_stats(stats)), grads


def _global_norm(grads):
    """``optax.global_norm`` of a gradient tree whose leaves may be
    partitioned over further mesh axes (the model axis): a sliced
    leaf's squared norm is summed across its slices, so every shard
    reports the norm of the WHOLE tree and the result is replicated.
    On an un-partitioned tree this is ``optax.global_norm`` itself."""
    if not sharding_lib.vma_of(grads):
        return optax.global_norm(grads)
    total = jnp.float32(0.0)
    for g in jax.tree_util.tree_leaves(grads):
        sq = jnp.sum(jnp.square(g.astype(jnp.float32)))
        sliced = tuple(jax.typeof(sq).vma)
        total = total + (jax.lax.psum(sq, sliced) if sliced else sq)
    return jnp.sqrt(total)


class JaxPolicy(Policy):
    """Base JAX policy. Subclasses (or ``build_jax_policy`` templates)
    override :meth:`loss` and optionally :meth:`extra_action_out`,
    :meth:`stats_coeffs`, :meth:`postprocess_trajectory`."""

    # Names of host-side scalar coefficients fed into the loss each call
    # (e.g. PPO's adaptive kl coeff). Values live in self.coeff_values.
    coeff_names: Tuple[str, ...] = ("lr", "entropy_coeff")

    # Exploration strategy used when exploration_config gives no "type"
    # (reference Policy._create_exploration default per algorithm).
    default_exploration: str = "StochasticSampling"

    # Recurrent unroll length; instance-overridden in __init__ for
    # recurrent models. A class default so bespoke-net policies that
    # bypass JaxPolicy.__init__ (SAC/DDPG families) stay feedforward.
    _unroll_T: int = 1

    # Per-leaf param placement (docs/sharding.md "2-D mesh & param
    # partitioning"). Class defaults = the replicated legacy contract,
    # so bespoke-net policies that bypass __init__ (SAC/DDPG families)
    # keep replicated trees; __init__ installs per-leaf trees when the
    # mesh carries a "model" axis and the model declares rules.
    _param_pspecs = None
    _opt_pspecs = None
    _opt_sharding = None

    @property
    def last_learn_timers(self) -> Dict[str, float]:
        """Per-stage timers of the most recent learn call (device
        transfer / compile / step), lazily created so bespoke-net
        policies that bypass __init__ report them too."""
        t = self.__dict__.get("_last_learn_timers")
        if t is None:
            t = self.__dict__["_last_learn_timers"] = {}
        return t

    def __init__(self, observation_space, action_space, config: Dict):
        super().__init__(observation_space, action_space, config)
        self.model_config = dict(config.get("model") or {})
        dist_type = config.get("dist_type")
        self.dist_class, self.num_outputs = ModelCatalog.get_action_dist(
            action_space, self.model_config, dist_type
        )
        self.model = ModelCatalog.get_model(
            observation_space, action_space, self.num_outputs,
            self.model_config,
        )
        # Recurrent learn-path unroll length (reference max_seq_len,
        # rnn_sequencing.py chop length): flat train rows are chopped
        # into fixed (B, T) unrolls with zero initial state at chunk
        # starts and a `resets` column at episode/fragment boundaries.
        self._unroll_T = (
            int(self.model_config.get("max_seq_len", 20))
            if self.model.is_recurrent
            else 1
        )

        # ---- mesh / shardings (ray_tpu.sharding runtime) ----
        self.mesh = sharding_lib.resolve_mesh(config)
        self.n_shards = sharding_lib.num_shards(self.mesh)
        self._param_sharding = sharding_lib.replicated(self.mesh)
        self._data_sharding = sharding_lib.batch_sharded(self.mesh)

        # ---- params / optimizer ----
        seed = int(config.get("seed") or 0)
        self._rng = jax.random.PRNGKey(seed)
        self._rng, init_rng = jax.random.split(self._rng)
        with tracing.phase("setup:model_init") as _init:
            init_args = (init_rng, self._dummy_obs(batch=2))
            init_kwargs = {}
            if self.model.is_recurrent:
                init_args = (
                    init_rng,
                    init_args[1][:, None],
                    self.model.initial_state(2),
                )
                if getattr(self.model, "use_prev_action", False):
                    init_kwargs["prev_actions"] = jnp.zeros(
                        (2, 1) + tuple(action_space.shape or ()),
                        jnp.float32,
                    )
                if getattr(self.model, "use_prev_reward", False):
                    init_kwargs["prev_rewards"] = jnp.zeros(
                        (2, 1), jnp.float32
                    )
            self.params = self.model.init(*init_args, **init_kwargs)
            # per-leaf placement: on a mesh with a "model" axis a model
            # that declares partition rules gets sharded param trees
            self._install_param_placement()
            self.params = _tree_to_device(self.params, self._param_sharding)
            _note_tree_size(_init, self.params)

        grad_clip = config.get("grad_clip")
        chain = []
        if grad_clip:
            chain.append(optax.clip_by_global_norm(grad_clip))
        chain.append(optax.scale_by_adam(eps=config.get("adam_epsilon", 1e-8)))
        self._tx = optax.chain(*chain)
        with tracing.phase("setup:optimizer_init"):
            opt0 = self._tx.init(self.params)
            if self._param_pspecs is not None:
                # optimizer moments inherit each param's placement
                # (suffix-matched by path+shape); scalars replicate
                self._opt_pspecs = sharding_lib.state_pspecs(
                    opt0, self.params, self._param_pspecs
                )
                self._opt_sharding = sharding_lib.named_tree(
                    self.mesh, self._opt_pspecs
                )
            else:
                self._opt_sharding = self._param_sharding
            self.opt_state = _tree_to_device(opt0, self._opt_sharding)

        # ---- schedules / coefficients ----
        from ray_tpu.utils.schedules import make_schedule

        self._lr_schedule = make_schedule(
            config.get("lr_schedule"), config.get("lr", 5e-5)
        )
        self._entropy_schedule = make_schedule(
            config.get("entropy_coeff_schedule"),
            config.get("entropy_coeff", 0.0),
        )
        self.coeff_values: Dict[str, float] = {
            "lr": float(self._lr_schedule(0)),
            "entropy_coeff": float(self._entropy_schedule(0)),
        }
        self._init_coeffs()

        # SGD geometry (static per compile)
        self.train_batch_size = int(config.get("train_batch_size", 4000))
        self.minibatch_size = int(
            config.get("sgd_minibatch_size")
            or config.get("train_batch_size", 4000)
        )
        self.num_sgd_iter = int(config.get("num_sgd_iter", 1))

        # (batch_size, with_frames) -> compiled SGD-nest program
        self._learn_fns: Dict[Tuple[int, bool], Any] = {}
        self._action_fn = None
        self._value_fn = None
        self.num_grad_updates = 0
        # Non-gradient state (target networks etc) — placement follows
        # the params it mirrors (suffix-matched) when partitioned.
        self.aux_state: Dict[str, Any] = self._init_aux_state()
        self._publish_params_bytes()

        # ---- exploration ----
        self._init_exploration()

        # ---- view requirements (reference view_requirement.py:15) ----
        # Shifted columns the sampler should populate for this policy.
        from ray_tpu.policy.policy import ViewRequirement

        mc = self.model_config
        if mc.get("lstm_use_prev_action") or mc.get("use_prev_action"):
            self.view_requirements[SampleBatch.PREV_ACTIONS] = (
                ViewRequirement(
                    data_col=SampleBatch.ACTIONS, shift=-1,
                    space=action_space,
                )
            )
        if mc.get("lstm_use_prev_reward") or mc.get("use_prev_reward"):
            self.view_requirements[SampleBatch.PREV_REWARDS] = (
                ViewRequirement(
                    data_col=SampleBatch.REWARDS, shift=-1
                )
            )

    # -- subclass hooks --------------------------------------------------

    def _init_exploration(self) -> None:
        """(Re)build the exploration strategy, merge its scheduled
        coefficients, and reset its carried state. Shared by __init__
        and update_config here and in the actor-critic policies (SAC,
        DDPG) that bypass the base constructor."""
        from ray_tpu.utils.exploration import exploration_from_config

        self.exploration = exploration_from_config(
            self.config,
            self.action_space,
            getattr(self, "model_config", None)
            or self.config.get("model")
            or {},
            default=self.default_exploration,
        )
        self.coeff_values.update(self.exploration.init_coeffs())
        self._expl_state: Tuple = ()
        self._expl_state_batch = -1
        self._last_obs = None  # for ParameterNoise sigma adaptation

    def _refold_exploration_config(self, new_config: Dict) -> None:
        """Hook for subclasses that mirror flat config knobs into
        exploration_config (DQN's epsilon surface)."""

    def _init_coeffs(self) -> None:
        """Subclasses add extra coefficients to self.coeff_values."""

    def _init_aux_state(self) -> Dict[str, Any]:
        """Subclasses return initial aux (non-gradient) state, e.g.
        target-network params."""
        return {}

    def loss(
        self,
        params,
        batch: Dict[str, jnp.ndarray],
        rng: jax.Array,
        coeffs: Dict[str, jnp.ndarray],
    ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        raise NotImplementedError

    def loss_with_aux(self, params, aux, batch, rng, coeffs):
        """Loss entry point inside the learn program. ``aux`` is the
        replicated non-gradient state (e.g. target-network params for
        DQN/SAC — the reference keeps these as separate torch modules);
        base policies ignore it."""
        return self.loss(params, batch, rng, coeffs)

    def extra_action_out(
        self, dist_inputs, value, dist, rng
    ) -> Dict[str, jnp.ndarray]:
        """Extra per-step fetches stored into the SampleBatch
        (reference TorchPolicy.extra_action_out)."""
        return {SampleBatch.VF_PREDS: value}

    # -- model helpers ---------------------------------------------------

    def _dummy_obs(self, batch: int = 2) -> jnp.ndarray:
        shape = self.observation_space.shape
        dtype = self.observation_space.dtype
        return jnp.zeros((batch,) + tuple(shape), dtype)

    def model_forward(
        self,
        params,
        obs,
        state=(),
        resets=None,
        prev_actions=None,
        prev_rewards=None,
    ):
        """Uniform forward: handles recurrent (B, T) vs flat (B,) models.
        Returns (dist_inputs, value, state_out) flattened over (B*T,).
        prev_actions/prev_rewards feed recurrent models configured with
        lstm_use_prev_action/_reward (view-requirement columns)."""
        if self.model.is_recurrent:
            kwargs = {}
            if resets is not None:
                kwargs["resets"] = resets
            if prev_actions is not None:
                kwargs["prev_actions"] = prev_actions
            if prev_rewards is not None:
                kwargs["prev_rewards"] = prev_rewards
            return self.model.apply(params, obs, state, **kwargs)
        return self.model.apply(params, obs)

    def get_initial_state(self) -> List[np.ndarray]:
        return [np.asarray(s[0]) for s in self.model.initial_state(1)]

    def _apply_model_for_actions(self, params, obs, rng, explore):
        """Non-recurrent inference forward inside the jitted action fn.
        Override to thread inference-time randomness into the model
        (e.g. NoisyNet weight noise in the DQN family); ``explore`` is
        static under jit. The default ignores both."""
        return self.model.apply(params, obs)

    # -- param placement (2-D data x model meshes) -----------------------

    def _model_partition_rules(self):
        """Ordered placement rules for this policy's params:
        ``model_config["partition_rules"]`` wins, then the model
        class's escape hatch / own rules (``with_logical_rules`` /
        ``partition_rules()``). None = replicate everything."""
        mc = getattr(self, "model_config", None) or {}
        if mc.get("partition_rules"):
            return tuple(mc["partition_rules"])
        model = getattr(self, "model", None)
        if model is None:
            return None
        ov = getattr(model, "_partition_rules_override", None)
        if ov is not None:
            return tuple(ov)
        fn = getattr(model, "partition_rules", None)
        if callable(fn):
            try:
                rules = fn()
            except TypeError:  # pragma: no cover - odd signatures
                rules = None
            if rules:
                return tuple(rules)
        return None

    def _install_param_placement(self) -> None:
        """Derive per-leaf param specs from the model's rules when the
        mesh has a model axis (docs/sharding.md). Runs on the HOST
        param tree right after model.init, before device placement."""
        if sharding_lib.model_axis(self.mesh) is None:
            return
        rules = self._model_partition_rules()
        if not rules:
            return
        self._param_pspecs = sharding_lib.param_pspecs(
            self.params, self.mesh, rules
        )
        self._param_sharding = sharding_lib.named_tree(
            self.mesh, self._param_pspecs
        )

    @property
    def param_shardings(self):
        """Per-leaf NamedSharding tree of the params (a single
        replicated NamedSharding on un-partitioned policies) — the
        placement serve/rollout/checkpoint call sites must use instead
        of assuming replication."""
        return self._param_sharding

    @property
    def param_pspecs(self):
        """PartitionSpec tree of the params; None = replicated."""
        return self._param_pspecs

    @property
    def is_model_sharded(self) -> bool:
        """Whether params are actually split across a model axis of
        size > 1 (a size-1 axis keeps every leaf whole — the parity
        geometry)."""
        return (
            self._param_pspecs is not None
            and sharding_lib.model_shards(self.mesh) > 1
        )

    def _params_match_active_rules(self) -> bool:
        """Do the live param arrays sit where the active rules say
        (same mesh, per-leaf placement)? False e.g. after a raw
        device_put replaced the tree — the serve plane gates its fused
        forward on this."""
        if self._param_pspecs is None:
            return True
        try:
            arrs = jax.tree_util.tree_leaves(self.params)
            wants = jax.tree_util.tree_leaves(
                self._param_sharding,
                is_leaf=lambda x: isinstance(x, NamedSharding),
            )
            if len(arrs) != len(wants):
                return False
            for arr, want in zip(arrs, wants):
                s = getattr(arr, "sharding", None)
                if s is None or not s.is_equivalent_to(want, arr.ndim):
                    return False
            return True
        except Exception:
            return False

    def _carry_pspecs(self, with_frames: bool = False):
        """(params, opt_state, aux) PartitionSpec trees for learn-
        program construction — bare ``P()`` everywhere on the
        replicated path, per-leaf trees when partitioned (aux leaves
        suffix-match the params they mirror, e.g. target networks)."""
        if self._param_pspecs is None:
            return P(), P(), P()
        p_ps = self._param_pspecs
        o_ps = (
            self._opt_pspecs
            if self._opt_pspecs is not None
            else sharding_lib.state_pspecs(
                self.opt_state, self.params, p_ps
            )
        )
        a_ps = sharding_lib.state_pspecs(
            self.aux_state, self.params, p_ps
        )
        if with_frames and isinstance(a_ps, dict):
            a_ps = {"__frames__": P(), **a_ps}
        return p_ps, o_ps, a_ps

    def _carry_shardings(self, a_ps):
        """The learn programs' explicit placement: NamedShardings of
        (params, opt_state, aux) per their spec trees (all replicated
        unless partitioned; ``a_ps`` is the aux tree of
        :meth:`_carry_pspecs`), then the replicated sharding that rng
        and coeffs take — jit broadcasts one sharding over each
        argument's pytree leaves."""
        rep = sharding_lib.replicated(self.mesh)
        p_sh = self._param_sharding
        a_sh = (
            sharding_lib.named_tree(self.mesh, a_ps)
            if self._param_pspecs is not None
            else rep
        )
        return p_sh, self._opt_sharding or p_sh, a_sh, rep

    def _publish_params_bytes(self) -> None:
        """``ray_tpu_params_bytes{policy,placement}``: global tree
        bytes + what one device holds under the active placement."""
        try:
            total = sharding_lib.tree_nbytes(self.params)
            if self._param_pspecs is not None:
                per_shard = sharding_lib.tree_shard_nbytes(
                    self.params, self._param_pspecs, self.mesh
                )
            else:
                per_shard = total
            telemetry_metrics.set_params_bytes(
                type(self).__name__, total, per_shard
            )
        except Exception:  # telemetry must never break the policy
            pass

    # -- inference -------------------------------------------------------

    def _action_step_body(
        self, params, obs, rng, coeffs, *, explore=True, expl_state=(),
        state=(), prev_actions=None, prev_rewards=None,
    ):
        """The per-step action computation — model forward,
        distribution, exploration sampling, extra fetches — as a pure
        traced body: ``(actions, state_out, extra, expl_state)``.
        ``state`` is the model's per-stream state pytree (``()`` for a
        feedforward model): a model with state takes ``obs`` as a
        one-step unroll and hands the state back advanced. Shared by
        the jitted ``compute_actions`` program
        (:meth:`_build_action_fn`) and the device rollout lane
        (``execution/jax_rollout.py``), with the SAME internal rng
        split structure, so the two rollout lanes consume identical
        key streams per step (the fixed-seed parity contract of
        docs/pipeline.md)."""
        if self.model.is_recurrent:
            kwargs = {}
            if prev_actions is not None:
                kwargs["prev_actions"] = prev_actions[:, None]
            if prev_rewards is not None:
                kwargs["prev_rewards"] = prev_rewards[:, None]
            dist_inputs, value, state_out = self.model.apply(
                params, obs[:, None], state, **kwargs
            )
        else:
            rng_m, rng = jax.random.split(rng)
            dist_inputs, value, state_out = self._apply_model_for_actions(
                params, obs, rng_m, explore
            )
        dist = self.dist_class(dist_inputs)
        rng_x, rng = jax.random.split(rng)
        actions, logp, expl_state = self.exploration.sample_fn(
            dist, rng_x, explore, coeffs, expl_state
        )
        extra = {
            SampleBatch.ACTION_DIST_INPUTS: dist_inputs,
            SampleBatch.ACTION_LOGP: logp,
        }
        extra.update(
            self.extra_action_out(dist_inputs, value, dist, rng)
        )
        return actions, state_out, extra, expl_state

    def _block_forward(self, params):
        """The block form of a model that commits a block a step, as
        its generation description calls it."""
        return lambda tokens, state, commit: self.model.apply(
            params, tokens, state, commit=commit
        )

    def action_block_body(self, params, rng, state):
        """:meth:`_action_step_body` for a model whose
        ``tokens_per_step`` is a block: ``(actions (N, B), state after
        the block, extra)``, every ``extra`` column ``(N, B, ...)``. The
        model's generation description denoises and commits (it samples
        by confidence, not one categorical a stream: the exploration is
        its own, at temperature 1); what is stored for a token is the
        forward's that committed it, and ``unmask_step`` says which."""
        rng_x, _ = jax.random.split(rng)
        tokens, state_out, kept = self.model.generation.generate(
            self._block_forward(params), state, rng_x
        )
        return tokens, state_out, {
            SampleBatch.ACTION_DIST_INPUTS: kept["logits"],
            SampleBatch.ACTION_LOGP: kept["logp"],
            SampleBatch.VF_PREDS: kept["value"],
            SampleBatch.UNMASK_STEP: kept["trace"],
        }

    def block_first_value(self, params, state):
        """``(N,)``: the value the streams' NEXT blocks start from (the
        lane's tail and truncation bootstraps)."""
        return self.model.generation.first_value(
            self._block_forward(params), state
        )

    def reset_model_state(self, state, mask):
        """The model's per-stream state with the rows of ``mask`` (N,)
        bool set to the start of an episode: the model's own
        ``reset_state`` where it has one (a key/value cache need not
        be cleared), its ``initial_state`` otherwise."""
        reset = getattr(self.model, "reset_state", None)
        if reset is not None:
            return reset(state, mask)
        from ray_tpu.env.jax_env import tree_where

        return tree_where(
            mask, tuple(self.model.initial_state(mask.shape[0])), tuple(state)
        )

    @property
    def supports_batched_serve(self) -> bool:
        """Whether concurrent single-request inference may coalesce
        into the serve plane's fused batched forward
        (``serve/policy_server.py``): the program vmaps
        :meth:`_action_step_body` over per-request rng keys, so it
        needs a feedforward model and stateless exploration (carried
        OU/ParameterNoise state is per-stream, and a request stream
        has no stable slot identity). Ineligible policies still serve,
        one ``compute_actions`` per request."""
        return (
            not self.model.is_recurrent
            and not self.exploration.needs_last_obs
            and self.exploration.initial_state(1) == ()
            # model-sharded params may fuse only while the serve mesh
            # is the training mesh with params placed per the active
            # rules (the fused forward carries the per-leaf shardings);
            # anything else falls back to per-request compute_actions
            # through the same queue (docs/serving.md)
            and (
                not self.is_model_sharded
                or self._params_match_active_rules()
            )
        )

    @property
    def supports_jax_rollout(self) -> bool:
        """Whether this policy's act path can lower into the device
        rollout lane's scanned program (``execution/jax_rollout.py``):
        stateless exploration and a model whose only inputs are
        the observation and its own per-stream state (the lane carries
        that state with the env's; a model fed the previous action or
        reward, and stateful exploration such as OU noise or
        ParameterNoise, stay on the actor lane)."""
        return (
            not getattr(self.model, "use_prev_action", False)
            and not getattr(self.model, "use_prev_reward", False)
            and not self.exploration.needs_last_obs
            and self.exploration.initial_state(1) == ()
        )

    def _build_action_fn(self):
        model = self.model
        recurrent = model.is_recurrent
        use_prev_a = recurrent and getattr(
            model, "use_prev_action", False
        )
        use_prev_r = recurrent and getattr(
            model, "use_prev_reward", False
        )

        def fn(
            params, obs, states, rng, explore, coeffs, expl_state,
            prev_a, prev_r,
        ):
            return self._action_step_body(
                params, obs, rng, coeffs,
                explore=explore, expl_state=expl_state,
                state=states if recurrent else (),
                prev_actions=prev_a if use_prev_a else None,
                prev_rewards=prev_r if use_prev_r else None,
            )

        return jax.jit(fn, static_argnames=("explore",))

    def compute_actions(
        self,
        obs_batch,
        state_batches=None,
        prev_action_batch=None,
        prev_reward_batch=None,
        explore: bool = True,
        timestep: Optional[int] = None,
        **kwargs,
    ):
        if self._action_fn is None:
            self._action_fn = self._build_action_fn()
        self.exploration.update_coeffs(
            self.coeff_values, self.global_timestep
        )
        params = self.exploration.params_for_inference(self, explore)
        self._rng, rng = jax.random.split(self._rng)
        obs = jnp.asarray(obs_batch)
        if self.exploration.needs_last_obs:
            self._last_obs = obs
        states = tuple(jnp.asarray(s) for s in (state_batches or ()))
        bsize = int(obs.shape[0])
        if self._expl_state_batch != bsize:
            self._expl_state = self.exploration.initial_state(bsize)
            self._expl_state_batch = bsize
        # prev-action/reward inputs for recurrent models that want them
        # (zeros at episode starts / when the caller passes nothing)
        if prev_action_batch is not None:
            prev_a = jnp.asarray(prev_action_batch)
        else:
            prev_a = jnp.zeros(
                (bsize,) + tuple(self.action_space.shape), jnp.float32
            ) if self.action_space.shape else jnp.zeros(
                (bsize,), jnp.int32
            )
        prev_r = (
            jnp.asarray(prev_reward_batch, jnp.float32)
            if prev_reward_batch is not None
            else jnp.zeros((bsize,), jnp.float32)
        )
        actions, state_out, extra, self._expl_state = self._action_fn(
            params, obs, states, rng, bool(explore),
            self._coeff_array(), self._expl_state, prev_a, prev_r,
        )
        return (
            np.asarray(actions),
            [np.asarray(s) for s in state_out],
            {k: np.asarray(v) for k, v in extra.items()},
        )

    def compute_log_likelihoods(
        self, actions, obs_batch, state_batches=None
    ) -> np.ndarray:
        """Log-prob of given actions under the current policy (reference
        Policy.compute_log_likelihoods :660 — used by the IS/WIS
        off-policy estimators). Deliberately NOT jitted: callers pass
        variable-length per-episode slices, and a jit cache keyed on
        every distinct episode length would recompile constantly for a
        sub-millisecond MLP forward."""
        dist_inputs, _, _ = self.model_forward(
            self.params, jnp.asarray(obs_batch)
        )
        return np.asarray(
            self.dist_class(dist_inputs).logp(jnp.asarray(actions))
        )

    def value_batch(self, obs_batch, state_batches=None) -> np.ndarray:
        """Bootstrap values for GAE (reference ppo value branch)."""
        if self._value_fn is None:
            model = self.model

            def fn(params, obs, states):
                if model.is_recurrent:
                    _, value, _ = model.apply(params, obs[:, None], states)
                else:
                    _, value, _ = model.apply(params, obs)
                return value

            self._value_fn = jax.jit(fn)
        states = tuple(jnp.asarray(s) for s in (state_batches or ()))
        return np.asarray(
            self._value_fn(self.params, jnp.asarray(obs_batch), states)
        )

    # -- learning --------------------------------------------------------

    def _coeff_array(self) -> Dict[str, jnp.ndarray]:
        # Cache device scalars; re-transfer only the coefficients whose
        # host values changed (each put is a host→device round trip).
        cache = getattr(self, "_coeff_cache", None)
        if cache is None:
            cache = self._coeff_cache = {}
        out = {}
        for k, v in self.coeff_values.items():
            ent = cache.get(k)
            if ent is None or ent[0] != v:
                ent = (v, jnp.asarray(v, jnp.float32))
                cache[k] = ent
            out[k] = ent[1]
        return out

    def _update_scheduled_coeffs(self):
        t = self.global_timestep
        self.coeff_values["lr"] = float(self._lr_schedule(t))
        self.coeff_values["entropy_coeff"] = float(self._entropy_schedule(t))

    def _nest_device_fn(self, batch_size: int, with_frames: bool = False):
        """The per-batch SGD-nest device body —
        ``(params, opt_state, aux, batch, rng, coeffs) -> (params,
        opt_state, stats)`` — shared by the per-call learn program
        (:meth:`_build_learn_fn`) and the fused superstep scan
        (:meth:`learn_superstep`): both wrap THIS body, so the fused
        chain is bit-identical to per-call dispatch. Runs inside
        ``shard_map`` (uses the mesh collectives)."""
        n_shards = self.n_shards
        stack_k = int(self.observation_space.shape[-1]) if (
            with_frames
        ) else 0
        if batch_size % n_shards:
            raise ValueError(
                f"batch size {batch_size} not divisible by "
                f"{n_shards} data shards"
            )
        b_loc = max(1, batch_size // n_shards)
        mb_loc = min(b_loc, max(1, self.minibatch_size // n_shards))
        # recurrent: shuffle/gather whole T-row sequences, never rows
        T_seq = self._unroll_T
        if T_seq > 1:
            if b_loc % T_seq:
                raise ValueError(
                    f"per-shard batch {b_loc} not a multiple of "
                    f"max_seq_len={T_seq}"
                )
            mb_loc = max(T_seq, (mb_loc // T_seq) * T_seq)
        num_mb = max(1, b_loc // mb_loc)
        num_iters = self.num_sgd_iter
        tx = self._tx
        mesh = self.mesh
        axis = sharding_lib.BATCH_AXIS
        loss_fn = self.loss_with_aux

        rebuild_obs = self._rebuild_obs_from_frames

        def device_fn(params, opt_state, aux, batch, rng, coeffs):
            if with_frames:
                # rebuild stacked observations from the replicated
                # frame pool (ops/framestack): one gather, then the
                # nest proceeds on ordinary row columns (policies with
                # non-flat obs layouts override the hook)
                frames = aux["__frames__"]
                aux = {
                    k: v for k, v in aux.items() if k != "__frames__"
                }
                batch = rebuild_obs(frames, batch, stack_k)
            # Different shuffle stream per data shard.
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))

            # one minibatch of every row (decided from shapes, for any
            # num_sgd_iter): the nest takes the batch as it lies. A
            # mean over rows does not depend on their order, so a
            # permutation has nothing to select; a gathered copy would
            # move every pixel row three more times (pack, gather,
            # unpack) and double a sequence model's stored states
            whole_batch = num_mb == 1 and mb_loc == b_loc
            telemetry_metrics.inc_learn_minibatch_lowering(
                "whole" if whole_batch else "gathered"
            )
            # a strict subset of rows is gathered by index: uint8 row
            # columns (pixel obs) gather 3-4x faster viewed as uint32
            # lanes (measured: 127 -> 420 GB/s effective on v5e —
            # narrow-element gathers are element-width-bound), so pack
            # them once per nest and unpack per minibatch
            packed_shapes = {}
            batch = dict(batch)
            if not whole_batch:
                for k, v in list(batch.items()):
                    if (
                        v.dtype == jnp.uint8
                        and v.ndim >= 2
                        and int(np.prod(v.shape[1:])) % 4 == 0
                    ):
                        packed_shapes[k] = v.shape
                        batch[k] = jax.lax.bitcast_convert_type(
                            v.reshape(v.shape[0], -1, 4), jnp.uint32
                        )

            # a model whose saved activations of a whole minibatch do
            # not fit asks for groups of unrolls, each taken through
            # forward, loss and backward on its own (models/sequence_lm)
            ask = getattr(self.model, "loss_groups", None)
            loss_groups = ask(mb_loc // T_seq) if ask else None

            def _unpack(k, v):
                shp = packed_shapes.get(k)
                if shp is None:
                    return v
                u8 = jax.lax.bitcast_convert_type(v, jnp.uint8)
                return u8.reshape((v.shape[0],) + shp[1:])

            def mb_step(carry, mb_rng_idx):
                params, opt_state = carry
                idx, mb_rng, is_last = mb_rng_idx
                if whole_batch:
                    mb = batch
                else:
                    # __chunk__ columns hold one row per T-row unroll
                    # (chunk-start recurrent states); gather them by
                    # the unroll indices the row permutation selected
                    with jax.named_scope("learn/minibatch"):
                        mb = {
                            k: _unpack(
                                k,
                                (
                                    v[idx.reshape(-1, T_seq)[:, 0] // T_seq]
                                    if k.startswith("__chunk__")
                                    else v[idx]
                                ),
                            )
                            for k, v in batch.items()
                        }
                # differentiate a per-shard view of the replicated
                # params so the gradients stay per-shard and the pmean
                # below is the one real cross-shard reduction
                # (sharding/specs.py "varying-axes typing")
                with jax.named_scope("learn/loss_grad"):
                    if loss_groups is None:
                        (loss, stats), grads = jax.value_and_grad(
                            loss_fn, has_aux=True
                        )(
                            sharding_lib.varying(params, axis),
                            aux, mb, mb_rng, coeffs,
                        )
                    else:
                        (loss, stats), grads = _grouped_loss_grad(
                            loss_fn, loss_groups,
                            self.model.reduce_group_stats,
                            sharding_lib.varying(params, axis),
                            aux, mb, mb_rng, coeffs, axis,
                        )
                with jax.named_scope("learn/allreduce"):
                    grads = jax.lax.pmean(grads, axis)
                with jax.named_scope("learn/optimizer"):
                    updates, opt_state = tx.update(
                        grads, opt_state, params
                    )
                    lr = coeffs["lr"]
                    updates = jax.tree_util.tree_map(
                        lambda u: -lr * u.astype(jnp.float32), updates
                    )
                    params = optax.apply_updates(params, updates)
                # grad_gnorm: FINAL minibatch only. The 12-leaf
                # reduce+sqrt chain measures ~2x the model's own
                # fwd+bwd per step on this backend (profile_nest2),
                # so running it every step nearly halves nest MFU;
                # the reference's torch learner likewise reports the
                # last batch's extra_grad_info per update.
                with jax.named_scope("learn/grad_norm"):
                    gnorm = jax.lax.cond(
                        is_last,
                        lambda: _global_norm(grads),
                        lambda: jnp.float32(0.0),
                    )
                stats = dict(stats, total_loss=loss, grad_gnorm=gnorm)
                return (params, opt_state), stats

            def shuffled_rows(perm_rng):
                """``(num_mb, mb_loc)`` row indices of an epoch's
                minibatches (a recurrent policy's T-row sequences move
                whole)."""
                if T_seq > 1:
                    seq_perm = jax.random.permutation(
                        perm_rng, b_loc // T_seq
                    )
                    perm = (
                        seq_perm[:, None] * T_seq
                        + jnp.arange(T_seq)[None, :]
                    ).reshape(-1)
                else:
                    perm = jax.random.permutation(perm_rng, b_loc)
                return perm[: num_mb * mb_loc].reshape(num_mb, mb_loc)

            def epoch(carry, rng_e_i):
                rng_e, ep_i = rng_e_i
                # one key stream for both forms: a loss that draws
                # noise gets the same mb_rngs whether or not a
                # permutation is drawn from perm_rng
                perm_rng, scan_rng = jax.random.split(rng_e)
                idx = None if whole_batch else shuffled_rows(perm_rng)
                mb_rngs = jax.random.split(scan_rng, num_mb)
                is_last = (ep_i == num_iters - 1) & (
                    jnp.arange(num_mb) == num_mb - 1
                )
                carry, stats = jax.lax.scan(
                    mb_step, carry, (idx, mb_rngs, is_last)
                )
                return carry, stats

            rngs = jax.random.split(rng, num_iters)
            with jax.named_scope("sgd_nest"):
                (params, opt_state), stats = jax.lax.scan(
                    epoch,
                    (params, opt_state),
                    (rngs, jnp.arange(num_iters)),
                )

            # mean over epochs × minibatches, then over shards —
            # except grad_gnorm, which only the final step computed
            # (every other entry is 0, so the sum IS that value)
            def reduce_stat(name, x):
                agg = x.sum() if name == "grad_gnorm" else x.mean()
                return jax.lax.pmean(agg, axis)

            stats = {
                k: reduce_stat(k, v) for k, v in stats.items()
            }
            return params, opt_state, stats

        return device_fn

    def _build_learn_fn(self, batch_size: int, with_frames: bool = False):
        """Compile the full SGD nest for a given total batch size."""
        device_fn = self._nest_device_fn(
            batch_size, with_frames=with_frames
        )
        mesh = self.mesh
        axis = sharding_lib.BATCH_AXIS
        # per-leaf carry specs: bare P() (replicated) on the legacy
        # path, the rule-derived trees when partitioned — the body
        # then sees LOCAL param slices and the model inserts its own
        # model-axis collectives (models/transformer.py)
        p_ps, o_ps, a_ps = self._carry_pspecs(with_frames=with_frames)
        bp, bo, ba = sharding_lib.manual_pspecs(mesh, (p_ps, o_ps, a_ps))
        sharded = jax.shard_map(
            device_fn,
            mesh=mesh,
            in_specs=(bp, bo, ba, P(axis), P(), P()),
            out_specs=(bp, bo, P()),
        )
        # Donate only opt_state: params buffers must stay valid because an
        # async sampler thread may be running compute_actions with them
        # concurrently (IMPALA sync mode shares the policy object).
        p_sh, o_sh, a_sh, rep = self._carry_shardings(a_ps)
        return sharding_lib.sharded_jit(
            sharded,
            in_specs=(p_sh, o_sh, a_sh, self._data_sharding, rep, rep),
            out_specs=(p_sh, o_sh, rep),
            donate_argnums=(1,),
            label=f"learn[{type(self).__name__}:{batch_size}]",
        )

    # -- superstep: K updates per dispatch (docs/data_plane.md) ----------

    # Policies whose update body can't ride the generic scan (sequence
    # replay with per-chunk state handling) set this True to opt out
    # even when they kept the base learn program.
    _superstep_opt_out = False

    @property
    def supports_superstep(self) -> bool:
        """Whether K updates of this policy may fuse into one
        ``lax.scan`` dispatch (:meth:`learn_superstep`). True only when
        the subclass kept the base learn-program composition — the
        superstep scan is built from :meth:`_device_update_fn`, so a
        policy that replaced :meth:`_build_learn_fn` wholesale
        (AlphaZero, QMIX, MADDPG, SlateQ) must chain per-call. The
        actor-critic families override this with their own identity
        checks."""
        return (
            not self._superstep_opt_out
            and type(self)._build_learn_fn is JaxPolicy._build_learn_fn
            and type(self)._nest_device_fn is JaxPolicy._nest_device_fn
            and type(self)._device_update_fn
            is JaxPolicy._device_update_fn
        )

    def _device_update_fn(self, batch_size=None, with_frames=False):
        """Uniform single-update device body for the superstep scan:
        ``(params, opt_state, aux, batch, rng, coeffs) -> (params,
        opt_state, aux, stats)``. The base policy wraps the per-batch
        SGD nest (aux — target nets etc. — passes through unchanged);
        actor-critic policies (SAC/DDPG) override with bodies that
        thread their aux through the update."""
        nest = self._nest_device_fn(
            int(batch_size), with_frames=with_frames
        )

        def update_fn(params, opt_state, aux, batch, rng, coeffs):
            if with_frames:
                # per-update frame pool rides the batch tree (the
                # per-call path ships it via aux; inside a scan each
                # slot has its own pool)
                batch = dict(batch)
                frames = batch.pop(_FRAMES)
                params, opt_state, stats = nest(
                    params,
                    opt_state,
                    {"__frames__": frames, **aux},
                    batch,
                    rng,
                    coeffs,
                )
            else:
                params, opt_state, stats = nest(
                    params, opt_state, aux, batch, rng, coeffs
                )
            return params, opt_state, aux, stats

        return update_fn

    def _wrap_update_program(self, update_fn, batch_size: int):
        """shard_map + sharded_jit wrap of a 4-output single-update
        body — the one per-call learn-program shape the actor-critic
        family (SAC/DDPG/CQL/CRR) shares."""
        mesh = self.mesh
        axis = sharding_lib.BATCH_AXIS
        p_ps, o_ps, a_ps = self._carry_pspecs()
        bp, bo, ba = sharding_lib.manual_pspecs(mesh, (p_ps, o_ps, a_ps))
        sharded = jax.shard_map(
            update_fn,
            mesh=mesh,
            in_specs=(bp, bo, ba, P(axis), P(), P()),
            out_specs=(bp, bo, ba, P()),
        )
        p_sh, o_sh, a_sh, rep = self._carry_shardings(a_ps)
        return sharding_lib.sharded_jit(
            sharded,
            in_specs=(p_sh, o_sh, a_sh, self._data_sharding, rep, rep),
            out_specs=(p_sh, o_sh, a_sh, rep),
            donate_argnums=(1,),
            label=f"learn[{type(self).__name__}:{batch_size}]",
        )

    def _learn_coeffs(self):
        """Host coefficients the learn program consumes this call —
        what the per-update path passes, so the superstep matches it.
        Frozen across a superstep's K updates (staleness contract:
        docs/data_plane.md)."""
        self._update_scheduled_coeffs()
        return self._coeff_array()

    def _updates_per_learn_call(self, batch_size: int) -> int:
        """num_grad_updates increment of ONE learn call (the base nest
        runs num_sgd_iter × minibatches; actor-critic bodies one)."""
        return self.num_sgd_iter * max(
            1, batch_size // max(1, self.minibatch_size)
        )

    # Whether the per-update PER priority refresh consumes a host rng
    # split (SAC/DDPG: always; DQN: only under NoisyNet) — the
    # superstep must replay the exact split order of the per-update
    # path for bit parity.
    @property
    def _td_refresh_uses_rng(self) -> bool:
        return False

    def _td_error_device_fn(self):
        """Per-sample TD-error device body ``(params, aux, batch, rng)
        -> (B,)`` for the in-scan prioritized-replay refresh; None for
        policies without per-sample errors (the caller falls back to
        the batch-mean scalar, like ``DQN._single_update``)."""
        return None

    def _after_superstep(self) -> None:
        """Hook: host-side cache invalidation after a fused chain
        moved the params (SAC drops its device-flattened actor
        snapshots here)."""

    # ray-tpu: hot-path
    def _active_mask(self, k: int, k_max: int) -> np.ndarray:
        """The (k_max,) float32 active mask for a k-of-k_max superstep,
        cached per (k, k_max): the mask is read-only on the device side
        so the same host array serves every dispatch."""
        masks = self.__dict__.setdefault("_active_masks", {})
        m = masks.get((k, k_max))
        if m is None:
            m = np.zeros(k_max, np.float32)
            m[:k] = 1.0
            masks[(k, k_max)] = m
        return m

    # ray-tpu: hot-path
    def _split_chain(self, family, slot, k=None, k_max=None):
        """A host key schedule as ONE program: advance ``self._rng``
        through a slot's splits, ``k`` slots in a row, and return one
        key stack per stream. ``slot`` lists the streams in split
        order, each by its number of sequential
        ``rng, r = jax.random.split(rng)``: ``None`` is one split and
        yields a key, ``n > 0`` is n splits (a ``lax.scan`` of the
        same split) and yields an ``(n, 2)`` stack, ``0`` takes no
        split and yields a zero key. With ``k`` the stacks gain a
        leading slot axis padded with zero keys to ``k_max``.

        threefry splitting is an integer function of the key, so the
        chain composed inside one jitted function gives the stacks and
        the advanced stream of the sequential host loop bit for bit
        (docs/data_plane.md "rng split order"); only the dispatch
        count changes. Every lane's schedule is a signature of this
        one cache; the program is ``<family>[<slots>x<splits>...]`` in
        ``compile_stats()``, the device ledger and a profiler trace."""
        fns = self.__dict__.setdefault("_split_chain_fns", {})
        sig = (family, slot, k, k_max)
        fn = fns.get(sig)
        if fn is None:

            def one_split(rng, _):
                rng, r = jax.random.split(rng)
                return rng, r

            def chain(rng):
                streams = [[] for _ in slot]
                for _ in range(1 if k is None else k):
                    for keys, n in zip(streams, slot):
                        if n is None:
                            rng, r = jax.random.split(rng)
                        elif n:
                            rng, r = jax.lax.scan(
                                one_split, rng, None, length=n
                            )
                        else:
                            r = jnp.zeros_like(rng)
                        keys.append(r)
                if k is None:
                    return (rng, *(keys[0] for keys in streams))
                return (rng, *(
                    jnp.stack(
                        keys + [jnp.zeros_like(keys[0])] * (k_max - k)
                    )
                    for keys in streams
                ))

            dims = [] if k is None else [f"{k}of{k_max}"]
            dims += [str(1 if n is None else n) for n in slot]
            fn = sharding_lib.sharded_jit(
                chain, label=f"{family}[{'x'.join(dims)}]"
            )
            fns[sig] = fn
        self._rng, *stacks = fn(self._rng)
        return stacks

    # ray-tpu: hot-path
    def _superstep_host_keys(self, k, k_max, refresh, td_rng):
        """The superstep's key schedule: per update the learn split,
        then (under ``refresh``) the priority pass's key, a split of
        its own where that pass consumes one (``td_rng``) and a zero
        key where it does not. Returns the ``(k_max, 2)`` learn stack
        and the priority stack (``None`` without ``refresh``)."""
        slot = (None, None if td_rng else 0) if refresh else (None,)
        stacks = self._split_chain("learn_keys", slot, k, k_max)
        return stacks[0], (stacks[1] if refresh else None)

    # ray-tpu: hot-path
    def _rollout_host_keys(self, k, k_max, T):
        """The rollout superstep's key schedule: per slot, T rollout
        splits then the learn split, k*(T+1) sequential splits.
        Returns the ``(k_max, 2)`` learn stack and the
        ``(k_max, T, 2)`` rollout stack."""
        ro_rngs, rngs = self._split_chain(
            "rollout_learn_keys", (T, None), k, k_max
        )
        return rngs, ro_rngs

    # ray-tpu: hot-path
    def _rollout_keys(self, T):
        """The standalone rollout's key schedule: T rollout splits
        (the actor lane's one split per env step) and no learn split.
        Returns the ``(T, 2)`` stack."""
        return self._split_chain("rollout_keys", (T,))[0]

    # ray-tpu: hot-path
    def _drive_superstep(
        self,
        k,
        k_max,
        batch_size,
        *,
        family,
        cache_key,
        program,
        key_schedule,
        feed,
        carried=False,
        drained=None,
        **span_attrs,
    ):
        """The host side of a fused lane, once for all of them: check
        ``k`` against ``k_max``, fetch or build the program, span
        ``learn:keys`` around the coefficients and the key schedule,
        ONE dispatch under ``learn:superstep``, ONE readback under
        ``learn:drain``, then the host's share of the chain (counters,
        timers, the per-update stat dicts).

        A lane hands over what is its own: ``family`` and ``cache_key``
        name its program, ``program()`` gives the
        ``build_superstep_fn`` arguments of its feed (called on a cache
        miss), ``key_schedule(k, k_max)`` its key stacks in argument
        order, ``feed(keys, active)`` the feed argument after counting
        what of it crosses H2D; ``carried`` says that the first output
        after the learner state stays on the device (the env carry);
        ``drained(span, *extra)`` finishes what rode the drain beside
        the stats, inside the drain's span. ``span_attrs`` go on
        ``learn:keys`` and ``learn:superstep``.

        Returns ``(infos, skipped, carry, extra)``: per-update host
        stat dicts and nan-guard skip flags, the carried output (None
        without), and the list of drained outputs after the stats."""
        import time as _time

        from ray_tpu.sharding import superstep as superstep_lib

        k = int(k)
        k_max = int(k_max or k)
        if not 1 <= k <= k_max:
            raise ValueError(f"k={k} outside [1, k_max={k_max}]")
        nan_guard = bool(self.config.get("nan_guard"))
        cache_key = (*cache_key, batch_size, k_max, nan_guard)
        fns = self.__dict__.setdefault("_superstep_fns", {})
        fn = fns.get(cache_key)
        if fn is None:
            fn = fns[cache_key] = superstep_lib.build_superstep_fn(
                mesh=self.mesh,
                k=k_max,
                label=(
                    f"{family}[{type(self).__name__}:"
                    f"{batch_size}x{k_max}]"
                ),
                nan_guard=nan_guard,
                # per-leaf (params, opt, aux) placement threads
                # through the scan carry + donation unchanged
                carry_pspecs=(
                    self._carry_pspecs()
                    if self._param_pspecs is not None
                    else None
                ),
                **program(),
            )

        with tracing.start_span("learn:keys", k=k, **span_attrs):
            coeffs = self._learn_coeffs()
            # the exact per-update host split order, as ONE program:
            # the same threefry splits in the same order, so the key
            # stacks and the advanced self._rng are those of the
            # sequential host loop bit for bit (_split_chain)
            keys = key_schedule(k, k_max)
        active = self._active_mask(k, k_max)
        feed_arg = feed(keys, active)

        compiles_before = getattr(fn, "traces", 0)
        t0 = _time.perf_counter()
        with tracing.start_span(
            "learn:superstep", k=k, batch_size=batch_size, **span_attrs
        ) as _sp:
            self.params, self.opt_state, self.aux_state, *tail = fn(
                self.params,
                self.opt_state,
                self.aux_state,
                feed_arg,
                active,
                *keys,
                coeffs,
            )
            carry = tail.pop(0) if carried else None
            _sp.set_attribute(
                "recompiles",
                getattr(fn, "traces", 0) - compiles_before,
            )
            # ONE drain for the whole chain: the stacked stats tree and
            # whatever the lane stacked beside it (the PER priority
            # matrix, the episode metrics) come back in a single
            # device→host readback
            with tracing.start_span("learn:drain") as _drain:
                # ray-tpu: allow[RTA005] the ONE counted drain for the chain
                stats, *extra = jax.device_get(tail)
                if drained is not None:
                    extra = drained(_drain, *extra)
            # the drain proves the superstep program finished: close
            # its device-busy interval in the ledger (timestamps only,
            # no extra sync)
            device_ledger.drain_point()
            # the host's share of the chain, still inside the span: the
            # counters and the per-update stat dicts
            self.num_grad_updates += k * self._updates_per_learn_call(
                batch_size
            )
            self._after_superstep()
            telemetry_metrics.counter(
                telemetry_metrics.LEARN_STEPS_TOTAL,
                "SGD-nest programs dispatched",
            ).inc(float(k))
            telemetry_metrics.inc_superstep_updates(k)
            self.last_learn_timers["learn_superstep_s"] = (
                _time.perf_counter() - t0
            )
            self.last_learn_timers["learn_recompiles"] = float(
                getattr(fn, "traces", 0) - compiles_before
            )

            skip = np.asarray(
                stats.get(superstep_lib.SKIP_KEY, np.zeros(k_max))
            )
            skipped = [bool(skip[i] > 0.5) for i in range(k)]
            infos = [
                {
                    name: float(np.asarray(v)[i])
                    for name, v in stats.items()
                    if name != superstep_lib.SKIP_KEY
                }
                for i in range(k)
            ]
            return infos, skipped, carry, extra

    def learn_superstep(
        self,
        k: int,
        batch_size: int,
        *,
        stacked=None,
        rings=None,
        k_max: Optional[int] = None,
        refresh_priorities: bool = False,
    ):
        """Run ``k`` updates as ONE compiled program (the uniform
        superstep contract — docs/data_plane.md): one dispatch, one
        stats readback, weights never bounce through the host between
        updates. Bit-identical to ``k`` sequential
        ``learn_on_device_batch(..., defer_stats=True)`` calls on the
        same batches (same device body, same host rng-split order;
        host-side ``after_learn_on_batch`` reactions lag the chain —
        callers that need them apply them to the drained stats).

        Feed (exactly one):
          - ``stacked``: ``(k_max, B, ...)`` column tree — host numpy
            (one H2D for the whole superstep) or already-resident
            device arrays (PPO's prefetched batches, zero H2D).
          - ``rings``: a :class:`~ray_tpu.execution.replay_buffer
            .DeviceReplayBuffer` feed (``buf.superstep_feed(idx,
            extra)``) — the scan gathers each update's rows from the
            device rings in place; only the ``(k_max, B)`` index array
            (and PER weights) cross the wire.

        ``k_max`` fixes the compiled scan length; any ``k <= k_max``
        runs through the same executable via the active mask (no
        per-K recompile — ``compile_stats()``-asserted in tests).
        ``refresh_priorities`` runs the per-sample TD-error body after
        each update (post-update state, per-update order) and returns
        the stacked ``|td|`` matrix in one D2H.

        Returns ``(infos, priorities, skipped)``: per-update host stat
        dicts (update order), the ``(k, B)`` priority matrix (None
        unless refreshing), and the per-update nan-guard skip flags.
        """
        if (stacked is None) == (rings is None):
            raise ValueError(
                "learn_superstep needs exactly one of stacked/rings"
            )
        pri_fn = (
            self._td_error_device_fn() if refresh_priorities else None
        )
        if refresh_priorities and pri_fn is None:
            raise ValueError(
                f"{type(self).__name__} has no per-sample TD-error "
                "body; gate refresh_priorities on "
                "policy._td_error_device_fn() is not None"
            )
        # the priority pass's key is a split of its own iff the
        # per-update pass consumes one, a zero key otherwise
        td_rng = refresh_priorities and self._td_refresh_uses_rng

        if rings is not None:
            extra_cols = tuple(sorted(rings.extra))
            cache_mode = ("rings", rings.key, extra_cols)

            def program():
                return dict(
                    update_fn=self._device_update_fn(batch_size),
                    rings=rings,
                    extra_cols=extra_cols,
                    priority_fn=pri_fn,
                )

            def feed(keys, active):
                # sample-path payload: the pre-drawn index matrix +
                # stacked extra columns, counted only when they
                # actually cross H2D — a device-tree draw hands device
                # arrays here and the sample path ships zero payload
                # bytes
                telemetry_metrics.add_h2d_bytes(
                    "replay_sample",
                    sum(
                        v.nbytes
                        for v in (rings.idx, *rings.extra.values())
                        if not isinstance(v, jax.Array)
                    ),
                )
                return rings.store, rings.idx, rings.extra

        else:
            cols = tuple(sorted(stacked))
            cache_mode = ("stacked", cols)
            with_frames = _FRAMES in stacked

            def program():
                return dict(
                    update_fn=self._device_update_fn(
                        batch_size, with_frames=with_frames
                    ),
                    stacked_cols=cols,
                    replicated_cols=(_FRAMES,) if with_frames else (),
                    priority_fn=pri_fn,
                )

            def feed(keys, active):
                if not any(
                    isinstance(v, jax.Array) for v in stacked.values()
                ):
                    telemetry_metrics.add_h2d_bytes(
                        "learn", sharding_lib.tree_nbytes(stacked)
                    )
                return stacked

        def key_schedule(k, k_max):
            rngs, pri_rngs = self._superstep_host_keys(
                k, k_max, refresh_priorities, td_rng
            )
            return (rngs, pri_rngs) if refresh_priorities else (rngs,)

        def drained(span, pri):
            pri = np.abs(np.asarray(pri)[: int(k)])
            # the |td| pull that feeds the host alpha-power — the PER
            # path's one remaining D2H (docs/data_plane.md)
            telemetry_metrics.add_d2h_bytes(
                "replay_priorities", pri.nbytes
            )
            span.set_attribute("bytes", pri.nbytes)
            return [pri]

        infos, skipped, _, extra = self._drive_superstep(
            k,
            k_max,
            batch_size,
            family="superstep",
            cache_key=(cache_mode, refresh_priorities),
            program=program,
            key_schedule=key_schedule,
            feed=feed,
            drained=drained if refresh_priorities else None,
        )
        return infos, (extra[0] if refresh_priorities else None), skipped

    # ray-tpu: hot-path
    def learn_rollout_superstep(
        self,
        k: int,
        batch_size: int,
        rollout,
        *,
        k_max: Optional[int] = None,
    ):
        """Fused rollout+learn superstep (docs/data_plane.md): ``k``
        iterations of [roll out T env steps on the mesh → postprocess
        → one SGD-nest update] as ONE compiled program — the device
        rollout lane's hot path. The only H2D payload is the key
        stacks and the active mask; rollout rows never exist on the
        host.

        ``rollout`` is the engine's feed descriptor
        (``execution/jax_rollout.RolloutSuperstepFeed``): ``carry``
        the device-resident env carry, ``body`` the per-shard rollout
        function the scan slot calls, ``steps`` the env steps per
        slot, ``key`` the compile-cache key.

        Host rng split order per slot — ``steps`` rollout splits, then
        the learn split — matches the actor lane's local-worker
        stream (one ``compute_actions`` split per env step, then
        ``learn_on_batch``'s), the fixed-seed parity contract.

        Returns ``(infos, carry, metrics, skipped)``: per-update host
        stat dicts, the advanced env carry (feed it back next call),
        the stacked per-slot metrics tree (host numpy), and per-update
        nan-guard skip flags.
        """
        T = int(rollout.steps)

        def program():
            return dict(
                update_fn=self._device_update_fn(batch_size),
                rollout_fn=rollout.body,
                # a model with per-stream state: the params and the
                # carry (a cache per env) are handed over, not copied
                donate_rollout_state=bool(
                    getattr(getattr(self, "model", None), "is_recurrent", False)
                ),
            )

        def feed(keys, active):
            # the lane's entire H2D payload: key stacks + the mask
            telemetry_metrics.add_h2d_bytes(
                "rollout",
                sum(int(r.nbytes) for r in keys) + active.nbytes,
            )
            return rollout.carry

        infos, skipped, carry, (metrics,) = self._drive_superstep(
            k,
            k_max,
            batch_size,
            family="rollout_superstep",
            cache_key=("rollout", rollout.key),
            program=program,
            # T rollout splits then the learn split per slot
            key_schedule=lambda k, k_max: self._rollout_host_keys(
                k, k_max, T
            ),
            feed=feed,
            carried=True,
            rollout=True,
        )
        telemetry_metrics.note_expert_load(infos)
        telemetry_metrics.note_index_selection(infos)
        telemetry_metrics.note_diffusion_passes(infos)
        return infos, carry, metrics, skipped

    def prepare_batch(self, samples) -> Tuple[Dict[str, np.ndarray], int]:
        """Public phase 1 of learning: turn a SampleBatch (or plain dict of
        arrays) into the host tree the compiled learn program consumes.

        Enforces static-shape discipline — the leading dim must be a
        multiple of the data shards; trims when possible, tiles tiny
        batches up. Returns ``(host_tree, batch_size)``; the tree is ready
        for ``jax.device_put`` onto ``self.data_sharding`` (directly or via
        a :class:`~ray_tpu.execution.device_feed.DeviceFeeder`)."""
        if isinstance(samples, SampleBatch) or not isinstance(
            samples, dict
        ):
            batch = self._batch_to_train_tree(samples)
        else:  # plain dict of arrays (benchmarks, tests)
            batch = {
                k: np.asarray(v)
                for k, v in samples.items()
                if isinstance(v, np.ndarray) and v.dtype != object
            }
        # the deduplicated frame pool is NOT a row column: it is
        # exempt from row trimming/tiling (trimmed idx rows keep
        # pointing at valid pool entries)
        frames = batch.pop(_FRAMES, None)
        bsize = int(next(iter(batch.values())).shape[0])
        # recurrent batches must also divide into whole T-row unrolls
        div = self.n_shards * self._unroll_T
        if bsize < div:
            reps = -(-div // bsize)
            orig = bsize
            batch = {
                k: np.tile(v, (reps,) + (1,) * (v.ndim - 1))[:div]
                for k, v in batch.items()
            }
            if "resets" in batch:
                # tile wrap points can land mid-unroll; the carry from
                # the end of one copy must not leak into the next
                # (stored chunk-start states only cover unroll row 0)
                resets = batch["resets"].copy()
                resets[orig::orig] = 1.0
                batch["resets"] = resets
            bsize = div
        else:
            trim = (bsize // div) * div
            if trim != bsize:
                batch = {k: v[:trim] for k, v in batch.items()}
                bsize = trim
        if self._unroll_T > 1 and "state_in_0" in batch:
            # stored-state mode ships ONE state per unroll, not per
            # row: only chunk-start states are ever read (the [:, 0]
            # in model_forward_train), so slicing here cuts the
            # host→device state transfer by T. Sliced AFTER trim/tile
            # so tiled layouts keep the state of each final chunk
            # start.
            T = self._unroll_T
            k = 0
            while f"state_in_{k}" in batch:
                batch[f"__chunk__state_in_{k}"] = batch.pop(
                    f"state_in_{k}"
                )[::T]
                k += 1
        if frames is not None:
            batch[_FRAMES] = frames
        return batch, bsize

    @property
    def data_sharding(self):
        """Sharding for train-batch leading-dim placement (public, for
        DeviceFeeder wiring)."""
        return self._data_sharding

    def batch_shardings(self, host_tree):
        """Per-column placement for a prepared train batch: row columns
        shard over the data axis; the deduplicated frame pool
        (``obs_frames``) replicates so every shard can gather stacks
        locally. Pass this method itself as a DeviceFeeder's
        ``sharding`` to get per-batch resolution. Columns whose
        leading dim doesn't divide the shard count (only possible for
        trees that bypassed ``prepare_batch``) fall back to
        replication instead of erroring (specs.leaf_sharding)."""
        if isinstance(host_tree, dict):
            return sharding_lib.sharding_tree(
                host_tree, self.mesh, replicate_keys=(_FRAMES,)
            )
        return self._data_sharding

    def learn_fn(self, batch_size: int, *, with_frames: bool = False):
        """Public accessor for the compiled SGD-nest program at a given
        (post-``prepare_batch``) batch size. Signature of the returned
        function is stable:

            ``fn(params, opt_state, aux_state, batch, rng, coeffs)
            -> (params, opt_state, stats)``

        Benchmarks and learner threads must obtain the program here (or
        use :meth:`learn_on_device_batch`) rather than via private
        attributes, so internal refactors can't silently break them.
        ``with_frames=True`` compiles the variant whose observations
        arrive as a deduplicated frame pool in ``aux['__frames__']``
        plus an ``obs_frame_idx`` row column (``ops/framestack``)."""
        key = (batch_size, with_frames)
        fn = self._learn_fns.get(key)
        if fn is None:
            # bespoke-net policies (SAC family) override
            # _build_learn_fn without the frames variant
            fn = (
                self._build_learn_fn(batch_size, with_frames=True)
                if with_frames
                else self._build_learn_fn(batch_size)
            )
            self._learn_fns[key] = fn
        return fn

    def learn_on_device_batch(
        self, dev_batch: Dict[str, Any], batch_size: int,
        *, defer_stats: bool = False,
    ) -> Dict[str, Any]:
        """Public phase 2 of learning: run the compiled SGD nest on an
        already-device-resident batch (e.g. transferred ahead of time by a
        DeviceFeeder so host→device copy overlapped the previous step).

        Batches in the deduplicated framestack format (``obs_frames``
        frame pool + ``obs_frame_idx`` rows — see ``ops/framestack``)
        rebuild their observations device-side: the pool rides the
        replicated aux slot (its sharding), so stacks gather locally on
        every data shard.

        ``defer_stats=True`` skips the blocking ``device_get`` of the
        stats tree and returns it as device arrays instead: dispatch
        returns as soon as XLA enqueues the program, so consecutive
        learner steps pipeline on-device and the per-dispatch host
        latency amortizes across the queue. The caller materializes stats later with
        ``jax.device_get`` — by then the program has long finished and
        the fetch is cheap. Deferring also skips
        ``after_learn_on_batch`` (host-side coefficient updates need
        host stats), so only defer for policies that don't override it."""
        import time as _time

        aux = self.aux_state
        if _FRAMES in dev_batch:
            dev_batch = dict(dev_batch)
            frames = jax.device_put(
                dev_batch.pop(_FRAMES), self._param_sharding
            )
            aux = {"__frames__": frames, **aux}
            fn = self.learn_fn(batch_size, with_frames=True)
        else:
            fn = self.learn_fn(batch_size)
        self._update_scheduled_coeffs()
        self._rng, rng = jax.random.split(self._rng)
        coeffs = self._coeff_array()
        compiles_before = getattr(fn, "traces", 0)
        compile_s_before = getattr(fn, "compile_time_s", 0.0)
        t0 = _time.perf_counter()
        with tracing.start_span(
            "learn:nest", batch_size=batch_size
        ) as _sp:
            self.params, self.opt_state, stats = fn(
                self.params,
                self.opt_state,
                aux,
                dev_batch,
                rng,
                coeffs,
            )
            self.num_grad_updates += self.num_sgd_iter * max(
                1, batch_size // max(1, self.minibatch_size)
            )
            _sp.set_attribute("deferred", bool(defer_stats))
            _sp.set_attribute(
                "recompiles",
                getattr(fn, "traces", 0) - compiles_before,
            )
            telemetry_metrics.counter(
                telemetry_metrics.LEARN_STEPS_TOTAL,
                "SGD-nest programs dispatched",
            ).inc()
            if defer_stats:
                return stats
            if self.config.get("deferred_stats"):
                # flag-gated one-call lag (docs/data_plane.md): hand
                # back the PREVIOUS nest's stats — that program has
                # long finished, so the fetch doesn't serialize on
                # THIS dispatch and the per-call device round trip
                # overlaps compute. The very first call has nothing
                # lagged and returns only cur_lr.
                prev = self.__dict__.get("_lagged_stats")
                self.__dict__["_lagged_stats"] = stats
                stats = (
                    jax.device_get(prev) if prev is not None else None
                )
            else:
                # One device→host transfer for all stats (individual
                # float() conversions each pay a full device round
                # trip).
                stats = jax.device_get(stats)
                # stats landed → the nest finished; close its ledger
                # interval at this (the one counted) drain
                device_ledger.drain_point()
        # per-stage timers: a call that traced pays compile; the rest
        # of this call's wall time is the step (device compute + stats
        # fetch). Exposed both as metrics series (utils.metrics) and on
        # the policy for train()-result reporting.
        total_s = _time.perf_counter() - t0
        compile_s = (
            getattr(fn, "compile_time_s", 0.0) - compile_s_before
        )
        self.last_learn_timers["learn_compile_s"] = compile_s
        self.last_learn_timers["learn_step_s"] = max(
            0.0, total_s - compile_s
        )
        self.last_learn_timers["learn_recompiles"] = float(
            getattr(fn, "traces", 0) - compiles_before
        )
        timer_histogram("ray_tpu_learner_step_seconds").observe(
            self.last_learn_timers["learn_step_s"]
        )
        if compile_s:
            timer_histogram(
                "ray_tpu_learner_compile_seconds"
            ).observe(compile_s)
        if stats is None:  # deferred first call: nothing lagged yet
            return {"cur_lr": self.coeff_values["lr"]}
        out = {k: float(v) for k, v in stats.items()}
        out.update(self.after_learn_on_batch(out))
        out["cur_lr"] = self.coeff_values["lr"]
        return out

    # ray-tpu: drain-ok
    def flush_deferred_stats(self) -> Dict[str, float]:
        """Fetch (and clear) the stats handle a ``deferred_stats``
        policy is still holding — call after the last learn step when
        the final update's numbers matter."""
        prev = self.__dict__.pop("_lagged_stats", None)
        if prev is None:
            return {}
        stats = jax.device_get(prev)
        # the lagged handle belongs to the most recent dispatch on
        # this thread — its arrival closes that ledger interval
        device_ledger.drain_point()
        return {k: float(v) for k, v in stats.items()}

    def learn_on_batch(self, samples: SampleBatch) -> Dict[str, Any]:
        """One full multi-epoch SGD update (reference
        TorchPolicy.learn_on_batch :467 + the whole train_ops stack).
        ``jax.device_put`` dispatch is asynchronous, so the transfer
        overlaps this host code until the program consumes the buffers."""
        import time as _time

        batch, bsize = self.prepare_batch(samples)
        # the frame pool is replicated, not row-sharded
        frames = batch.pop(_FRAMES, None)
        telemetry_metrics.add_h2d_bytes(
            "learn",
            sharding_lib.tree_nbytes(batch)
            + (frames.nbytes if frames is not None else 0),
        )
        t0 = _time.perf_counter()
        with tracing.start_span("learn:transfer", batch_size=bsize):
            dev = _tree_to_device(batch, self._data_sharding)
            if frames is not None:
                dev = dict(
                    dev,
                    **{
                        _FRAMES: jax.device_put(
                            frames, self._param_sharding
                        )
                    },
                )
            # block so the transfer timer is honest (the learn program
            # would wait on these buffers anyway; only the sliver of
            # host code between here and dispatch loses overlap — the
            # async path is the DeviceFeeder, which times its own
            # transfers)
            jax.block_until_ready(dev)
        transfer_s = _time.perf_counter() - t0
        self.last_learn_timers["learn_transfer_s"] = transfer_s
        timer_histogram(
            "ray_tpu_learner_transfer_seconds"
        ).observe(transfer_s)
        return self.learn_on_device_batch(dev, bsize)

    def after_learn_on_batch(self, stats: Dict[str, float]) -> Dict[str, float]:
        """Hook for host-side coefficient updates (e.g. PPO kl coeff)."""
        return {}

    def _rebuild_obs_from_frames(self, frames, batch, stack_k: int):
        """Device-side hook (runs inside the jitted learn program):
        turn the deduplicated frame pool + per-row first-frame indices
        back into the OBS column. Policies whose obs column is not a
        flat row layout (IMPALA's (B, T) unrolls) override this.
        ``build_stacks`` routes uint8 pools through the same uint32-lane
        gather trick as the per-minibatch row gather below (MFU.md
        "what would move it further" item 1)."""
        from ray_tpu.ops.framestack import build_stacks

        batch = dict(batch)
        batch[SampleBatch.OBS] = build_stacks(
            frames, batch.pop(_FRAME_IDX), stack_k
        )
        return batch

    # Losses that never read NEXT_OBS (the on-policy family) set this
    # False so the train tree doesn't ship a second full obs column to
    # the device — for pixel envs that halves learner ingest bytes.
    _ship_next_obs: bool = True

    def _td_input_tree(self, samples):
        """Batch tree for the per-sample TD-error programs: a
        device-resident replay sample is already the train tree (use
        it in place — no D2H round trip); host SampleBatches convert
        through ``_batch_to_train_tree``."""
        if getattr(samples, "is_device_resident", False):
            return samples.tree
        return self._batch_to_train_tree(samples)

    def replay_columns(self, samples: SampleBatch) -> Dict[str, np.ndarray]:
        """Host column tree a device-resident replay buffer stores for
        this policy (docs/data_plane.md): the learn program's
        train-tree columns — same key selection and dtype casts as
        ``learn_on_batch`` — WITHOUT the framestack transfer-format
        dedup. A replay buffer stores rows, and randomly sampled rows
        are not sliding windows; the pool format would be rejected by
        the ring anyway (unequal column lengths)."""
        missing = object()
        prev = self.config.get("dedup_framestack", missing)
        self.config["dedup_framestack"] = False
        try:
            return self._batch_to_train_tree(samples)
        finally:
            if prev is missing:
                self.config.pop("dedup_framestack", None)
            else:
                self.config["dedup_framestack"] = prev

    def compress_for_shipping(self, batch: SampleBatch) -> SampleBatch:
        """Worker-side, after postprocessing, right before a fragment
        ships to the driver: replace stacked framestack observations
        with the deduplicated pool + index columns
        (``ops/framestack.compress_fragment_obs``). A stacked pixel
        fragment moves ~2k single frames' worth of bytes per step
        through pickle → object ring → driver concat → H2D; the
        pool moves ~1. Applies only when the loss can train from the
        pool: on-policy flat rows (``_ship_next_obs`` False) or fixed
        unrolls (IMPALA family, which only needs the bootstrap stack —
        reconstructible at ``idx[-1]+1``). Offline output
        (``config["output"]``) keeps materialized stacks so written
        datasets stay self-describing."""
        if not self.config.get("compress_obs_shipping", True):
            return batch
        if self.config.get("output"):
            return batch
        fixed = bool(self.config.get("_fixed_unrolls"))
        if not fixed and self._ship_next_obs:
            # replay families read full NEXT_OBS — pool it too
            # (terminal stacks included) so the fragment still ships
            # ~k× smaller and the driver rebuilds both columns
            # byte-identically before replay insert
            return self._compress_replay_shipping(batch)
        model = getattr(self, "model", None)  # bespoke-net policies
        if model is None or model.is_recurrent:
            return batch
        obs = batch.get(SampleBatch.OBS)
        if (
            isinstance(obs, np.ndarray)
            and obs.ndim == 4
            and 2 <= obs.shape[-1] <= 8
            and SampleBatch.NEXT_OBS in batch
        ):
            from ray_tpu.ops.framestack import compress_fragment_obs

            dones = np.asarray(
                batch[SampleBatch.TERMINATEDS], bool
            ) | np.asarray(
                batch.get(
                    SampleBatch.TRUNCATEDS,
                    np.zeros(batch.count, bool),
                ),
                bool,
            )
            dec = compress_fragment_obs(
                obs, np.asarray(batch[SampleBatch.NEXT_OBS]), dones
            )
            if dec is not None:
                pool, idx = dec
                cols = {
                    k: v
                    for k, v in batch.items()
                    if k
                    not in (SampleBatch.OBS, SampleBatch.NEXT_OBS)
                }
                cols[_FRAMES] = pool
                cols[_FRAME_IDX] = idx
                return SampleBatch(cols)
        return batch

    def _compress_replay_shipping(self, batch: SampleBatch) -> SampleBatch:
        """Worker-side framestack dedup for the off-policy (replay)
        path: OBS and NEXT_OBS pool together via
        ``ops/framestack.compress_replay_obs`` — per-episode terminal
        stacks ride as pseudo-rows, so ``materialize_fragment`` on the
        driver rebuilds BOTH columns byte-identically (``obs[t] =
        stack(idx[t])``, ``next_obs[t] = stack(idx[t]+1)``) before
        rows enter the replay buffer."""
        model = getattr(self, "model", None)  # bespoke-net policies
        if model is None or model.is_recurrent:
            return batch
        obs = batch.get(SampleBatch.OBS)
        if not (
            isinstance(obs, np.ndarray)
            and obs.ndim == 4
            and 2 <= obs.shape[-1] <= 8
            and SampleBatch.NEXT_OBS in batch
        ):
            return batch
        from ray_tpu.ops.framestack import compress_replay_obs

        dones = np.asarray(
            batch[SampleBatch.TERMINATEDS], bool
        ) | np.asarray(
            batch.get(
                SampleBatch.TRUNCATEDS,
                np.zeros(batch.count, bool),
            ),
            bool,
        )
        dec = compress_replay_obs(
            obs, np.asarray(batch[SampleBatch.NEXT_OBS]), dones
        )
        if dec is None:
            return batch
        pool, idx = dec
        cols = {
            k: v
            for k, v in batch.items()
            if k not in (SampleBatch.OBS, SampleBatch.NEXT_OBS)
        }
        cols[_FRAMES] = pool
        cols[_FRAME_IDX] = idx
        return SampleBatch(cols)

    def _maybe_dedup_framestack(
        self, tree: Dict[str, np.ndarray]
    ) -> Dict[str, np.ndarray]:
        """Replace a stacked (N, H, W, k) OBS column with the
        deduplicated frame pool + index columns when rows really are
        sliding windows (ops/framestack) — ~k× fewer obs bytes over the
        host→device boundary. Segment boundaries (fragment starts,
        episode resets) come from the batch's bookkeeping columns; the
        decomposition verifies the sliding-window property and falls
        back to shipping stacks when it doesn't hold."""
        obs = tree.get(SampleBatch.OBS)
        if (
            obs is None
            or self.model.is_recurrent
            or not self.config.get("dedup_framestack", True)
            or obs.ndim != 4
            or not 2 <= obs.shape[-1] <= 8
            or obs.nbytes
            < self.config.get("dedup_framestack_min_bytes", 1 << 20)
        ):
            return tree
        from ray_tpu.ops.framestack import decompose_segmented_obs

        n = obs.shape[0]
        seg = np.zeros(n, bool)
        seg[0] = True
        for col in (
            SampleBatch.UNROLL_ID,
            SampleBatch.EPS_ID,
            SampleBatch.AGENT_INDEX,
        ):
            v = tree.get(col)
            if v is not None and len(v) == n:
                seg[1:] |= v[1:] != v[:-1]
        tcol = tree.get(SampleBatch.T)
        if tcol is not None and len(tcol) == n:
            seg[1:] |= tcol[1:] != tcol[:-1] + 1
        out = decompose_segmented_obs(obs, seg)
        if out is None:
            return tree
        stream, idx = out
        tree = dict(tree)
        del tree[SampleBatch.OBS]
        tree[_FRAMES] = stream
        tree[_FRAME_IDX] = idx
        return tree

    def _batch_to_train_tree(self, samples: SampleBatch) -> Dict[str, np.ndarray]:
        """Select training columns as a flat dict of arrays. For
        recurrent models, derive the per-row ``resets`` column the
        (B, T) unroll forward consumes: 1 wherever the trajectory is
        discontinuous (episode change, or a non-contiguous step counter
        marking a fragment boundary between different env slots)."""
        drop = {SampleBatch.INFOS, SampleBatch.SEQ_LENS}
        # carry-style recurrent models (LSTM) train from the sampler's
        # stored chunk-start states so the train-time forward matches
        # the rollout-time forward exactly for mid-episode chunks;
        # other models' per-row states are rollout-side plumbing (the
        # GAE bootstrap reads the last row host-side) and never ship
        # to device (R2D2 overrides this method and keeps the state
        # columns its sequence loss needs)
        stored_state = (
            self.model.is_recurrent
            and getattr(self.model, "supports_stored_train_state", False)
        )
        if not self._ship_next_obs:
            drop = drop | {SampleBatch.NEXT_OBS}
        tree = {
            k: np.asarray(v)
            for k, v in samples.items()
            if k not in drop
            and (stored_state or not k.startswith("state_in_"))
            and not k.startswith("state_out_")
            and isinstance(v, np.ndarray)
            and v.dtype != object
        }
        tree = self._maybe_dedup_framestack(tree)
        if self.model.is_recurrent and "resets" not in tree:
            n = len(next(iter(tree.values())))
            resets = np.zeros(n, np.float32)
            eps = tree.get(SampleBatch.EPS_ID)
            tcol = tree.get(SampleBatch.T)
            if not stored_state:
                # row 0 is always treated as a trajectory start (also
                # makes tiled copies in prepare_batch reset at each
                # wrap point); with stored state the chunk-start state
                # column is itself correct at row 0 and at every tiled
                # copy, so row 0 is a reset only when it genuinely
                # starts an episode (step counter 0)
                resets[0] = 1.0
            elif tcol is None or tcol[0] == 0:
                resets[0] = 1.0
            if eps is not None:
                resets[1:] = np.maximum(
                    resets[1:], (eps[1:] != eps[:-1]).astype(np.float32)
                )
            if tcol is not None:
                resets[1:] = np.maximum(
                    resets[1:],
                    (tcol[1:] != tcol[:-1] + 1).astype(np.float32),
                )
            tree["resets"] = resets
        return tree

    def model_forward_train(self, params, batch, stats_out=None):
        """Learn-path forward over a flat training batch. Feedforward
        models pass through; recurrent models reshape the N flat rows
        into (N/T, T) unrolls — chunk starts use the sampler's stored
        states when the model supports it (LSTM; exact rollout replay)
        and zero states otherwise (GTrXL; documented approximation in
        models/attention.py), with the ``resets`` column zeroing the
        carry at trajectory boundaries — and return flattened (N,)
        outputs, so losses written against flat rows work unchanged
        (the reference's rnn_sequencing role, fixed-shape style). A
        loss that hands in a ``stats_out`` dict gets back what a model
        with ``train_stats`` counts in its forward (expert load), to
        merge into its own stats."""
        obs = batch[SampleBatch.OBS]
        if not self.model.is_recurrent:
            return self.model.apply(params, obs)
        kwargs = {}
        if getattr(self.model, "tokens_per_step", 1) > 1:
            # a block a step: the model read no observation; its update
            # replays the committed tokens with the trace of their passes
            obs = batch[SampleBatch.ACTIONS]
            kwargs["trace"] = batch[SampleBatch.UNMASK_STEP]
        T = self._unroll_T
        N = obs.shape[0]
        if N % T:
            raise ValueError(
                f"recurrent train batch of {N} rows is not a multiple "
                f"of the unroll length max_seq_len={T}"
            )
        B = N // T
        resets = batch.get("resets")
        if resets is not None:
            kwargs["resets"] = resets.reshape(B, T)
        if getattr(self.model, "use_prev_action", False):
            pa = batch.get(SampleBatch.PREV_ACTIONS)
            if pa is not None:
                kwargs["prev_actions"] = pa.reshape(
                    (B, T) + pa.shape[1:]
                )
        if getattr(self.model, "use_prev_reward", False):
            pr = batch.get(SampleBatch.PREV_REWARDS)
            if pr is not None:
                kwargs["prev_rewards"] = pr.reshape(B, T)
        stored = getattr(self.model, "supports_stored_train_state", False)
        if stored and "__chunk__state_in_0" in batch:
            # prepare_batch already sliced to one state per unroll
            state0 = []
            k = 0
            while f"__chunk__state_in_{k}" in batch:
                state0.append(batch[f"__chunk__state_in_{k}"])
                k += 1
            state0 = tuple(state0)
        elif stored and "state_in_0" in batch:
            # per-row columns (compute_gradients path, which bypasses
            # prepare_batch): each unroll starts from the state the
            # sampler recorded at its first row (exact rollout replay
            # for mid-episode chunks; resets re-zero the carry at any
            # in-chunk episode boundary)
            state0 = []
            k = 0
            while f"state_in_{k}" in batch:
                s = batch[f"state_in_{k}"]
                state0.append(
                    s.reshape((B, T) + s.shape[1:])[:, 0]
                )
                k += 1
            state0 = tuple(state0)
        else:
            state0 = self._zero_initial_state(obs, B)
        if getattr(self.model, "train_stats", False):
            # the model names its scopes under learn/
            kwargs["scope"] = "learn"
            kwargs["stats_out"] = stats_out
        return self.model.apply(
            params, obs.reshape((B, T) + obs.shape[1:]), state0,
            **kwargs,
        )

    def _zero_initial_state(self, obs, B: int):
        """Zero recurrent state for B unrolls, derived from the batch
        (0 * anchor) so the scan carry is device-varying under
        shard_map — plain jnp.zeros is axis-unvarying and trips the
        lax.scan vma check inside the sharded learn program."""
        anchor = obs.reshape(B, -1)[:, 0].astype(jnp.float32)
        return tuple(
            s + 0.0 * anchor.reshape((B,) + (1,) * (s.ndim - 1))
            for s in self.model.initial_state(B)
        )

    # -- gradients API (A3C-style parity) --------------------------------

    def compute_gradients(self, samples: SampleBatch):
        if not hasattr(self, "_grad_fn"):
            loss_fn = self.loss_with_aux

            def gfn(params, aux, batch, rng, coeffs):
                (loss, stats), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(params, aux, batch, rng, coeffs)
                return grads, dict(stats, total_loss=loss)

            self._grad_fn = sharding_lib.sharded_jit(
                gfn, label=f"grads[{type(self).__name__}]"
            )
        batch = self._batch_to_train_tree(samples)
        if self._unroll_T > 1:
            # async-gradient batches bypass prepare_batch: trim to
            # whole unrolls so model_forward_train's reshape holds
            n = len(next(iter(batch.values())))
            trim = (n // self._unroll_T) * self._unroll_T
            if trim == 0:
                raise ValueError(
                    f"compute_gradients batch of {n} rows is shorter "
                    f"than one max_seq_len={self._unroll_T} unroll"
                )
            if trim != n:
                batch = {k: v[:trim] for k, v in batch.items()}
        self._rng, rng = jax.random.split(self._rng)
        grads, stats = self._grad_fn(
            self.params, self.aux_state, batch, rng, self._coeff_array()
        )
        return jax.device_get(grads), {k: float(v) for k, v in stats.items()}

    def apply_gradients(self, gradients) -> None:
        if not hasattr(self, "_apply_fn"):
            tx = self._tx

            def afn(params, opt_state, grads, lr):
                updates, opt_state = tx.update(grads, opt_state, params)
                updates = jax.tree_util.tree_map(
                    lambda u: -lr * u.astype(jnp.float32), updates
                )
                return optax.apply_updates(params, updates), opt_state

            self._apply_fn = sharding_lib.sharded_jit(
                afn,
                donate_argnums=(0, 1),
                label=f"apply_grads[{type(self).__name__}]",
            )
        self.params, self.opt_state = self._apply_fn(
            self.params,
            self.opt_state,
            gradients,
            jnp.asarray(self.coeff_values["lr"], jnp.float32),
        )

    # -- weights ---------------------------------------------------------

    def update_config(self, new_config: Dict) -> None:
        """Apply mutated hyperparameters at runtime (PBT explore,
        reference tune/schedulers/pbt.py does this via checkpoint+restart
        of the whole trial). Loss constants (clip_param, vf_loss_coeff,
        ...) are baked into the compiled learn programs, so those are
        dropped for re-trace; lr/entropy schedules are rebuilt from the
        new config; subclass coefficients re-derived."""
        self.config.update(new_config)
        from ray_tpu.utils.schedules import make_schedule

        self._lr_schedule = make_schedule(
            self.config.get("lr_schedule"), self.config.get("lr", 5e-5)
        )
        self._entropy_schedule = make_schedule(
            self.config.get("entropy_coeff_schedule"),
            self.config.get("entropy_coeff", 0.0),
        )
        # Re-derive loss coefficients from the mutated config, but keep
        # adaptive state (e.g. PPO's kl_coeff) for keys NOT explicitly
        # mutated — exploit just restored the donor's adapted values.
        adapted = {
            k: v
            for k, v in self.coeff_values.items()
            if k not in new_config
        }
        self._init_coeffs()
        self.coeff_values.update(
            {k: v for k, v in adapted.items() if k in self.coeff_values}
        )
        self._update_scheduled_coeffs()
        # SGD geometry is cached at init and baked into the compiled
        # nest; refresh it so mutations of these knobs take effect.
        self.train_batch_size = int(
            self.config.get("train_batch_size", self.train_batch_size)
        )
        self.minibatch_size = int(
            self.config.get("sgd_minibatch_size")
            or self.config.get("train_batch_size", self.minibatch_size)
        )
        self.num_sgd_iter = int(
            self.config.get("num_sgd_iter", self.num_sgd_iter)
        )
        self._learn_fns.clear()
        self.__dict__.pop("_superstep_fns", None)
        if hasattr(self, "_grad_fn"):
            del self._grad_fn
        # Rebuild exploration (type/knobs may have mutated) and drop the
        # compiled action program — its closure captured the old
        # strategy object.
        self._refold_exploration_config(new_config)
        self._init_exploration()
        self._action_fn = None

    # When set (a tuple of top-level param keys), only those subtrees
    # ship to sampling-only workers on sync_weights(inference_only=True)
    # — e.g. SAC's actor without its critic/target towers. None = full.
    inference_weight_keys: Optional[Tuple[str, ...]] = None

    def get_weights(self):
        return jax.device_get(self.params)

    def get_inference_weights(self):
        keys = self.inference_weight_keys
        if keys is None or not isinstance(self.params, dict):
            return self.get_weights()
        return jax.device_get(
            {k: self.params[k] for k in keys if k in self.params}
        )

    def _weights_sharding(self, weights):
        """Placement for an incoming (possibly partial) host weight
        tree: the per-leaf tree sliced to the given top-level keys
        when partitioned, the single replicated sharding otherwise.
        This is the reshard-on-restore half of the checkpoint
        contract: gather-on-save stays the format, and a tree saved
        under any mesh geometry re-places per the ACTIVE rules here."""
        ps = self._param_sharding
        if (
            isinstance(ps, dict)
            and isinstance(weights, dict)
            and all(k in ps for k in weights)
        ):
            return {k: ps[k] for k in weights}
        return ps

    def set_weights(self, weights) -> None:
        if (
            isinstance(weights, dict)
            and isinstance(self.params, dict)
            and set(weights) < set(self.params)
        ):
            # partial tree (inference-only sync): merge over the
            # existing params instead of dropping the absent subtrees
            merged = dict(self.params)
            merged.update(
                _tree_to_device(
                    weights, self._weights_sharding(weights)
                )
            )
            self.params = merged
        else:
            self.params = _tree_to_device(
                weights, self._weights_sharding(weights)
            )
        self._publish_params_bytes()
        self.exploration.on_weights_updated(self)

    def get_state(self) -> Dict[str, Any]:
        return {
            "weights": self.get_weights(),
            "opt_state": jax.device_get(self.opt_state),
            "coeff_values": dict(self.coeff_values),
            "global_timestep": self.global_timestep,
            "num_grad_updates": self.num_grad_updates,
            "exploration_state": self.exploration.get_state(),
        }

    def set_state(self, state: Dict[str, Any]) -> None:
        self.set_weights(state["weights"])
        if "opt_state" in state:
            self.opt_state = _tree_to_device(
                state["opt_state"],
                self._opt_sharding or self._param_sharding,
            )
        self.coeff_values.update(state.get("coeff_values", {}))
        self.global_timestep = state.get("global_timestep", 0)
        self.num_grad_updates = state.get("num_grad_updates", 0)
        self.exploration.set_state(state.get("exploration_state", {}))


def build_jax_policy(
    name: str,
    *,
    loss_fn,
    extra_action_out_fn=None,
    postprocess_fn=None,
    init_coeffs_fn=None,
    after_learn_fn=None,
    stats_fn=None,
):
    """Runtime policy-class builder, the JAX counterpart of the
    reference's ``build_policy_class`` (``rllib/policy/policy_template.py:38``
    — whose framework="jax" mode still inherited TorchPolicy; here the
    parent is the real JaxPolicy).

    ``loss_fn(policy, params, batch, rng, coeffs) -> (loss, stats)``
    """

    class _Built(JaxPolicy):
        def loss(self, params, batch, rng, coeffs):
            return loss_fn(self, params, batch, rng, coeffs)

        def _init_coeffs(self):
            if init_coeffs_fn:
                self.coeff_values.update(init_coeffs_fn(self))

        def extra_action_out(self, dist_inputs, value, dist, rng):
            if extra_action_out_fn:
                return extra_action_out_fn(
                    self, dist_inputs, value, dist, rng
                )
            return super().extra_action_out(dist_inputs, value, dist, rng)

        def postprocess_trajectory(
            self, sample_batch, other_agent_batches=None, episode=None
        ):
            if postprocess_fn:
                return postprocess_fn(
                    self, sample_batch, other_agent_batches, episode
                )
            return sample_batch

        def after_learn_on_batch(self, stats):
            if after_learn_fn:
                return after_learn_fn(self, stats)
            return {}

    _Built.__name__ = name
    _Built.__qualname__ = name
    return _Built


def _note_tree_size(span, tree) -> None:
    """``params`` and ``bytes`` of a parameter tree, as attributes of
    the set-up span that built it."""
    leaves = jax.tree_util.tree_leaves(tree)
    span.set_attribute("params", int(sum(x.size for x in leaves)))
    span.set_attribute(
        "bytes", int(sum(x.size * x.dtype.itemsize for x in leaves))
    )
