"""SlateQ: Q-learning over recommendation slates.

Counterpart of the reference's ``rllib/algorithms/slateq/slateq.py``
(Ie et al. 2019) and ``slateq_torch_policy.py``: per-item Q values
decomposed over a slate with a multinomial-proportional user-choice
model — Q(s, slate) = Σ_i score_i·Q_i / (Σ_i score_i + no_click), the
greedy slate maximizes that over ALL candidate slates, and the TD
target bootstraps the max next-slate value (``build_slateq_losses``,
``get_per_slate_q_values``, ``score_documents``).

The user-choice model is LEARNED, like the reference's UserChoiceModel:
a multinomial-logit with learnable affinity scale (beta) and no-click
score, fit by cross-entropy on the observed click/no-click events with
its own learning rate (``lr_choice_model``), and its probabilities
drive both the slate decomposition and the TD targets (stop-gradient:
the TD loss never reshapes the choice model). Slates are ordered
S-permutations enumerated at init (same as the reference's precomputed
``policy.slates``). The whole step — choice NLL, per-item Q net, slate
enumeration via gather, choice-weighted decomposition, target max —
is ONE jitted program; slate enumeration is a static (A, S) index
table so XLA sees fixed shapes.

Because the stock samplers stack flat observation arrays, observations
are the FLAT RecSim layout ``[user(E) | docs(C*E) | response(2S)]``
where response carries the PREVIOUS step's click indicator and watch
times (the RecSim convention the reference consumes); the policy slices
it. ``SyntheticSlateEnv`` below provides the interest-evolution-style
test env (the image has no RecSim)."""

from __future__ import annotations

import itertools
from typing import Dict, List, Optional

import flax.linen as nn
import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu import sharding as sharding_lib
from ray_tpu.algorithms.algorithm_config import AlgorithmConfig  # noqa: F401
from ray_tpu.algorithms.dqn.dqn import DQN, DQNConfig
from ray_tpu.data.sample_batch import SampleBatch
from ray_tpu.models.base import get_activation
from ray_tpu.policy.jax_policy import JaxPolicy, _tree_to_device
from ray_tpu.policy.policy import Policy


class SyntheticSlateEnv(gym.Env):
    """Interest-evolution-style slate env: the user clicks candidates
    proportionally to interest (dot product), watch time rewards
    interest, and interest slowly drifts toward watched content."""

    def __init__(self, config=None):
        config = config or {}
        self.E = int(config.get("embedding_dim", 4))
        self.C = int(config.get("num_candidates", 8))
        self.S = int(config.get("slate_size", 2))
        self.horizon = int(config.get("horizon", 20))
        self._rng = np.random.default_rng(config.get("seed", 0))
        self.observation_space = gym.spaces.Box(
            -np.inf,
            np.inf,
            (self.E + self.C * self.E + 2 * self.S,),
            np.float32,
        )
        self.action_space = gym.spaces.MultiDiscrete(
            [self.C] * self.S
        )

    def _sample_docs(self):
        docs = self._rng.standard_normal((self.C, self.E))
        return (docs / np.linalg.norm(docs, axis=1, keepdims=True)).astype(
            np.float32
        )

    def _obs(self):
        return np.concatenate(
            [
                self.user,
                self.docs.reshape(-1),
                self.last_response.reshape(-1),
            ]
        ).astype(np.float32)

    def reset(self, *, seed=None, options=None):
        if seed is not None:
            self._rng = np.random.default_rng(seed)
        user = self._rng.standard_normal(self.E)
        self.user = (user / np.linalg.norm(user)).astype(np.float32)
        self.docs = self._sample_docs()
        self.last_response = np.zeros((2, self.S), np.float32)
        self._t = 0
        return self._obs(), {}

    def step(self, action):
        slate = np.asarray(action, np.int64).reshape(-1)[: self.S]
        scores = self.docs[slate] @ self.user  # (S,)
        # multinomial proportional choice with no-click mass
        probs = np.maximum(scores + 1.0, 0.0)
        all_mass = np.concatenate([probs, [1.0]])  # no-click last
        all_mass = all_mass / all_mass.sum()
        choice = self._rng.choice(self.S + 1, p=all_mass)
        click = np.zeros(self.S, np.float32)
        watch = np.zeros(self.S, np.float32)
        reward = 0.0
        if choice < self.S:
            click[choice] = 1.0
            watch[choice] = max(0.0, float(scores[choice])) + 0.1
            reward = float(watch[choice])
            # interest drifts toward watched content
            doc = self.docs[slate[choice]]
            self.user = (0.95 * self.user + 0.05 * doc).astype(
                np.float32
            )
            self.user /= np.linalg.norm(self.user)
        self.last_response = np.stack([click, watch])
        self.docs = self._sample_docs()
        self._t += 1
        truncated = self._t >= self.horizon
        return self._obs(), reward, False, truncated, {}


class _ItemQNet(nn.Module):
    """Q(user, doc) per candidate (reference QValueModel)."""

    hiddens: tuple = (64, 64)

    @nn.compact
    def __call__(self, user, docs):
        # user: (B, E); docs: (B, C, E) → (B, C)
        B, C, E = docs.shape
        act = get_activation("relu")
        x = jnp.concatenate(
            [jnp.repeat(user[:, None], C, axis=1), docs], axis=-1
        ).reshape(B * C, 2 * E)
        for i, h in enumerate(self.hiddens):
            x = act(nn.Dense(h, name=f"fc_{i}")(x))
        return nn.Dense(1, name="q")(x).reshape(B, C)


class SlateQConfig(DQNConfig):
    """reference slateq.py SlateQConfig."""

    def __init__(self, algo_class=None):
        super().__init__(algo_class or SlateQ)
        self.slate_size = 2
        self.num_candidates = 8
        self.embedding_dim = 4
        self.hiddens = [64, 64]
        self.lr = 1e-3
        self.train_batch_size = 64
        self.rollout_fragment_length = 20
        self.n_step = 1
        self.target_network_update_freq = 500
        self.num_steps_sampled_before_learning_starts = 500
        self.replay_buffer_config = {
            "capacity": 20000,
            "prioritized_replay": False,
        }

    def training(
        self,
        *,
        slate_size: Optional[int] = None,
        num_candidates: Optional[int] = None,
        embedding_dim: Optional[int] = None,
        **kwargs,
    ) -> "SlateQConfig":
        super().training(**kwargs)
        if slate_size is not None:
            self.slate_size = slate_size
        if num_candidates is not None:
            self.num_candidates = num_candidates
        if embedding_dim is not None:
            self.embedding_dim = embedding_dim
        return self


def _score_documents(user, docs, no_click_score=1.0, min_normalizer=-1.0):
    """reference score_documents: proportional choice scores (the
    FIXED scorer; kept for choice_model="proportional")."""
    scores = jnp.sum(user[:, None, :] * docs, axis=-1)  # (B, C)
    scores = scores - min_normalizer
    no_click = jnp.full((user.shape[0],), no_click_score - min_normalizer)
    return scores, no_click


class _ChoiceModel(nn.Module):
    """LEARNED multinomial-logit user-choice model (reference
    slateq_torch_policy.py UserChoiceModel: learnable ``beta`` scaling
    the user·doc affinity and a learnable no-click score, fit by
    cross-entropy on observed clicks with its own learning rate,
    ``lr_choice_model``)."""

    @nn.compact
    def __call__(self, user, docs):
        # beta starts at 0 (uniform choice, matching the reference's
        # UserChoiceModel init): the model must LEARN the affinity
        # scale from observed clicks, so its NLL has genuine headroom
        # below the untrained value — a beta=1 init happens to sit
        # near this env's optimum (exp(s) ≈ 1+s on unit-normalized
        # docs) and leaves the fit nothing to do
        beta = self.param(
            "beta", lambda k: jnp.asarray(0.0, jnp.float32)
        )
        score_no_click = self.param(
            "score_no_click", lambda k: jnp.asarray(0.0, jnp.float32)
        )
        dots = jnp.sum(user[:, None, :] * docs, axis=-1)  # (B, C)
        scores = jnp.clip(beta * dots, -15.0, 15.0)
        no_click = jnp.broadcast_to(
            jnp.clip(score_no_click, -15.0, 15.0), (user.shape[0],)
        )
        return scores, no_click


def _choice_masses(scores, no_click):
    """Multinomial-logit masses: exp(score) per doc, exp(no_click)
    abstention mass — the v_i the slate decomposition normalizes."""
    return jnp.exp(scores), jnp.exp(no_click)


class SlateQJaxPolicy(JaxPolicy):
    """reference slateq_torch_policy.py (decomposed slate Q)."""

    default_exploration = "EpsilonGreedy"

    def __init__(self, observation_space, action_space, config):
        from ray_tpu.algorithms.dqn.dqn import (
            _epsilon_exploration_config,
        )

        config = dict(config)
        config["exploration_config"] = _epsilon_exploration_config(
            config
        )
        Policy.__init__(self, observation_space, action_space, config)
        self.E = int(config.get("embedding_dim", 4))
        self.C = int(config.get("num_candidates", 8))
        self.S = int(config.get("slate_size", 2))
        # all ordered slates (reference precomputes policy.slates)
        self.slates = np.array(
            list(itertools.permutations(range(self.C), self.S)),
            np.int32,
        )  # (A, S)

        self.mesh = sharding_lib.resolve_mesh(config)
        self.n_shards = sharding_lib.num_shards(self.mesh)
        self._param_sharding = sharding_lib.replicated(self.mesh)
        self._data_sharding = sharding_lib.batch_sharded(self.mesh)

        self.qnet = _ItemQNet(tuple(config.get("hiddens", (64, 64))))
        self.choice_model = _ChoiceModel()
        seed = int(config.get("seed") or 0)
        self._rng = jax.random.PRNGKey(seed)
        self._rng, r1, r2 = jax.random.split(self._rng, 3)
        dummy_u = jnp.zeros((2, self.E), jnp.float32)
        dummy_d = jnp.zeros((2, self.C, self.E), jnp.float32)
        self.params = _tree_to_device(
            {
                "q": self.qnet.init(r1, dummy_u, dummy_d),
                "choice": self.choice_model.init(
                    r2, dummy_u, dummy_d
                ),
            },
            self._param_sharding,
        )
        self.aux_state = _tree_to_device(
            {"target_params": self.params["q"]}, self._param_sharding
        )
        # separate learning rates: TD net vs the choice model's NLL
        # (reference lr_choice_model vs lr_q_model)
        self._tx = optax.multi_transform(
            {
                "q": optax.adam(float(config.get("lr", 1e-3))),
                "choice": optax.adam(
                    float(config.get("lr_choice_model", 1e-2))
                ),
            },
            lambda params: {
                k: jax.tree_util.tree_map(lambda _: k, sub)
                for k, sub in params.items()
            },
        )
        self.opt_state = _tree_to_device(
            self._tx.init(self.params), self._param_sharding
        )
        self.gamma = float(config.get("gamma", 0.99))
        # base learn_on_device_batch plumbing (schedules feed the
        # traced coeffs dict; the adam tx already embeds the lr, so the
        # scheduled value is informational here)
        from ray_tpu.utils.schedules import make_schedule

        self._lr_schedule = make_schedule(
            config.get("lr_schedule"), config.get("lr", 1e-3)
        )
        self._entropy_schedule = make_schedule(None, 0.0)
        self.coeff_values: Dict[str, float] = {
            "lr": float(self._lr_schedule(0)),
            "entropy_coeff": 0.0,
        }
        self.train_batch_size = int(config.get("train_batch_size", 64))
        self.minibatch_size = self.train_batch_size
        self.num_sgd_iter = 1
        self._learn_fns: Dict = {}
        self._action_fn = None
        self.num_grad_updates = 0
        self._init_exploration()

    # -- obs slicing -------------------------------------------------------

    def _split_obs(self, obs):
        user = obs[:, : self.E]
        docs = obs[
            :, self.E : self.E + self.C * self.E
        ].reshape(-1, self.C, self.E)
        response = obs[:, self.E + self.C * self.E :].reshape(
            -1, 2, self.S
        )
        return user, docs, response

    def _slate_values(self, q_values, scores, no_click):
        """Q(s, slate) for every slate (reference
        get_per_slate_q_values)."""
        slates = jnp.asarray(self.slates)  # (A, S)
        q_slate = q_values[:, slates]  # (B, A, S)
        s_slate = scores[:, slates]  # (B, A, S)
        denom = s_slate.sum(-1) + no_click[:, None]  # (B, A)
        return (q_slate * s_slate).sum(-1) / denom  # (B, A)

    # -- inference ---------------------------------------------------------

    def _build_action_fn(self):
        def fn(params, obs, rng, explore, epsilon):
            user, docs, _ = self._split_obs(obs)
            q = self.qnet.apply(params["q"], user, docs)
            scores, no_click = _choice_masses(
                *self.choice_model.apply(params["choice"], user, docs)
            )
            slate_vals = self._slate_values(q, scores, no_click)
            greedy = jnp.argmax(slate_vals, axis=-1)  # (B,)
            if explore:
                rng_u, rng_a = jax.random.split(rng)
                rand = jax.random.randint(
                    rng_a, greedy.shape, 0, self.slates.shape[0]
                )
                use_rand = (
                    jax.random.uniform(rng_u, greedy.shape) < epsilon
                )
                idx = jnp.where(use_rand, rand, greedy)
            else:
                idx = greedy
            return jnp.asarray(self.slates)[idx]  # (B, S)

        return jax.jit(fn, static_argnames=("explore",))

    def compute_actions(
        self, obs_batch, state_batches=None, explore=True, **kwargs
    ):
        if self._action_fn is None:
            self._action_fn = self._build_action_fn()
        self.exploration.update_coeffs(
            self.coeff_values, self.global_timestep
        )
        self._rng, rng = jax.random.split(self._rng)
        actions = self._action_fn(
            self.params,
            jnp.asarray(obs_batch, jnp.float32),
            rng,
            bool(explore),
            jnp.asarray(
                self.coeff_values.get("epsilon", 0.0), jnp.float32
            ),
        )
        return np.asarray(actions), [], {}

    # -- learning ----------------------------------------------------------

    def _build_learn_fn(self, batch_size: int):
        from jax.sharding import PartitionSpec as P

        gamma = self.gamma
        tx = self._tx
        axis = sharding_lib.data_axis(self.mesh)

        def device_fn(params, opt_state, aux, batch, rng, coeffs):
            obs = batch[SampleBatch.OBS]
            next_obs = batch[SampleBatch.NEXT_OBS]
            actions = batch[SampleBatch.ACTIONS].astype(jnp.int32)
            done = batch[SampleBatch.TERMINATEDS].astype(jnp.float32)
            user, docs, _ = self._split_obs(obs)
            # NEXT_OBS response slot carries THIS transition's clicks
            next_user, next_docs, next_resp = self._split_obs(next_obs)
            click = next_resp[:, 0, :]  # (B, S)
            watch = next_resp[:, 1, :]
            reward = jnp.sum(watch * click, axis=1)

            # target: max over next slates of the decomposed value.
            # Target Qs evaluate the NEXT observation's user/docs — the
            # reference evaluates its target model on current obs with
            # a "TODO: find out whether obs or next_obs is correct"
            # (slateq_torch_policy.py:137); with per-step candidate
            # resampling only the next-obs pairing is coherent. Choice
            # probabilities come from the CURRENT learned choice model
            # (stop-gradient: the TD loss must not reshape it).
            tq = self.qnet.apply(
                aux["target_params"], next_user, next_docs
            )
            n_scores, n_no_click = _choice_masses(
                *self.choice_model.apply(
                    params["choice"], next_user, next_docs
                )
            )
            n_scores = jax.lax.stop_gradient(n_scores)
            n_no_click = jax.lax.stop_gradient(n_no_click)
            target_slate_vals = self._slate_values(
                tq, n_scores, n_no_click
            )
            next_max = jnp.max(target_slate_vals, axis=-1)
            y = jax.lax.stop_gradient(
                reward + gamma * (1.0 - done) * next_max
            )

            is_weights = batch.get(
                "weights", jnp.ones_like(done)
            )  # PER importance correction

            def loss_fn(p):
                q = self.qnet.apply(p["q"], user, docs)  # (B, C)
                slate_q = jnp.take_along_axis(
                    q, actions, axis=1
                )  # (B, S)
                clicked_q = jnp.sum(slate_q * click, axis=1)  # (B,)
                clicked = click.sum(axis=1)  # 0/1
                td = (clicked_q - y) * clicked  # only clicked rows
                # normalize by the GLOBAL clicked count so gradient
                # weight per sample doesn't depend on how clicks land
                # across shards (pmean of grads follows)
                n = jnp.maximum(
                    jax.lax.psum(clicked.sum(), axis), 1.0
                )
                shards = jax.lax.psum(1.0, axis)
                td_loss = (
                    shards * jnp.sum(is_weights * jnp.square(td)) / n
                )
                # choice-model NLL on the OBSERVED event: which of the
                # S shown docs was clicked, or no-click (class S) —
                # reference slateq_torch_policy.py choice_loss with
                # lr_choice_model
                c_scores, c_no_click = self.choice_model.apply(
                    p["choice"], user, docs
                )
                shown = jnp.take_along_axis(
                    c_scores, actions, axis=1
                )  # (B, S)
                logits = jnp.concatenate(
                    [shown, c_no_click[:, None]], axis=1
                )  # (B, S+1)
                label = jnp.where(
                    clicked > 0,
                    jnp.argmax(click, axis=1),
                    jnp.full_like(actions[:, 0], self.S),
                )
                logp = jax.nn.log_softmax(logits, axis=-1)
                nll = -jnp.take_along_axis(
                    logp, label[:, None], axis=1
                ).squeeze(1)
                choice_loss = jnp.mean(nll)
                return (
                    td_loss + choice_loss,
                    (clicked_q, td, n, choice_loss),
                )

            (
                (loss, (clicked_q, td, n, choice_loss)),
                grads,
            ) = jax.value_and_grad(loss_fn, has_aux=True)(
                sharding_lib.varying(params, axis)
            )
            grads = jax.lax.pmean(grads, axis)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            stats = {
                "total_loss": loss,
                "choice_loss": choice_loss,
                "choice_beta": params["choice"]["params"]["beta"],
                "mean_q_clicked": jnp.sum(clicked_q) / n,
                "mean_td_error": jnp.sum(td) / n,
                "click_fraction": jnp.mean(click.sum(axis=1)),
            }
            stats = jax.tree_util.tree_map(
                lambda x: jax.lax.pmean(x, axis), stats
            )
            return params, opt_state, stats

        sharded = jax.shard_map(
            device_fn,
            mesh=self.mesh,
            in_specs=(P(), P(), P(), P(axis), P(), P()),
            out_specs=(P(), P(), P()),
        )
        rep = self._param_sharding
        return sharding_lib.sharded_jit(
            sharded,
            in_specs=(rep, rep, rep, self._data_sharding, rep, rep),
            out_specs=(rep, rep, rep),
            donate_argnums=(1,),
            label=f"learn[{type(self).__name__}:{batch_size}]",
        )

    def _refold_exploration_config(self, new_config):
        from ray_tpu.algorithms.dqn.dqn import (
            _epsilon_exploration_config,
        )

        self.config["exploration_config"] = _epsilon_exploration_config(
            self.config, force_keys=new_config
        )

    def update_target(self) -> None:
        # the choice model has no target copy: TD targets always use
        # the freshest learned choice probabilities
        self.aux_state = {"target_params": self.params["q"]}

    def _batch_to_train_tree(self, samples: SampleBatch):
        keys = [
            SampleBatch.OBS,
            SampleBatch.NEXT_OBS,
            SampleBatch.ACTIONS,
            SampleBatch.TERMINATEDS,
            "weights",  # PER importance correction
        ]
        return {
            k: np.asarray(samples[k]) for k in keys if k in samples
        }

    def compute_td_error(self, samples) -> np.ndarray:
        """Per-sample |TD| for prioritized-replay refresh (unclicked
        rows report 0 — they contribute no TD signal)."""
        if not hasattr(self, "_td_error_fn"):

            def fn(params, aux, batch):
                obs = batch[SampleBatch.OBS]
                next_obs = batch[SampleBatch.NEXT_OBS]
                actions = batch[SampleBatch.ACTIONS].astype(jnp.int32)
                done = batch[SampleBatch.TERMINATEDS].astype(
                    jnp.float32
                )
                user, docs, _ = self._split_obs(obs)
                next_user, next_docs, next_resp = self._split_obs(
                    next_obs
                )
                click = next_resp[:, 0, :]
                watch = next_resp[:, 1, :]
                reward = jnp.sum(watch * click, axis=1)
                tq = self.qnet.apply(
                    aux["target_params"], next_user, next_docs
                )
                n_scores, n_no_click = _choice_masses(
                    *self.choice_model.apply(
                        params["choice"], next_user, next_docs
                    )
                )
                next_max = jnp.max(
                    self._slate_values(tq, n_scores, n_no_click),
                    axis=-1,
                )
                y = reward + self.gamma * (1.0 - done) * next_max
                q = self.qnet.apply(params["q"], user, docs)
                clicked_q = jnp.sum(
                    jnp.take_along_axis(q, actions, axis=1) * click,
                    axis=1,
                )
                return (clicked_q - y) * click.sum(axis=1)

            self._td_error_fn = jax.jit(fn)
        batch = self._td_input_tree(samples)
        td = self._td_error_fn(self.params, self.aux_state, batch)
        return np.abs(np.asarray(td))

    def get_initial_state(self):
        return []


class SlateQ(DQN):
    _default_policy_class = SlateQJaxPolicy

    @classmethod
    def get_default_config(cls) -> SlateQConfig:
        return SlateQConfig(cls)

    def setup(self, config) -> None:
        if int(config.get("n_step", 1)) != 1:
            raise ValueError(
                "SlateQ derives rewards from the slate response in "
                "NEXT_OBS; n-step folding would pair them wrongly — "
                "n_step must be 1"
            )
        if config.get("lr_schedule"):
            raise ValueError(
                "SlateQ's compiled step embeds a fixed adam lr; "
                "lr_schedule is not supported yet"
            )
        super().setup(config)


# default example-env registration so tuned_examples yamls resolve it
from ray_tpu.env.registry import register_env  # noqa: E402

register_env("SyntheticSlate-v0", lambda cfg: SyntheticSlateEnv(cfg))
