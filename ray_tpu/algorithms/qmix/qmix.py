"""QMIX: monotonic value-function factorization for cooperative
multi-agent RL.

Counterpart of the reference's ``rllib/algorithms/qmix/qmix.py``
(Rashid et al. 2018) and ``qmix_policy.py``: per-agent utility network
(parameter-shared) + a monotonic mixing network whose weights are
generated by state-conditioned hypernetworks (abs() keeps dQtot/dQa >=
0), trained end-to-end with joint TD.

Like the reference (which follows pymarl), agents are RECURRENT (GRU
utility nets, parameter-shared) trained over WHOLE EPISODES: the replay
buffer stores padded episode sequences, hidden states start from zero
at each episode's first step (both at rollout and train time — the
train-time unroll replays the rollout exactly), and the masked TD loss
runs over every in-episode timestep. ``agent_rnn=False`` falls back to
feedforward agents with the same mixing core. Collection is a
driver-side joint collector (the reference's grouping machinery
collapsed to stepping the MultiAgentEnv directly): all agents step
simultaneously, the team reward is the sum of agent rewards, and the
global state is the concatenation of agent observations. The whole
update — GRU unrolls, hypernets, mixer, target blend — is ONE jitted
program with static (B, T+1) shapes (episodes pad to
``episode_limit``, the pymarl batching style, which suits XLA)."""

from __future__ import annotations

from typing import Dict, List, Optional

import flax.linen as nn
import gymnasium as gym
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.algorithms.algorithm import (
    Algorithm,
    NUM_AGENT_STEPS_SAMPLED,
    NUM_ENV_STEPS_SAMPLED,
)
from ray_tpu.algorithms.algorithm_config import AlgorithmConfig
from ray_tpu.data.sample_batch import DEFAULT_POLICY_ID
from ray_tpu.env.registry import get_env_creator
from ray_tpu.evaluation.metrics import RolloutMetrics
from ray_tpu.execution.train_ops import NUM_ENV_STEPS_TRAINED
from ray_tpu.models.base import get_activation
from ray_tpu.utils.schedules import PiecewiseSchedule


class _AgentQNet(nn.Module):
    """Per-agent utility network (parameter-shared): fc → GRU → Q head
    (the reference's RNNAgent in qmix_policy.py). Sequence-shaped: obs
    (B, T, d) + carry (B, cell) → q (B, T, A), carry. Feedforward mode
    (``recurrent=False``) skips the GRU."""

    num_actions: int
    hiddens: tuple = (64,)
    cell_size: int = 64
    recurrent: bool = True

    def initial_state(self, batch_size: int) -> jnp.ndarray:
        return jnp.zeros((batch_size, self.cell_size), jnp.float32)

    @nn.compact
    def __call__(self, obs, h=None):
        x = obs.astype(jnp.float32)
        act = get_activation("relu")
        for i, width in enumerate(self.hiddens):
            x = act(nn.Dense(width, name=f"fc_{i}")(x))
        if self.recurrent:
            cell = nn.GRUCell(self.cell_size, name="gru")
            scan = nn.scan(
                lambda c, carry, xt: c(carry, xt),
                variable_broadcast="params",
                split_rngs={"params": False},
                in_axes=1,
                out_axes=1,
            )
            if h is None:
                h = self.initial_state(x.shape[0])
            h, x = scan(cell, h, x)
        return nn.Dense(self.num_actions, name="q")(x), h


class _Mixer(nn.Module):
    """Monotonic mixer with state-conditioned hypernetworks
    (reference qmix.py QMixer)."""

    n_agents: int
    embed_dim: int = 32
    hypernet_hidden: int = 64

    @nn.compact
    def __call__(self, agent_qs, state):
        # agent_qs: (B, n), state: (B, s)
        B = agent_qs.shape[0]
        act = get_activation("relu")
        w1 = jnp.abs(
            nn.Dense(
                self.n_agents * self.embed_dim, name="hyper_w1"
            )(state)
        ).reshape(B, self.n_agents, self.embed_dim)
        b1 = nn.Dense(self.embed_dim, name="hyper_b1")(state)
        hidden = jax.nn.elu(
            jnp.einsum("bn,bne->be", agent_qs, w1) + b1
        )
        w2 = jnp.abs(
            nn.Dense(self.embed_dim, name="hyper_w2")(state)
        )
        b2 = nn.Dense(
            1, name="hyper_b2_out"
        )(act(nn.Dense(self.hypernet_hidden, name="hyper_b2_h")(state)))
        q_tot = jnp.sum(hidden * w2, axis=-1, keepdims=True) + b2
        return q_tot.squeeze(-1)  # (B,)


class QMIXConfig(AlgorithmConfig):
    """reference qmix.py QMixConfig."""

    def __init__(self, algo_class=None):
        super().__init__(algo_class or QMIX)
        self.mixing_embed_dim = 32
        self.hypernet_hidden = 64
        self.agent_hiddens = [64]
        # recurrent agents over whole episodes (the reference/pymarl
        # default); False = feedforward agents on 1-step transitions
        self.agent_rnn = True
        self.agent_cell_size = 64
        # static pad length for episode sequences (XLA-friendly);
        # longer episodes are chunked at this boundary
        self.episode_limit = 64
        self.double_q = True
        self.lr = 5e-4
        self.gamma = 0.99
        self.train_batch_size = 32
        self.rollout_fragment_length = 16
        self.target_network_update_freq = 200
        self.num_steps_sampled_before_learning_starts = 200
        self.buffer_size = 5000
        self.initial_epsilon = 1.0
        self.final_epsilon = 0.05
        self.epsilon_timesteps = 5000

    def training(
        self,
        *,
        mixing_embed_dim: Optional[int] = None,
        agent_rnn: Optional[bool] = None,
        episode_limit: Optional[int] = None,
        double_q: Optional[bool] = None,
        buffer_size: Optional[int] = None,
        target_network_update_freq: Optional[int] = None,
        num_steps_sampled_before_learning_starts: Optional[int] = None,
        epsilon_timesteps: Optional[int] = None,
        final_epsilon: Optional[float] = None,
        **kwargs,
    ) -> "QMIXConfig":
        super().training(**kwargs)
        if mixing_embed_dim is not None:
            self.mixing_embed_dim = mixing_embed_dim
        if agent_rnn is not None:
            self.agent_rnn = agent_rnn
        if episode_limit is not None:
            self.episode_limit = episode_limit
        if double_q is not None:
            self.double_q = double_q
        if buffer_size is not None:
            self.buffer_size = buffer_size
        if target_network_update_freq is not None:
            self.target_network_update_freq = target_network_update_freq
        if num_steps_sampled_before_learning_starts is not None:
            self.num_steps_sampled_before_learning_starts = (
                num_steps_sampled_before_learning_starts
            )
        if epsilon_timesteps is not None:
            self.epsilon_timesteps = epsilon_timesteps
        if final_epsilon is not None:
            self.final_epsilon = final_epsilon
        return self


class QMIX(Algorithm):
    @classmethod
    def get_default_config(cls) -> QMIXConfig:
        return QMIXConfig(cls)

    def setup(self, config: Dict) -> None:
        # own joint collector: bypass the WorkerSet env machinery
        env_spec = config.get("env")
        super().setup(dict(config, env=None))
        self.env = get_env_creator(env_spec)(
            config.get("env_config") or {}
        )
        obs, _ = self.env.reset(seed=config.get("seed"))
        self.agent_ids: List = sorted(obs.keys())
        self.n_agents = len(self.agent_ids)
        a0 = self.agent_ids[0]
        self.obs_dim = int(np.prod(np.asarray(obs[a0]).shape))
        space = getattr(self.env, "action_space", None)
        if isinstance(space, dict):
            space = space[a0]
        elif isinstance(space, gym.spaces.Dict):
            space = next(iter(space.spaces.values()))
        assert isinstance(space, gym.spaces.Discrete), (
            "QMIX requires Discrete agent actions"
        )
        self.num_actions = int(space.n)
        self._cur_obs = obs
        self._episode_reward = 0.0
        self._episode_len = 0

        seed = int(config.get("seed") or 0)
        self._rng = jax.random.PRNGKey(seed)
        self._np_rng = np.random.default_rng(seed)
        self.recurrent = bool(config.get("agent_rnn", True))
        self.episode_limit = int(config.get("episode_limit", 64))
        self.qnet = _AgentQNet(
            self.num_actions,
            tuple(config.get("agent_hiddens", (64,))),
            int(config.get("agent_cell_size", 64)),
            self.recurrent,
        )
        self.mixer = _Mixer(
            self.n_agents,
            int(config.get("mixing_embed_dim", 32)),
            int(config.get("hypernet_hidden", 64)),
        )
        self._rng, r1, r2 = jax.random.split(self._rng, 3)
        dummy_obs = jnp.zeros((2, 1, self.obs_dim), jnp.float32)
        dummy_qs = jnp.zeros((2, self.n_agents), jnp.float32)
        dummy_state = jnp.zeros(
            (2, self.obs_dim * self.n_agents), jnp.float32
        )
        self.params = {
            "agent": self.qnet.init(
                r1, dummy_obs, self.qnet.initial_state(2)
            ),
            "mixer": self.mixer.init(r2, dummy_qs, dummy_state),
        }
        self.target_params = jax.tree_util.tree_map(
            lambda x: x, self.params
        )
        self._tx = optax.adam(float(config.get("lr", 5e-4)))
        self.opt_state = self._tx.init(self.params)
        self._epsilon = PiecewiseSchedule(
            [
                (0, float(config.get("initial_epsilon", 1.0))),
                (
                    int(config.get("epsilon_timesteps", 5000)),
                    float(config.get("final_epsilon", 0.05)),
                ),
            ]
        )
        self._buffer: List[Dict] = []
        self._buffer_idx = 0
        # device-resident episode replay (docs/data_plane.md):
        # padded episode rows live on the learner mesh; the index
        # draw stays on self._np_rng (same stream as the host list),
        # and per-episode unpadded step counts ride a host ring so
        # the trained-steps counter needs no device readback
        self._dev_buffer = None
        self._ep_steps = None
        from ray_tpu.execution.replay_buffer import (
            DeviceReplayBuffer,
            resolve_device_resident,
        )

        # episode rows need no batch/shard divisibility (n_eps is
        # rounded at sample time), so resolve without the batch check
        if resolve_device_resident(
            {**config, "train_batch_size": 0},
            config.get("_mesh"),
        ):
            cap = int(config.get("buffer_size", 5000))
            self._dev_buffer = DeviceReplayBuffer(
                capacity=cap,
                seed=config.get("seed"),
                mesh=config.get("_mesh"),
                memory_cap_bytes=config.get(
                    "replay_memory_cap_bytes"
                ),
                label="qmix_episodes",
            )
            self._ep_steps = np.zeros(cap, np.float32)
        self._last_target_update = 0
        self._act_fn = None
        self._learn_fn = None
        # per-agent GRU carry across env steps (zero at episode start)
        self._hidden = np.zeros(
            (self.n_agents, self.qnet.cell_size), np.float32
        )
        self._episode_rows: List[Dict] = []  # current episode

    # -- acting -----------------------------------------------------------

    def _actions(self, obs_stack: np.ndarray, epsilon: float):
        if self._act_fn is None:

            def fn(params, obs, h, rng, eps):
                # agents batch as rows; one-step unroll carries h
                q, h_new = self.qnet.apply(
                    params["agent"], obs[:, None], h
                )
                q = q[:, 0]  # (n, A)
                greedy = jnp.argmax(q, axis=-1)
                rng_u, rng_a = jax.random.split(rng)
                rand = jax.random.randint(
                    rng_a, greedy.shape, 0, q.shape[-1]
                )
                use_rand = (
                    jax.random.uniform(rng_u, greedy.shape) < eps
                )
                return jnp.where(use_rand, rand, greedy), h_new

            self._act_fn = jax.jit(fn)
        self._rng, rng = jax.random.split(self._rng)
        acts, h_new = self._act_fn(
            self.params,
            jnp.asarray(obs_stack),
            jnp.asarray(self._hidden),
            rng,
            jnp.asarray(epsilon, jnp.float32),
        )
        if self.recurrent:
            self._hidden = np.asarray(h_new)
        return np.asarray(acts)

    def _collect(self, num_steps: int) -> int:
        cap = int(self.config.get("buffer_size", 5000))
        steps = 0
        for _ in range(num_steps):
            obs_stack = np.stack(
                [
                    np.asarray(
                        self._cur_obs[a], np.float32
                    ).reshape(-1)
                    for a in self.agent_ids
                ]
            )
            eps = float(
                self._epsilon(self._counters[NUM_ENV_STEPS_SAMPLED])
            )
            acts = self._actions(obs_stack, eps)
            action_dict = {
                a: int(acts[i]) for i, a in enumerate(self.agent_ids)
            }
            next_obs, rewards, terms, truncs, _ = self.env.step(
                action_dict
            )
            terminated = bool(terms.get("__all__", False))
            done = terminated or bool(truncs.get("__all__", False))
            team_reward = float(
                sum(rewards.get(a, 0.0) for a in self.agent_ids)
            )
            next_stack = (
                np.stack(
                    [
                        np.asarray(
                            next_obs.get(a, self._cur_obs[a]),
                            np.float32,
                        ).reshape(-1)
                        for a in self.agent_ids
                    ]
                )
                if next_obs
                else obs_stack
            )
            row = {
                "obs": obs_stack,
                "actions": acts.astype(np.int32),
                "reward": np.float32(team_reward),
                "next_obs": next_stack,
                # bootstrap mask uses TERMINATION only: a time-limit
                # truncation must still bootstrap Q(s')
                "done": np.float32(terminated),
            }
            self._episode_rows.append(row)
            self._episode_reward += team_reward
            self._episode_len += 1
            steps += self.n_agents
            self._counters[NUM_ENV_STEPS_SAMPLED] += 1
            self._counters[NUM_AGENT_STEPS_SAMPLED] += self.n_agents
            # episodes are the replay unit (reference/pymarl episode
            # batching); the episode_limit boundary also closes a
            # chunk so arbitrarily long episodes fit static shapes
            if done or len(self._episode_rows) >= self.episode_limit:
                self._push_episode(cap)
                if done:
                    self._episode_history.append(
                        RolloutMetrics(
                            self._episode_len, self._episode_reward
                        )
                    )
                    self._episodes_total += 1
                    self._episode_reward = 0.0
                    self._episode_len = 0
                    next_obs, _ = self.env.reset()
                if self.recurrent:
                    # reset the carry at CHUNK boundaries too, not
                    # just episode ends: the train-time unroll starts
                    # every stored chunk from zero state, so acting
                    # must match (memory horizon == episode_limit;
                    # pymarl sidesteps this by setting episode_limit
                    # to the env horizon — do the same when memory
                    # beyond the limit matters)
                    self._hidden = np.zeros_like(self._hidden)
            self._cur_obs = next_obs
        return steps

    def _push_episode(self, cap: int) -> None:
        """Pad the finished episode (or episode chunk) to the static
        (T+1)-step layout and store it as one replay row. obs has T+1
        rows (the final next_obs appended) so the train-time unroll
        can bootstrap the last step."""
        rows = self._episode_rows
        self._episode_rows = []
        if not rows:
            return
        T = self.episode_limit
        L = len(rows)
        n, d = self.n_agents, self.obs_dim
        obs = np.zeros((T + 1, n, d), np.float32)
        actions = np.zeros((T, n), np.int32)
        reward = np.zeros(T, np.float32)
        done = np.zeros(T, np.float32)
        mask = np.zeros(T, np.float32)
        for t, r in enumerate(rows):
            obs[t] = r["obs"]
            actions[t] = r["actions"]
            reward[t] = r["reward"]
            done[t] = r["done"]
            mask[t] = 1.0
        obs[L] = rows[-1]["next_obs"]
        ep = {
            "obs": obs,
            "actions": actions,
            "reward": reward,
            "done": done,
            "mask": mask,
        }
        if self._dev_buffer is not None:
            pos = self._dev_buffer._idx
            self._dev_buffer.add_tree(
                {k: v[None] for k, v in ep.items()}
            )
            if not self._dev_buffer.spilled:
                self._ep_steps[pos] = float(mask.sum())
                return
            # memory projection spilled on the first insert: QMIX
            # keeps its episode-list semantics instead of the generic
            # spill ring — abandon the device buffer and fall through
            self._dev_buffer = None
            self._ep_steps = None
        if len(self._buffer) < cap:
            self._buffer.append(ep)
        else:
            self._buffer[self._buffer_idx] = ep
        self._buffer_idx = (self._buffer_idx + 1) % cap

    # -- learning ---------------------------------------------------------

    def _build_learn_fn(self):
        gamma = float(self.config.get("gamma", 0.99))
        double_q = bool(self.config.get("double_q", True))
        qnet, mixer, tx = self.qnet, self.mixer, self._tx
        cell = self.qnet.cell_size
        # data-parallel episodes over a mesh (config "_mesh"): each
        # shard unrolls its episode slice; TD normalization and grads
        # reduce globally (psum), so the update equals the single-
        # device one. Mirrors the JaxPolicy shard_map nest — QMIX owns
        # a bespoke episode-major program, so the wiring lives here.
        from ray_tpu import sharding as sharding_lib

        mesh = self.config.get("_mesh")
        axis = None
        if mesh is not None and int(
            np.prod(list(mesh.shape.values()))
        ) > 1:
            axis = sharding_lib.data_axis(mesh)

        def unroll_q(agent_params, obs):
            """obs (B, T1, n, d) → per-step agent Qs (B, T1, n, A):
            agents fold into the unroll batch; hidden starts at ZERO
            (episode starts — matching rollout, which also started
            this episode's carry at zero)."""
            B, T1, n, d = obs.shape
            seq = obs.transpose(0, 2, 1, 3).reshape(B * n, T1, d)
            # derive the zero carry from the (possibly mesh-varying)
            # data so scan's carry in/out types agree under shard_map
            h0 = jnp.zeros((B * n, cell), jnp.float32) + 0.0 * seq[:, 0, :1]
            q, _ = qnet.apply(agent_params, seq, h0)
            return q.reshape(B, n, T1, -1).transpose(0, 2, 1, 3)

        def fn(params, target_params, opt_state, batch, rng):
            obs = batch["obs"]  # (B, T+1, n, d)
            B, T1, n, d = obs.shape
            T = T1 - 1
            state = obs.reshape(B, T1, n * d)  # (B, T+1, n*d)
            actions = batch["actions"].astype(jnp.int32)  # (B, T, n)
            mask = batch["mask"]  # (B, T)

            # target-net unroll over the whole episode; Qtot_tgt at
            # t+1 bootstraps step t (reference qmix_policy.py targets)
            tq = unroll_q(target_params["agent"], obs)  # (B,T+1,n,A)
            if double_q:
                oq_all = unroll_q(params["agent"], obs)
                next_acts = jnp.argmax(oq_all[:, 1:], axis=-1)
            else:
                next_acts = jnp.argmax(tq[:, 1:], axis=-1)  # (B,T,n)
            tq_sel = jnp.take_along_axis(
                tq[:, 1:], next_acts[..., None], axis=-1
            ).squeeze(-1)  # (B, T, n)
            target_qtot = mixer.apply(
                target_params["mixer"],
                tq_sel.reshape(B * T, n),
                state[:, 1:].reshape(B * T, n * d),
            ).reshape(B, T)
            y = jax.lax.stop_gradient(
                batch["reward"]
                + gamma * (1.0 - batch["done"]) * target_qtot
            )

            def loss_fn(p):
                q = unroll_q(p["agent"], obs)[:, :T]  # (B, T, n, A)
                q_sel = jnp.take_along_axis(
                    q, actions[..., None], axis=-1
                ).squeeze(-1)  # (B, T, n)
                q_tot = mixer.apply(
                    p["mixer"],
                    q_sel.reshape(B * T, n),
                    state[:, :T].reshape(B * T, n * d),
                ).reshape(B, T)
                td = (q_tot - y) * mask
                denom = jnp.maximum(jnp.sum(mask), 1.0)
                if axis is not None:
                    # normalize by the GLOBAL mask count so the summed
                    # cross-shard gradient equals the one-device update
                    denom = jax.lax.psum(denom, axis)
                loss = jnp.sum(jnp.square(td)) / denom
                return loss, (q_tot, td, denom)

            # per-shard view of the replicated params, so the psum
            # below is the one cross-shard reduction
            # (sharding/specs.py "varying-axes typing")
            (loss, (q_tot, td, denom)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(
                params
                if axis is None
                else sharding_lib.varying(params, axis)
            )
            if axis is not None:
                grads = jax.lax.psum(grads, axis)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            stats = {
                "loss": loss,
                "mean_qtot": jnp.sum(q_tot * mask) / denom,
                "mean_td_error": jnp.sum(td) / denom,
            }
            if axis is not None:
                stats = {
                    k: jax.lax.psum(v, axis) for k, v in stats.items()
                }
            return params, opt_state, stats

        if axis is None:
            return sharding_lib.sharded_jit(fn, label="learn[QMIX]")
        from jax.sharding import PartitionSpec as P

        sharded = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(P(), P(), P(), P(axis), P()),
            out_specs=(P(), P(), P()),
        )
        return sharding_lib.sharded_jit(sharded, label="learn[QMIX]")

    def training_step(self) -> Dict:
        config = self.config
        self._collect(int(config.get("rollout_fragment_length", 16)))
        train_info: Dict = {}
        buf_len = (
            len(self._dev_buffer)
            if self._dev_buffer is not None
            else len(self._buffer)
        )
        if (
            self._counters[NUM_ENV_STEPS_SAMPLED]
            >= config.get("num_steps_sampled_before_learning_starts", 0)
            and buf_len >= config["train_batch_size"]
        ):
            if self._learn_fn is None:
                self._learn_fn = self._build_learn_fn()
            n_eps = int(config["train_batch_size"])
            mesh = config.get("_mesh")
            if mesh is not None:
                # episodes shard over the data axis: round the sample
                # to a whole multiple so shapes stay static
                shards = int(np.prod(list(mesh.shape.values())))
                n_eps = max(shards, (n_eps // shards) * shards)
            idx = self._np_rng.integers(0, buf_len, n_eps)
            if self._dev_buffer is not None:
                # episode rows gather on device; unpadded step counts
                # come from the host-side ring (no device readback)
                batch = dict(self._dev_buffer.gather(idx).tree)
                steps_trained = int(self._ep_steps[idx].sum())
            else:
                rows = [self._buffer[i] for i in idx]
                batch = {
                    k: jnp.asarray(np.stack([r[k] for r in rows]))
                    for k in rows[0]
                }
                steps_trained = int(
                    sum(float(r["mask"].sum()) for r in rows)
                )
            self._rng, rng = jax.random.split(self._rng)
            self.params, self.opt_state, stats = self._learn_fn(
                self.params, self.target_params, self.opt_state,
                batch, rng,
            )
            stats = {
                k: float(v) for k, v in jax.device_get(stats).items()
            }
            train_info = {DEFAULT_POLICY_ID: stats}
            # train_batch_size counts EPISODES; env steps trained is
            # the unpadded timestep total
            self._counters[NUM_ENV_STEPS_TRAINED] += steps_trained
            if (
                self._counters[NUM_ENV_STEPS_TRAINED]
                - self._last_target_update
                >= config.get("target_network_update_freq", 200)
            ):
                self.target_params = jax.tree_util.tree_map(
                    lambda x: x, self.params
                )
                self._last_target_update = self._counters[
                    NUM_ENV_STEPS_TRAINED
                ]
                self._counters["num_target_updates"] += 1
        return train_info

    # -- checkpoint state -------------------------------------------------

    def __getstate__(self) -> Dict:
        return {
            "params": jax.device_get(self.params),
            "target_params": jax.device_get(self.target_params),
            "opt_state": jax.device_get(self.opt_state),
            "counters": dict(self._counters),
            "episodes_total": self._episodes_total,
        }

    def __setstate__(self, state: Dict) -> None:
        import collections

        self.params = jax.device_put(state["params"])
        self.target_params = jax.device_put(state["target_params"])
        self.opt_state = jax.device_put(state["opt_state"])
        self._counters = collections.defaultdict(
            int, state.get("counters", {})
        )
        self._episodes_total = state.get("episodes_total", 0)

    def cleanup(self) -> None:
        try:
            self.env.close()
        except Exception:
            pass
        super().cleanup()
