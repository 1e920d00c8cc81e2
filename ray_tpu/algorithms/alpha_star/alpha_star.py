"""AlphaStar-style league self-play training.

Counterpart of the reference's ``rllib/algorithms/alpha_star/
alpha_star.py:2,102`` (league-based asynchronous multi-agent training
with DISTRIBUTED PER-POLICY LEARNERS) and ``league_builder.py``:

- TWO trainable league roles — "main" (PFSP against frozen league
  snapshots, prioritized fictitious self-play) and "main_exploiter"
  (trains exclusively against the current main, the reference's
  exploiter role) — plus frozen snapshots that join the league when
  main dominates.
- Per-policy learner sharding, the TPU way: the reference places each
  trainable policy's learner on its own GPU shard
  (``alpha_star.py:102`` distributed learner actors); here each
  trainable policy compiles its SGD nest over its OWN SUBMESH of the
  device mesh (mesh split across trainables when enough devices
  exist), so the per-policy updates are independent XLA programs on
  disjoint devices — dispatched asynchronously from one controller,
  they run concurrently like the reference's learner shards.

Env contract: exactly two agents per game; agent ids are arbitrary but
sorted order decides sides — sorted[0] plays the first role of the
current matchup, sorted[1] the second. Matchups alternate between
(main vs PFSP-sampled snapshot) and (main_exploiter vs main).
Zero-sum outcome is read from per-agent episode rewards."""

from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from ray_tpu.algorithms.algorithm import (
    Algorithm,
    NUM_AGENT_STEPS_SAMPLED,
    NUM_ENV_STEPS_SAMPLED,
)
from ray_tpu.algorithms.ppo.ppo import PPO, PPOConfig, PPOJaxPolicy
from ray_tpu.algorithms.alpha_star.league_builder import (
    MAIN_POLICY_ID,
    LeagueBuilder,
)
from ray_tpu.data.sample_batch import SampleBatch
from ray_tpu.execution.rollout_ops import synchronous_parallel_sample
from ray_tpu.execution.train_ops import train_one_step


class AlphaStarConfig(PPOConfig):
    """reference alpha_star.py AlphaStarConfig (league knobs)."""

    def __init__(self, algo_class=None):
        super().__init__(algo_class or AlphaStar)
        self.win_rate_threshold = 0.7
        self.league_window = 50
        self.max_league_size = 8
        self.pfsp_power = 2.0
        self.num_workers = 0  # league matchmaking is driver-side
        # the exploiter role (reference league_builder main exploiters);
        # False = single-main league
        self.train_exploiter = True

    def training(
        self,
        *,
        win_rate_threshold: Optional[float] = None,
        league_window: Optional[int] = None,
        max_league_size: Optional[int] = None,
        train_exploiter: Optional[bool] = None,
        **kwargs,
    ) -> "AlphaStarConfig":
        super().training(**kwargs)
        if win_rate_threshold is not None:
            self.win_rate_threshold = win_rate_threshold
        if league_window is not None:
            self.league_window = league_window
        if max_league_size is not None:
            self.max_league_size = max_league_size
        if train_exploiter is not None:
            self.train_exploiter = train_exploiter
        return self


EXPLOITER_POLICY_ID = "main_exploiter"


class AlphaStar(Algorithm):
    _default_policy_class = PPOJaxPolicy

    @classmethod
    def get_default_config(cls) -> AlphaStarConfig:
        return AlphaStarConfig(cls)

    def setup(self, config: Dict) -> None:
        if int(config.get("num_workers", 0)) != 0:
            raise ValueError(
                "league matchmaking runs driver-side: num_workers=0 "
                "(the reference shards league actors instead)"
            )
        # main + first frozen snapshot share spaces from the env
        from ray_tpu.env.registry import get_env_creator

        probe = get_env_creator(config["env"])(
            config.get("env_config") or {}
        )
        obs_space = probe.observation_space
        act_space = probe.action_space
        try:
            probe.close()
        except Exception:
            pass
        self.league = LeagueBuilder(
            win_rate_threshold=config.get("win_rate_threshold", 0.7),
            window=config.get("league_window", 50),
            pfsp_power=config.get("pfsp_power", 2.0),
            max_league_size=config.get("max_league_size", 8),
            seed=config.get("seed"),
        )
        first = self.league.next_member_id()
        self._train_exploiter = bool(
            config.get("train_exploiter", True)
        )
        trainable = [MAIN_POLICY_ID] + (
            [EXPLOITER_POLICY_ID] if self._train_exploiter else []
        )
        # per-policy learner shards: split the mesh across trainable
        # policies when enough devices exist (the reference's
        # distributed per-policy learner actors, alpha_star.py:102);
        # fewer devices than trainables → everyone shares the full mesh
        import jax

        from ray_tpu import sharding as sharding_lib

        devices = list(jax.devices())
        per = len(devices) // len(trainable)
        submeshes = {}
        if per >= 1 and len(trainable) > 1 and len(devices) > 1:
            for i, pid in enumerate(trainable):
                submeshes[pid] = sharding_lib.get_mesh(
                    devices=devices[i * per : (i + 1) * per]
                )
        self._learner_submeshes = submeshes
        config["policies"] = {
            pid: (
                None,
                obs_space,
                act_space,
                (
                    {"_mesh": submeshes[pid]}
                    if pid in submeshes
                    else {}
                ),
            )
            for pid in trainable
        }
        config["policies"][first] = (None, obs_space, act_space, {})
        config["policies_to_train"] = trainable
        self._current_opponent = first
        self._obs_space, self._act_space = obs_space, act_space
        self._mapping_calls = 0
        self._matchup_idx = 0
        self._side_order = [MAIN_POLICY_ID, first]

        # The sampler re-consults the mapping fn for every agent at
        # each episode reset (exactly two agents per game), so every
        # even-numbered call starts a fresh matchup: the first
        # consulted agent plays the matchup's first role, the second
        # its opponent.
        def mapping_fn(agent_id, **kw):
            if self._mapping_calls % 2 == 0:
                self._new_matchup()
            role = self._side_order[self._mapping_calls % 2]
            self._mapping_calls += 1
            return role

        config["policy_mapping_fn"] = mapping_fn
        super().setup(config)
        self.league.register_member(first)

    def _new_matchup(self) -> None:
        """Per-episode matchmaking: alternate (main vs PFSP snapshot)
        with (main_exploiter vs main) — the reference's main-exploiter
        games train the exploiter against the CURRENT main while main
        keeps learning from the same episodes."""
        self._matchup_idx += 1
        if self._train_exploiter and self._matchup_idx % 2 == 0:
            self._side_order = [EXPLOITER_POLICY_ID, MAIN_POLICY_ID]
            return
        if self.league.members:
            self._current_opponent = self.league.sample_opponent()
        self._side_order = [MAIN_POLICY_ID, self._current_opponent]

    def training_step(self) -> Dict:
        train_batch = synchronous_parallel_sample(
            worker_set=self.workers,
            max_env_steps=self.config["train_batch_size"],
        )
        self._counters[NUM_ENV_STEPS_SAMPLED] += train_batch.env_steps()
        self._counters[NUM_AGENT_STEPS_SAMPLED] += (
            train_batch.agent_steps()
            if hasattr(train_batch, "agent_steps")
            else train_batch.env_steps()
        )
        # standardize every trainable policy's advantages (PPO
        # semantics, per learner shard)
        pb = getattr(train_batch, "policy_batches", {})
        for pid in self.config.get("policies_to_train") or []:
            if pid in pb:
                b = pb[pid]
                adv = np.asarray(
                    b[SampleBatch.ADVANTAGES], np.float32
                )
                b[SampleBatch.ADVANTAGES] = (
                    (adv - adv.mean()) / max(1e-4, adv.std())
                ).astype(np.float32)
        info = train_one_step(self, train_batch)

        # league bookkeeping from finished episodes' per-agent rewards
        lw = self.workers.local_worker()
        for m in lw.get_metrics():
            self._episode_history.append(m)
            self._episodes_total += 1
            by_pid: Dict[str, float] = {}
            for (aid, pid), r in m.agent_rewards.items():
                by_pid[pid] = by_pid.get(pid, 0.0) + r
            if MAIN_POLICY_ID in by_pid and len(by_pid) == 2:
                opp = next(
                    p for p in by_pid if p != MAIN_POLICY_ID
                )
                # PFSP stats track league snapshots only; exploiter
                # games don't count toward snapshot win rates
                if opp not in self.league.members:
                    continue
                diff = by_pid[MAIN_POLICY_ID] - by_pid[opp]
                outcome = (
                    1.0 if diff > 0 else (0.0 if diff < 0 else 0.5)
                )
                self.league.record_outcome(opp, outcome)

        # schedules (lr/entropy) read global_timestep
        lw.set_global_vars(
            {"timestep": self._counters[NUM_ENV_STEPS_SAMPLED]}
        )

        # snapshot main into the league when it dominates
        if self.league.should_snapshot():
            new_id = self.league.next_member_id()
            weights = lw.policy_map[MAIN_POLICY_ID].get_weights()
            self._add_league_policy(new_id, weights)
            self.league.register_member(new_id)
            self._counters["league_size"] = len(self.league.members)

        out = dict(info)
        out["league"] = self.league.state()
        return out

    def _add_league_policy(self, new_id: str, weights) -> None:
        """Add a frozen snapshot everywhere the mapping fn can route a
        game — including evaluation workers, whose policy_map was built
        before the league grew."""
        lw = self.workers.local_worker()
        cls = type(lw.policy_map[MAIN_POLICY_ID])
        lw.add_policy(
            new_id, cls, self._obs_space, self._act_space,
            weights=weights,
        )
        if self.evaluation_workers is not None:
            ev = self.evaluation_workers.local_worker()
            if ev is not None:
                ev.add_policy(
                    new_id, cls, self._obs_space, self._act_space,
                    weights=weights,
                )

    # -- checkpoint state: league snapshots + matchmaking stats ----------

    def __getstate__(self) -> Dict:
        state = super().__getstate__()
        lw = self.workers.local_worker()
        state["league"] = {
            "members": list(self.league.members),
            "num_snapshots": self.league.num_snapshots,
            "outcomes": {
                k: list(v) for k, v in self.league._outcomes.items()
            },
            "snapshot_weights": {
                m: lw.policy_map[m].get_weights()
                for m in self.league.members
                if m in lw.policy_map
            },
        }
        return state

    def __setstate__(self, state: Dict) -> None:
        league = state.pop("league", None)
        super().__setstate__(state)
        if league:
            lw = self.workers.local_worker()
            for m in league["members"]:
                if m not in lw.policy_map:
                    self._add_league_policy(
                        m, league["snapshot_weights"][m]
                    )
                elif m in league["snapshot_weights"]:
                    lw.policy_map[m].set_weights(
                        league["snapshot_weights"][m]
                    )
            self.league.members = list(league["members"])
            self.league.num_snapshots = league["num_snapshots"]
            self.league._outcomes = {
                k: list(v) for k, v in league["outcomes"].items()
            }
