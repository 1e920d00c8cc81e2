"""RolloutWorker: env + policy + sampler, runnable locally or as an actor.

Counterpart of the reference's ``rllib/evaluation/rollout_worker.py:130``
(``sample :824``, ``learn_on_batch :929``, ``get_weights :1578``,
``set_weights :1616``). The same class is the driver-local learner worker
(policy on the TPU mesh) and the remote CPU rollout actor (policy jitted on
host CPU) — platform selection happens naturally because actor processes pin
``JAX_PLATFORMS=cpu``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ray_tpu.data.sample_batch import (
    DEFAULT_POLICY_ID,
    MultiAgentBatch,
    SampleBatch,
)
from ray_tpu.env.env_context import EnvContext
from ray_tpu.env.multi_agent_env import MultiAgentEnv
from ray_tpu.env.registry import get_env_creator
from ray_tpu.env.vector_env import VectorEnv
from ray_tpu.evaluation.sampler import SyncSampler
from ray_tpu.models.catalog import ModelCatalog
from ray_tpu.util import tracing
from ray_tpu.utils.filter import get_filter


class RolloutWorker:
    def __init__(
        self,
        *,
        env_creator: Optional[Callable] = None,
        policy_cls=None,
        policy_specs: Optional[Dict] = None,
        policy_mapping_fn: Optional[Callable] = None,
        config: Optional[Dict] = None,
        worker_index: int = 0,
        num_workers: int = 0,
        seed: Optional[int] = None,
    ):
        self.config = dict(config or {})
        self.worker_index = worker_index
        self.num_workers = num_workers
        self.global_vars: Dict[str, Any] = {"timestep": 0}
        # chaos harness (docs/resilience.md): None unless the config /
        # RAY_TPU_FAULTS arms faults for this process — zero cost when
        # inert
        from ray_tpu.resilience import faults as faults_lib

        self._fault_injector = faults_lib.from_config(self.config)
        self._num_sample_calls = 0

        env_config = EnvContext(
            self.config.get("env_config") or {},
            worker_index=worker_index,
            num_workers=num_workers,
        )
        seed = (
            seed
            if seed is not None
            else self.config.get("seed")
        )
        if seed is not None:
            seed = seed + worker_index * 1000
            # the one sanctioned global-stream touch: third-party envs
            # (gym classics) draw from np.random at reset/step, and
            # per-worker reproducibility requires seeding that stream
            # here; library code itself threads explicit generators
            # ray-tpu: allow[RTA004] global seed side door for third-party envs
            np.random.seed(seed)

        # ---- build env ----
        self.env = None
        self.vector_env = None
        self.preprocessor = None
        if env_creator is not None:
            num_envs = int(self.config.get("num_envs_per_worker", 1))

            def make_sub_env(vector_index):
                ctx = env_config.copy_with_overrides(
                    vector_index=vector_index
                )
                return env_creator(ctx)

            probe = make_sub_env(0)
            from ray_tpu.env.jax_env import (
                JaxVectorEnv,
                JaxVectorEnvAdapter,
            )

            if isinstance(probe, JaxVectorEnv):
                # JAX-native env on the host (actor) lane: ONE adapter
                # drives all sub-env slots through jitted vmapped
                # step/reset — the same pure functions the device
                # rollout lane scans over, so the two lanes share
                # dynamics and per-env key streams (docs/pipeline.md)
                self._multiagent_env = False
                self.env = probe
                self.vector_env = JaxVectorEnvAdapter(
                    probe, num_envs, seed=seed
                )
            elif isinstance(probe, MultiAgentEnv):
                self.env = probe
                self._multiagent_env = True
            else:
                self._multiagent_env = False
                self.env = probe
                envs = [probe] + [
                    make_sub_env(i) for i in range(1, num_envs)
                ]
                self.vector_env = VectorEnv.vectorize_gym_envs(
                    lambda i: envs[i], num_envs, seed=seed
                )

        # ---- policies ----
        self.policy_map: Dict[str, Any] = {}
        self.policy_mapping_fn = policy_mapping_fn or (
            lambda agent_id, **kw: DEFAULT_POLICY_ID
        )
        self.filters: Dict[str, Any] = {}

        if policy_specs is None and policy_cls is not None:
            obs_space = self.config.get("observation_space") or (
                self.env.observation_space
            )
            act_space = self.config.get("action_space") or (
                self.env.action_space
            )
            policy_specs = {
                DEFAULT_POLICY_ID: (policy_cls, obs_space, act_space, {})
            }

        for pid, (cls, obs_space, act_space, overrides) in (
            policy_specs or {}
        ).items():
            pol_config = {
                **self.config,
                **(overrides or {}),
                "worker_index": worker_index,
                "num_workers": num_workers,
            }
            prep = ModelCatalog.get_preprocessor_for_space(obs_space)
            eff_obs_space = prep.observation_space
            if pid == DEFAULT_POLICY_ID or self.preprocessor is None:
                self.preprocessor = prep
            # Rollout workers (worker_index > 0) keep single-device CPU
            # meshes; the local worker builds its learner mesh from config.
            if worker_index > 0:
                pol_config.pop("_mesh", None)
            with tracing.phase("setup:policy", policy=pid):
                self.policy_map[pid] = cls(
                    eff_obs_space, act_space, pol_config
                )
            self.filters[pid] = get_filter(
                self.config.get("observation_filter", "NoFilter"),
                eff_obs_space.shape,
            )

        # ---- input reader (external envs / policy server) ----
        # config["input"] may be a callable(ioctx) -> reader with a
        # .next() method (reference offline/io_context + the
        # PolicyServerInput wiring); strings are offline paths handled
        # by the offline algorithms.
        self.input_reader = None
        inp = self.config.get("input")
        if callable(inp):
            from types import SimpleNamespace

            self.input_reader = inp(
                SimpleNamespace(
                    worker=self,
                    config=self.config,
                    worker_index=worker_index,
                )
            )

        # ---- sampler ----
        self.sampler = None
        if (
            self.input_reader is None
            and self.vector_env is not None
            and self.policy_map
        ):
            pid = DEFAULT_POLICY_ID
            sampler_cls = SyncSampler
            from ray_tpu.evaluation.sampler import AsyncSampler

            if self.config.get("sample_async"):
                sampler_cls = AsyncSampler
            cb_cls = self.config.get("callbacks_class")
            self.callbacks = cb_cls() if cb_cls else None
            self.sampler = sampler_cls(
                vector_env=self.vector_env,
                policy=self.policy_map[pid],
                callbacks=self.callbacks,
                preprocessor=self.preprocessor,
                obs_filter=self.filters.get(pid),
                rollout_fragment_length=int(
                    self.config.get("rollout_fragment_length", 200)
                ),
                batch_mode=self.config.get(
                    "batch_mode", "truncate_episodes"
                ),
                episode_horizon=self.config.get("horizon"),
                clip_actions=self.config.get("clip_actions", False),
                normalize_actions=self.config.get(
                    "normalize_actions", True
                ),
                flush_on_episode_end=not self.config.get(
                    "_fixed_unrolls", False
                ),
            )
        elif env_creator is not None and self._multiagent_env:
            from ray_tpu.evaluation.multi_agent_sampler import (
                MultiAgentSyncSampler,
            )

            self.sampler = MultiAgentSyncSampler(
                env=self.env,
                policy_map=self.policy_map,
                policy_mapping_fn=self.policy_mapping_fn,
                preprocessors={
                    pid: ModelCatalog.get_preprocessor_for_space(
                        p.observation_space
                    )
                    for pid, p in self.policy_map.items()
                },
                obs_filters=self.filters,
                rollout_fragment_length=int(
                    self.config.get("rollout_fragment_length", 200)
                ),
                batch_mode=self.config.get(
                    "batch_mode", "truncate_episodes"
                ),
            )

    # -- sampling --------------------------------------------------------

    def sample(self):
        """reference rollout_worker.py:824 (+ the output-writer wiring
        of reference offline/output_writer.py: every sampled batch is
        mirrored to the configured offline store)."""
        self._num_sample_calls += 1
        if self._fault_injector is not None:
            # deterministic chaos: may delay this call, or hard-exit
            # the process (exactly like a preemption — no exception,
            # no cleanup, the driver sees an actor-death error)
            self._fault_injector.on_sample(
                self.worker_index, self._num_sample_calls
            )
        with tracing.start_span(
            "rollout:sample", worker_index=self.worker_index
        ) as span:
            if self.input_reader is not None:
                batch = self.input_reader.next()
            else:
                assert self.sampler is not None, "worker has no env"
                batch = self.sampler.sample()
            span.set_attribute("env_steps", int(batch.env_steps()))
        out = self.config.get("output")
        if out:
            if not hasattr(self, "_output_writer"):
                from ray_tpu.offline import JsonWriter

                self._output_writer = JsonWriter(
                    out,
                    max_file_size=int(
                        self.config.get(
                            "output_max_file_size", 64 * 1024 * 1024
                        )
                    ),
                )
            self._output_writer.write(batch)
        return batch

    def sample_with_count(self):
        batch = self.sample()
        return batch, batch.env_steps()

    # -- preemption / drain protocol (docs/resilience.md) ----------------

    def preemption_notice(self) -> Optional[float]:
        """Seconds of grace left before this worker's preemption kills
        the process, or None. The FleetController polls this off the
        critical path. Two sources: the injected chaos deadline, and —
        absent an injector notice — the provider stub
        (``resilience/provider_notice.py``: env var / file probe, the
        same surface serving replicas poll), which is where a real
        cloud eviction endpoint plugs in."""
        if self._fault_injector is not None:
            grace = self._fault_injector.preemption_notice()
            if grace is not None:
                return grace
        from ray_tpu.resilience import provider_notice

        return provider_notice.probe()

    def drain_for_preemption(self) -> Dict[str, Any]:
        """Graceful exit: ship everything the fleet would otherwise
        lose with this worker — flushed observation-filter deltas and
        the episodes not yet harvested. Actor calls execute in order,
        so by the time this returns every previously submitted
        ``sample`` has completed and its result is already in the
        object store (the manager harvests those normally). After the
        drain the worker answers no more sample calls usefully; the
        driver removes it from rotation and reaps the process."""
        self._draining = True
        return {
            "filters": self.get_filters(flush_after=True),
            "metrics": self.get_metrics(),
            "num_sample_calls": self._num_sample_calls,
        }

    def add_policy(
        self,
        policy_id: str,
        policy_cls,
        observation_space,
        action_space,
        config_overrides: Optional[Dict] = None,
        weights=None,
    ) -> None:
        """Add a policy at runtime (reference Algorithm.add_policy →
        rollout_worker add_policy; league builders snapshot into the
        live policy map this way)."""
        pol_config = {
            **self.config,
            **(config_overrides or {}),
            "worker_index": self.worker_index,
            "num_workers": self.num_workers,
        }
        if self.worker_index > 0:
            pol_config.pop("_mesh", None)
        prep = ModelCatalog.get_preprocessor_for_space(
            observation_space
        )
        self.policy_map[policy_id] = policy_cls(
            prep.observation_space, action_space, pol_config
        )
        self.filters[policy_id] = get_filter(
            self.config.get("observation_filter", "NoFilter"),
            prep.observation_space.shape,
        )
        if weights is not None:
            self.policy_map[policy_id].set_weights(weights)

    def set_policy_mapping_fn(self, fn: Callable) -> None:
        """Swap the mapping fn; takes effect at the NEXT episode reset
        (the sampler re-consults it per episode) — remapping agents
        mid-episode would train a trajectory's tail under a policy
        that didn't produce its ACTION_LOGP/VF_PREDS."""
        self.policy_mapping_fn = fn
        if self.sampler is not None and hasattr(
            self.sampler, "policy_mapping_fn"
        ):
            self.sampler.policy_mapping_fn = fn

    def get_metrics(self) -> List:
        if self.input_reader is not None and hasattr(
            self.input_reader, "get_metrics"
        ):
            return self.input_reader.get_metrics()
        return self.sampler.get_metrics() if self.sampler else []

    # -- learning --------------------------------------------------------

    def policy(self, pid: str = DEFAULT_POLICY_ID):
        return self.policy_map[pid]

    def learn_on_batch(self, samples) -> Dict:
        """reference rollout_worker.py:929. Policies outside
        config["policies_to_train"] (league opponents, frozen experts)
        are skipped."""
        to_train = self.config.get("policies_to_train")
        if isinstance(samples, MultiAgentBatch):
            info = {}
            for pid, batch in samples.policy_batches.items():
                if pid in self.policy_map and (
                    to_train is None or pid in to_train
                ):
                    info[pid] = self.policy_map[pid].learn_on_batch(batch)
            return info
        return {
            DEFAULT_POLICY_ID: self.policy_map[
                DEFAULT_POLICY_ID
            ].learn_on_batch(samples)
        }

    def compute_gradients(self, samples):
        if isinstance(samples, MultiAgentBatch):
            samples = samples.policy_batches[DEFAULT_POLICY_ID]
        return self.policy_map[DEFAULT_POLICY_ID].compute_gradients(samples)

    # -- DD-PPO worker-side learning (reference ddppo.py:331
    # _sample_and_train_torch_distributed, split into the sample/grad
    # phases the driver-mediated allreduce loop drives) ----------------

    def sample_and_hold(self) -> int:
        """Sample + postprocess a batch and keep it locally for the
        decentralized SGD epochs; returns env steps collected."""
        batch = self.sample()
        if isinstance(batch, MultiAgentBatch):
            batch = batch.policy_batches[DEFAULT_POLICY_ID]
        if SampleBatch.ADVANTAGES in batch:
            adv = np.asarray(
                batch[SampleBatch.ADVANTAGES], np.float32
            )
            batch[SampleBatch.ADVANTAGES] = (
                (adv - adv.mean()) / max(1e-4, adv.std())
            ).astype(np.float32)
        self._held_batch = batch
        return batch.env_steps()

    def grads_on_held_batch(self):
        """One gradient over the locally held batch (one decentralized
        SGD epoch; the driver allreduces across workers). A restarted
        actor has no held batch — resample rather than crash the run."""
        if getattr(self, "_held_batch", None) is None:
            self.sample_and_hold()
        return self.policy_map[DEFAULT_POLICY_ID].compute_gradients(
            self._held_batch
        )

    def apply_gradients(self, grads) -> None:
        self.policy_map[DEFAULT_POLICY_ID].apply_gradients(grads)

    # -- weights & filters ----------------------------------------------

    def get_weights(
        self,
        policies: Optional[List[str]] = None,
        inference_only: bool = False,
    ) -> Dict:
        return {
            pid: (
                p.get_inference_weights()
                if inference_only
                else p.get_weights()
            )
            for pid, p in self.policy_map.items()
            if policies is None or pid in policies
        }

    def set_weights(self, weights: Dict, global_vars: Optional[Dict] = None):
        for pid, w in weights.items():
            if pid in self.policy_map:
                self.policy_map[pid].set_weights(w)
        if global_vars:
            self.set_global_vars(global_vars)

    def get_filters(self, flush_after: bool = False) -> Dict:
        out = {
            pid: f.as_serializable() for pid, f in self.filters.items()
        }
        if flush_after:
            for f in self.filters.values():
                f.clear_buffer()
        return out

    def sync_filters(self, new_filters: Dict) -> None:
        for pid, f in new_filters.items():
            if pid in self.filters:
                self.filters[pid].sync(f)

    def set_global_vars(self, global_vars: Dict) -> None:
        self.global_vars.update(global_vars)
        for p in self.policy_map.values():
            p.on_global_var_update(global_vars)

    # -- state / misc ----------------------------------------------------

    def save(self) -> Dict:
        return {
            "policy_states": {
                pid: p.get_state() for pid, p in self.policy_map.items()
            },
            "filters": self.get_filters(),
        }

    def restore(self, state: Dict) -> None:
        for pid, s in state.get("policy_states", {}).items():
            if pid in self.policy_map:
                self.policy_map[pid].set_state(s)
        self.sync_filters(state.get("filters", {}))

    def apply(self, fn: Callable, *args, **kwargs):
        """reference rollout_worker.py apply (used by foreach_worker)."""
        return fn(self, *args, **kwargs)

    def foreach_env(self, fn: Callable) -> List:
        if self.vector_env is None:
            return [fn(self.env)] if self.env else []
        return [fn(e) for e in self.vector_env.get_sub_environments()]

    def foreach_policy(self, fn: Callable) -> List:
        return [fn(p, pid) for pid, p in self.policy_map.items()]

    def stop(self) -> None:
        # stop the async sampling thread BEFORE closing its envs
        if self.sampler is not None and hasattr(self.sampler, "stop"):
            try:
                self.sampler.stop()
            except Exception:
                pass
        if self.input_reader is not None and hasattr(
            self.input_reader, "shutdown"
        ):
            try:
                self.input_reader.shutdown()
            except Exception:
                pass
        if self.vector_env is not None:
            for e in self.vector_env.get_sub_environments():
                try:
                    e.close()
                except Exception:
                    pass

    def ping(self) -> str:
        return "pong"
