"""tune.run: experiment runner.

Counterpart of the reference's ``ray/tune/tune.py:118`` (tune.run) +
``tune/execution/trial_runner.py:226`` (TrialRunner.step :793) +
``tune/execution/ray_trial_executor.py`` (trials as concurrently
scheduled actors).

Two execution modes:
- **parallel** (default for multi-trial experiments): each trial is a
  dedicated non-daemon actor process hosting the Trainable; up to
  ``max_concurrent`` trials advance truly concurrently, results are
  processed as they complete (schedulers see them event-driven, like
  the reference's RayTrialExecutor event loop). Trial actors run on the
  CPU JAX platform (the chip belongs to the driver), so this is the
  searcher/scheduler path, not the single-big-run path.
- **sequential in-process** (``parallel=False``, or one trial): trials
  time-slice the driver — the mode that owns the real TPU mesh.
"""

from __future__ import annotations

import os
import pickle
import traceback
from typing import Any, Dict, List, Optional, Type, Union

import ray_tpu as ray
from ray_tpu.tune.schedulers import (
    CONTINUE,
    STOP,
    FIFOScheduler,
    TrialScheduler,
)
from ray_tpu.tune.search import BasicVariantGenerator
from ray_tpu.tune.trainable import Trainable
from ray_tpu.tune.search import Domain as SearchDomain
from ray_tpu.tune.trial import (
    ERROR,
    PENDING,
    RUNNING,
    TERMINATED,
    Trial,
)


@ray.remote
class _TrialActor:
    """One trial's Trainable, hosted in a dedicated process
    (reference ray_trial_executor.py wraps trainables the same way)."""

    def __init__(self, trainable_cls, config):
        self._t = trainable_cls(config=config)

    def train(self):
        return self._t.train()

    def save(self, checkpoint_dir=None):
        return self._t.save(checkpoint_dir)

    def restore(self, path):
        self._t.restore(path)

    def stop(self):
        self._t.stop()

    def get_exploit_state(self):
        return self._t.get_exploit_state()

    def apply_exploit(self, state, scalars):
        self._t.apply_exploit(state, scalars)


class _RemoteTrainableProxy:
    """Synchronous facade over a _TrialActor, so schedulers (PBT
    exploit protocol, checkpointing) treat remote and in-process
    trials identically. Consumed refs are freed immediately — store
    entries otherwise live until driver shutdown, and exploit states
    carry full model weights."""

    def __init__(self, actor):
        self.actor = actor

    def _call(self, method, *args):
        ref = method.remote(*args)
        try:
            return ray.get(ref)
        finally:
            ray.free([ref])

    def save(self, checkpoint_dir=None):
        return self._call(self.actor.save, checkpoint_dir)

    def restore(self, path):
        self._call(self.actor.restore, path)

    def stop(self):
        self._call(self.actor.stop)

    def get_exploit_state(self):
        return self._call(self.actor.get_exploit_state)

    def apply_exploit(self, state, scalars):
        self._call(self.actor.apply_exploit, state, scalars)


class ExperimentAnalysis:
    """reference ray/tune/analysis/experiment_analysis.py."""

    def __init__(self, trials: List[Trial],
                 metric: str = "episode_reward_mean",
                 mode: str = "max"):
        self.trials = trials
        self.default_metric = metric
        self.default_mode = mode

    def get_best_trial(
        self, metric: Optional[str] = None, mode: Optional[str] = None
    ) -> Optional[Trial]:
        metric = metric or self.default_metric
        mode = mode or self.default_mode
        best, best_v = None, None
        for t in self.trials:
            v = t.last_result.get(metric)
            if v is None:
                continue
            if (
                best_v is None
                or (mode == "max" and v > best_v)
                or (mode == "min" and v < best_v)
            ):
                best, best_v = t, v
        return best

    @property
    def best_config(self) -> Optional[Dict]:
        t = self.get_best_trial()
        return t.config if t else None

    @property
    def results(self) -> Dict[str, Dict]:
        return {t.trial_id: t.last_result for t in self.trials}

    def dataframe(self) -> List[Dict]:
        return [
            {"trial_id": t.trial_id, **t.last_result}
            for t in self.trials
        ]


class TrialRunner:
    """reference tune/execution/trial_runner.py:226."""

    def __init__(
        self,
        trainable_cls,
        trials: List[Trial],
        scheduler: Optional[TrialScheduler] = None,
        max_iterations: int = 100,
        checkpoint_freq: int = 0,
        local_dir: Optional[str] = None,
        callbacks: Optional[List] = None,
        parallel: bool = False,
        max_concurrent: Optional[int] = None,
        experiment_dir: Optional[str] = None,
        resume: bool = False,
        search_alg=None,
        num_samples: int = 1,
        trial_name: str = "trial",
        stopping_criterion: Optional[Dict] = None,
        base_config: Optional[Dict] = None,
        sync_config=None,
        mesh_slots: Optional[List] = None,
    ):
        self.trainable_cls = trainable_cls
        self.trials = trials
        self.scheduler = scheduler or FIFOScheduler()
        self.max_iterations = max_iterations
        self.checkpoint_freq = checkpoint_freq
        self.local_dir = local_dir
        self.callbacks = callbacks or []
        self.parallel = parallel
        self.max_concurrent = max_concurrent or (os.cpu_count() or 4)
        self._in_flight: Dict = {}  # train ref -> trial
        self._parallel_proven = False  # any actor created successfully
        self.experiment_dir = experiment_dir
        # ask/tell suggestion mode (reference SearchGenerator wrapping
        # a Searcher): trials are created lazily from search_alg up to
        # num_samples, and results are told back
        self.search_alg = search_alg
        self.search_num_samples = num_samples
        self._search_stop = dict(stopping_criterion or {})
        self._search_name = trial_name
        self._search_base = dict(base_config or {})
        self._search_exhausted = False
        self.sync_config = sync_config
        # disjoint per-trial submeshes (mesh-sharded concurrent mode)
        self.mesh_slots = mesh_slots
        self._trial_slot: Dict = {}
        if resume:
            self._maybe_sync_down()
            self._restore_experiment_state()

    def _maybe_sync_down(self) -> None:
        """Pull the mirrored experiment dir before resuming when the
        local one is missing (head died; the upload_dir survived —
        reference tune/syncer.py restore path)."""
        sc = self.sync_config
        if (
            sc is None
            or sc.syncer is None
            or not self.experiment_dir
        ):
            return
        if not os.path.exists(
            os.path.join(self.experiment_dir, "experiment_state.pkl")
        ):
            remote = self._remote_dir(sc)
            # the SYNCER owns remote-path semantics (an object-store
            # backend answers for s3:// URIs; never os.path them here)
            if sc.syncer.exists(remote):
                sc.syncer.sync_down(remote, self.experiment_dir)

    def _remote_dir(self, sc) -> str:
        return os.path.join(
            sc.upload_dir, os.path.basename(self.experiment_dir)
        )

    def _maybe_sync_up(self) -> None:
        sc = self.sync_config
        if (
            sc is None
            or sc.syncer is None
            or not self.experiment_dir
            or not os.path.exists(self.experiment_dir)
        ):
            return
        import time as _time

        # the final save (all trials terminal) always syncs, or a
        # throttled last write would leave the mirror stale
        force = all(
            t.status in (TERMINATED, ERROR) for t in self.trials
        )
        now = _time.monotonic()
        last = getattr(self, "_last_sync_up", 0.0)
        if not force and now - last < sc.sync_period_s:
            return  # throttle (SyncConfig.sync_period_s)
        self._last_sync_up = now
        sc.syncer.sync_up(self.experiment_dir, self._remote_dir(sc))

    def _maybe_ask_searcher(self) -> None:
        if self.search_alg is None:
            return
        # only ask for as many live trials as can actually run: TPE-
        # style searchers model completed results, so over-asking up
        # front would degrade them to random search
        cap = self.max_concurrent if self.parallel else 1
        while len(self.trials) < self.search_num_samples and (
            sum(
                1
                for t in self.trials
                if t.status not in (TERMINATED, ERROR)
            )
            < cap
        ):
            trial_id = (
                f"{self._search_name}_{len(self.trials):05d}"
            )
            config = self.search_alg.suggest(trial_id)
            if config is None:
                # searcher exhausted before num_samples: record it so
                # is_finished() doesn't wait for trials that will
                # never exist
                self._search_exhausted = True
                break
            # constants from tune.run(config=...) merge under the
            # suggested keys (real-Tune semantics: config is both the
            # space template and the shared base)
            merged = {**self._search_base, **config}
            self.trials.append(
                Trial(
                    self._search_name,
                    merged,
                    stopping_criterion=self._search_stop,
                    trial_id=trial_id,
                )
            )

    # -- experiment-state durability (driver-restart resume) ---------------
    #
    # The reference checkpoints TrialRunner state to
    # experiment_state-*.json in the experiment dir
    # (tune/execution/trial_runner.py checkpoint()/resume()); a killed
    # driver resumes with tune.run(..., resume=True). Same protocol
    # here: per-trial status/last_result/checkpoint_path snapshots,
    # written atomically after every processed result.

    @property
    def _state_path(self) -> Optional[str]:
        if not self.experiment_dir:
            return None
        return os.path.join(self.experiment_dir, "experiment_state.pkl")

    def _save_experiment_state(self) -> None:
        path = self._state_path
        if not path:
            return
        os.makedirs(self.experiment_dir, exist_ok=True)
        state = {
            t.trial_id: {
                "status": t.status
                if t.status in (TERMINATED, ERROR)
                else PENDING,
                "config": t.config,
                "last_result": t.last_result,
                "checkpoint_path": t.checkpoint_path,
                "error": t.error,
            }
            for t in self.trials
        }
        from ray_tpu.util.atomic_io import atomic_write

        # atomic + fsync'd: a crash never corrupts (or un-publishes)
        # the experiment state a resume depends on
        atomic_write(path, lambda f: pickle.dump(state, f))
        self._maybe_sync_up()

    def _restore_experiment_state(self) -> None:
        path = self._state_path
        if not path or not os.path.exists(path):
            return
        with open(path, "rb") as f:
            saved = pickle.load(f)
        for trial in self.trials:
            s = saved.get(trial.trial_id)
            if s is None:
                continue
            trial.last_result = s["last_result"]
            trial.checkpoint_path = s["checkpoint_path"]
            trial.error = s["error"]
            trial.status = s["status"]
            # PENDING trials with a checkpoint restart from it (the
            # restore happens when their runner starts)

    def is_finished(self) -> bool:
        if (
            self.search_alg is not None
            and not self._search_exhausted
            and len(self.trials) < self.search_num_samples
        ):
            return False
        return all(
            t.status in (TERMINATED, ERROR) for t in self.trials
        )

    # -- shared result handling -------------------------------------------

    def _trial_checkpoint_dir(self, trial: Trial) -> Optional[str]:
        """Checkpoints land under the experiment dir when one exists,
        so experiment-state persistence and the syncer cover them
        (reference: trial logdirs inside the experiment dir)."""
        if not self.experiment_dir:
            return None
        return os.path.join(
            self.experiment_dir,
            trial.trial_id,
            f"checkpoint_{trial.last_result.get('training_iteration', 0):06d}",
        )

    def _process_result(self, trial: Trial, result: Dict) -> bool:
        """Record + schedule one result. Returns True if the trial
        should continue training."""
        trial.last_result = result
        trial.results.append(result)
        if self.search_alg is not None:
            self.search_alg.on_trial_result(trial.trial_id, result)
        for cb in self.callbacks:
            cb(trial, result)
        if self.checkpoint_freq and (
            result["training_iteration"] % self.checkpoint_freq == 0
        ):
            trial.checkpoint_path = trial.runner.save(
                self._trial_checkpoint_dir(trial)
            )
        decision = self.scheduler.on_trial_result(self, trial, result)
        if (
            decision == STOP
            or trial.should_stop(result)
            or result["training_iteration"] >= self.max_iterations
        ):
            trial.status = TERMINATED
            if self.search_alg is not None:
                self.search_alg.on_trial_complete(
                    trial.trial_id, result
                )
            self.scheduler.on_trial_complete(self, trial, result)
            if self.checkpoint_freq:
                trial.checkpoint_path = trial.runner.save(
                self._trial_checkpoint_dir(trial)
            )
            self._cleanup_trial(trial)
            self._save_experiment_state()
            return False
        self._save_experiment_state()
        return True

    def _fail_trial(self, trial: Trial, err: str) -> None:
        trial.status = ERROR
        trial.error = err
        if self.search_alg is not None:
            self.search_alg.on_trial_complete(
                trial.trial_id, error=True
            )
        # schedulers must learn about errored trials too — a
        # synchronous rung (HyperBand) would otherwise wait on the
        # dead trial's report forever
        self.scheduler.on_trial_complete(
            self, trial, trial.last_result or {}
        )
        self._cleanup_trial(trial)
        self._save_experiment_state()

    def step(self) -> None:
        self._maybe_ask_searcher()
        if self.parallel:
            self._step_parallel()
        elif self.mesh_slots:
            self._step_mesh_concurrent()
        else:
            self._step_sequential()

    # -- sequential in-process mode ----------------------------------------

    def _step_sequential(self) -> None:
        """Advance every live trial by one training iteration
        (reference trial_runner.py:793)."""
        for trial in self.trials:
            if trial.status in (TERMINATED, ERROR):
                continue
            if trial.runner is None:
                try:
                    trial.runner = self.trainable_cls(
                        config=trial.config
                    )
                    if trial.checkpoint_path:  # driver-restart resume
                        trial.runner.restore(trial.checkpoint_path)
                    trial.status = RUNNING
                except Exception:
                    self._fail_trial(trial, traceback.format_exc())
                    continue
            try:
                result = trial.runner.train()
            except Exception:
                self._fail_trial(trial, traceback.format_exc())
                continue
            self._process_result(trial, result)

    # -- mesh-sharded concurrent mode ---------------------------------------

    def _step_mesh_concurrent(self) -> None:
        """Advance live trials ONE iteration each, concurrently on
        threads, every trial jitted onto its own disjoint submesh
        (``config["_mesh"]``). Device compute overlaps across slots;
        a PBT population of S slot-sized trials costs ~1x wall clock
        instead of S x (the round-2/3 time-slicing)."""
        from concurrent.futures import ThreadPoolExecutor

        n_slots = len(self.mesh_slots)
        # assign free slots to pending trials
        used = {
            s
            for t, s in self._trial_slot.items()
            if t.status == RUNNING
        }
        for trial in self.trials:
            if trial.status != PENDING:
                continue
            free = next(
                (s for s in range(n_slots) if s not in used), None
            )
            if free is None:
                break
            try:
                cfg = dict(trial.config)
                cfg["_mesh"] = self.mesh_slots[free]
                trial.runner = self.trainable_cls(config=cfg)
                if trial.checkpoint_path:
                    trial.runner.restore(trial.checkpoint_path)
                trial.status = RUNNING
                self._trial_slot[trial] = free
                used.add(free)
            except Exception:
                self._fail_trial(trial, traceback.format_exc())
        live = [t for t in self.trials if t.status == RUNNING]
        if not live:
            return
        with ThreadPoolExecutor(max_workers=len(live)) as ex:
            futures = [
                (t, ex.submit(t.runner.train)) for t in live
            ]
            # collect EVERY result before processing any: schedulers
            # (PBT exploit) read other trials' runner state, which must
            # not race a train() still executing on a pool thread
            outcomes = []
            for trial, fut in futures:
                try:
                    outcomes.append((trial, fut.result(), None))
                except Exception:
                    outcomes.append(
                        (trial, None, traceback.format_exc())
                    )
        for trial, result, err in outcomes:
            if err is not None:
                self._fail_trial(trial, err)
                self._trial_slot.pop(trial, None)
                continue
            self._process_result(trial, result)
            if trial.status in (TERMINATED, ERROR):
                self._trial_slot.pop(trial, None)

    # -- parallel actor mode -------------------------------------------------

    def _start_trial_actor(self, trial: Trial) -> None:
        try:
            actor = _TrialActor.options(daemon=False).remote(
                self.trainable_cls, trial.config
            )
        except Exception:
            # Typically an unpicklable trainable/config. Before any
            # actor has proven viable, degrade gracefully to the
            # in-process mode rather than failing the experiment.
            if not self._parallel_proven:
                import warnings

                warnings.warn(
                    "trial actor creation failed "
                    f"({traceback.format_exc(limit=1).strip()}); "
                    "falling back to in-process sequential trials — "
                    "pass parallel=False to silence this"
                )
                self.parallel = False
            else:
                self._fail_trial(trial, traceback.format_exc())
            return
        self._parallel_proven = True
        trial.runner = _RemoteTrainableProxy(actor)
        if trial.checkpoint_path:  # driver-restart resume
            try:
                trial.runner.restore(trial.checkpoint_path)
            except Exception:
                self._fail_trial(trial, traceback.format_exc())
                return
        trial.status = RUNNING
        self._in_flight[actor.train.remote()] = trial

    def _step_parallel(self) -> None:
        """Event-driven execution over trial actors (reference
        ray_trial_executor.py event loop): keep up to max_concurrent
        trials running, process results as they complete."""
        live = set(self._in_flight.values())
        for trial in self.trials:
            if len(live) >= self.max_concurrent or not self.parallel:
                break
            if trial.status == PENDING and trial not in live:
                self._start_trial_actor(trial)
                if trial.status == RUNNING:
                    live.add(trial)
        if not self._in_flight:
            return
        ready, _ = ray.wait(
            list(self._in_flight.keys()), num_returns=1, timeout=10.0
        )
        for ref in ready:
            trial = self._in_flight.pop(ref)
            try:
                result = ray.get(ref)
            except Exception:
                self._fail_trial(trial, traceback.format_exc())
                continue
            finally:
                ray.free([ref])
            if self._process_result(trial, result):
                self._in_flight[
                    trial.runner.actor.train.remote()
                ] = trial

    def cleanup(self) -> None:
        """Stop any still-live trials (crash/interrupt path)."""
        for ref, trial in list(self._in_flight.items()):
            ray.free([ref])
        self._in_flight.clear()
        for trial in self.trials:
            if trial.runner is not None:
                self._cleanup_trial(trial)

    def _cleanup_trial(self, trial: Trial) -> None:
        if trial.runner is not None:
            try:
                trial.runner.stop()
            except Exception:
                pass
            if isinstance(trial.runner, _RemoteTrainableProxy):
                try:
                    ray.kill(trial.runner.actor)
                except Exception:
                    pass
            trial.runner = None


def run(
    run_or_experiment: Union[str, Type],
    *,
    config: Optional[Dict] = None,
    stop: Optional[Dict] = None,
    num_samples: int = 1,
    scheduler: Optional[TrialScheduler] = None,
    checkpoint_freq: int = 0,
    local_dir: Optional[str] = None,
    metric: str = "episode_reward_mean",
    mode: str = "max",
    max_iterations: int = 100,
    callbacks: Optional[List] = None,
    verbose: int = 1,
    seed: int = 0,
    parallel: Optional[bool] = None,
    max_concurrent_trials: Optional[int] = None,
    name: Optional[str] = None,
    resume: bool = False,
    search_alg=None,
    resources_per_trial: Optional[Dict] = None,
    sync_config=None,
    raise_on_failed_trial: bool = True,
) -> ExperimentAnalysis:
    """reference tune/tune.py:118.

    parallel: None (default) runs multi-trial experiments as concurrent
    actors and single-trial experiments in-process (where they own the
    TPU mesh). Force with True/False.

    resources_per_trial: {"TPU": n} (n > 0) declares accelerator
    trials: they run IN-PROCESS, time-slicing the driver's mesh
    across the population — each trainable jits onto the real TPU
    devices (a chip belongs to one process at a time and cannot be
    claimed by concurrent trial processes, so time-slicing is the
    single-host analog of the
    reference's GPU allocation via placement groups,
    tune/execution/ray_trial_executor.py). CPU-only trials keep the
    concurrent-actor path.

    resume: reattach to a previous run of the same experiment
    (``local_dir``/``name``): trials that finished stay finished,
    interrupted trials restart from their latest checkpoint (requires
    ``checkpoint_freq``; reference trial_runner.py resume()). Trial
    identity is positional — the deterministic variant generator must
    see the same config/num_samples/seed.
    """
    if isinstance(run_or_experiment, str):
        from ray_tpu.algorithms.registry import get_algorithm_class

        trainable_cls = get_algorithm_class(run_or_experiment)
        exp_name = name or run_or_experiment
    elif isinstance(run_or_experiment, type) and issubclass(
        run_or_experiment, Trainable
    ):
        trainable_cls = run_or_experiment
        exp_name = name or trainable_cls.__name__
    elif callable(run_or_experiment):
        # plain function trainable: tune.run(train_fn) + tune.report
        # (reference function_trainable.wrap_function)
        from ray_tpu.tune.function_trainable import wrap_function

        trainable_cls = wrap_function(run_or_experiment)
        exp_name = name or trainable_cls.__name__
    else:
        trainable_cls = run_or_experiment
        exp_name = name or trainable_cls.__name__

    if resume and not local_dir:
        raise ValueError(
            "tune.run(resume=True) needs local_dir: experiment state "
            "lives in <local_dir>/<name>/experiment_state.pkl"
        )
    stop = dict(stop or {})
    max_iters = int(stop.pop("training_iteration", max_iterations))
    if search_alg is not None:
        # suggestion mode: trials are created lazily from the searcher
        # (reference SearchGenerator); config is its space template
        trials = []
        parallel = bool(parallel) if parallel is not None else (
            num_samples > 1
        )
    else:
        gen = BasicVariantGenerator(config or {}, num_samples, seed)
        trials = [
            Trial(
                exp_name,
                v,
                stopping_criterion=stop,
                # stable across driver restarts so resume can match
                # trials to their saved state
                trial_id=f"{exp_name}_{i:05d}",
            )
            for i, v in enumerate(iter(gen.next_variant, None))
        ]
        if parallel is None:
            parallel = len(trials) > 1
    mesh_slots = None
    if resources_per_trial and resources_per_trial.get("TPU", 0) > 0:
        # Accelerator trials run in-process (concurrent actor
        # PROCESSES cannot share the chip claim), but they need not
        # time-slice: with enough devices the mesh partitions into
        # disjoint per-trial submeshes and trials run CONCURRENTLY on
        # threads — each jits onto its own devices, host python
        # interleaves, device compute overlaps (the reference's
        # fractional-GPU trial packing, ray_trial_executor.py resource
        # allocation, the TPU way). One device (or one slot's worth)
        # falls back to sequential time-slicing.
        parallel = False
        import jax

        per = int(resources_per_trial["TPU"])
        devs = jax.devices()
        slots = len(devs) // per if per >= 1 else 0
        # fractional requests (TPU: 0.5) keep the time-slicing path:
        # a submesh needs at least one whole device
        if per >= 1 and slots >= 2 and len(trials or []) != 1:
            from ray_tpu import sharding as sharding_lib

            mesh_slots = [
                sharding_lib.get_mesh(
                    devices=devs[i * per : (i + 1) * per]
                )
                for i in range(slots)
            ]
    experiment_dir = (
        os.path.join(local_dir, exp_name) if local_dir else None
    )
    runner = TrialRunner(
        trainable_cls,
        trials,
        scheduler=scheduler,
        max_iterations=max_iters,
        checkpoint_freq=checkpoint_freq,
        local_dir=local_dir,
        callbacks=callbacks,
        parallel=parallel,
        max_concurrent=max_concurrent_trials,
        experiment_dir=experiment_dir,
        resume=resume,
        search_alg=search_alg,
        num_samples=num_samples,
        trial_name=exp_name,
        stopping_criterion=stop,
        # constants shared by every suggested trial; Domain entries are
        # excluded (in suggestion mode the searcher owns the space)
        base_config={
            k: v
            for k, v in (config or {}).items()
            if not isinstance(v, SearchDomain)
        },
        sync_config=sync_config,
        mesh_slots=mesh_slots,
    )
    try:
        while not runner.is_finished():
            runner.step()
            if verbose:
                live = sum(1 for t in trials if t.status == RUNNING)
                best = ExperimentAnalysis(
                    trials, metric, mode
                ).get_best_trial()
                if best is not None:
                    print(
                        f"[tune] live={live} "
                        f"best[{metric}]="
                        f"{best.last_result.get(metric)}"
                    )
    finally:
        # Crash/interrupt path: without this, live non-daemon trial
        # actors (whole Trainables) outlive the experiment.
        runner.cleanup()
    errored = [t for t in trials if t.status == ERROR]
    if errored and raise_on_failed_trial:
        raise RuntimeError(
            f"{len(errored)} trial(s) errored; first: "
            f"{errored[0].error}"
        )
    return ExperimentAnalysis(trials, metric, mode)
