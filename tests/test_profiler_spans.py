"""One clock (PR 25): the program's spans, its programs' family names
and its named scopes in the JAX profiler's own trace.

A ``jax.profiler`` session is the switch: while one is live every span
site of ``ray_tpu/util/tracing.py`` is a ``TraceAnnotation`` on the
``/host:CPU`` plane of the session's ``.xplane.pb``, whether or not
``tracing.enable()`` was ever called; with neither on a span site
yields the null span and a process that never imported jax still has
not. ``sharded_jit`` names the function it hands to ``jax.jit`` after
its label's family, and the device programs carry ``jax.named_scope``s
at their stage boundaries (checked on the lowered text: a CPU trace
does not show them)."""

import glob
import os
import subprocess
import sys
import time

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from ray_tpu.sharding.compile import (  # noqa: E402
    ShardedFunction,
    compile_stats,
    label_family,
    sharded_jit,
)
from ray_tpu.util import tracing  # noqa: E402

LABEL = "replay_insert[a:3]"


def _host_events(log_dir):
    """``[(name, start_ns, duration_ns, stats)]`` of every host line of
    the newest ``.xplane.pb`` under ``log_dir``."""
    path = max(
        glob.glob(
            os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")
        ),
        key=os.path.getmtime,
    )
    data = jax.profiler.ProfileData.from_file(path)
    return [
        (ev.name, ev.start_ns, ev.duration_ns, dict(ev.stats))
        for plane in data.planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    ]


@pytest.fixture(scope="module")
def session(tmp_path_factory):
    """One profiler session. Its first part runs with
    ``tracing.enable()`` off; the second turns it on for one span."""
    tracing.disable()
    tracing.clear()
    fn = sharded_jit(lambda x: x + 1, label=LABEL)
    x = jnp.ones(4)
    fn(x)  # the one trace, before the session
    log_dir = str(tmp_path_factory.mktemp("profile"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        live = tracing.profiling()
        with tracing.start_span("train:iteration", iteration=1):
            with tracing.start_span("replay:insert", bytes=3) as span:
                span.set_attribute("rows", 5)
                jax.block_until_ready(fn(x))  # the diet dispatch path
            tracing.event("recovery:workers", dead=2)
            now = time.time()
            tracing.record_span("feeder:queue_wait", now - 0.25, now, depth=1)
        with tracing.context_span(None, "serve:batch", n=7):
            pass
        spans_profiler_only = tracing.get_spans()
        span_type = type(span)
        tracing.enable()
        try:
            with tracing.start_span("learn:superstep", k=8) as both:
                both.set_attribute("recompiles", 0)
        finally:
            tracing.disable()
        spans_enabled = tracing.get_spans()
        tracing.clear()
    finally:
        jax.profiler.stop_trace()
    return {
        "events": _host_events(log_dir),
        "live": live,
        "spans_profiler_only": spans_profiler_only,
        "spans_enabled": spans_enabled,
        "span_type": span_type,
        "fn": fn,
        "after": tracing.profiling(),
    }


def _named(session, name):
    return [e for e in session["events"] if e[0] == name]


def test_the_session_is_the_switch(session):
    assert session["live"] is True
    assert session["after"] is False
    assert not tracing.is_enabled()


def test_profiler_only_span_is_an_event_with_its_attributes(session):
    (ev,) = _named(session, "replay:insert")
    assert ev[3]["bytes"] == 3
    assert ev[3]["rows"] == 5  # set_attribute reaches the annotation
    (outer,) = _named(session, "train:iteration")
    assert outer[3]["iteration"] == 1


def test_profiler_only_span_nests_under_its_outer_span_by_time(session):
    (ev,) = _named(session, "replay:insert")
    (outer,) = _named(session, "train:iteration")
    assert outer[1] <= ev[1]
    assert ev[1] + ev[2] <= outer[1] + outer[2]
    # the dispatch the span covers lies inside it, on the same clock
    (call,) = [
        e for e in _named(session, f"PjitFunction({label_family(LABEL)})")
        if ev[1] <= e[1] and e[1] + e[2] <= ev[1] + ev[2]
    ][:1]
    assert call[2] > 0


def test_profiler_only_session_builds_no_span_objects(session):
    assert session["spans_profiler_only"] == []
    assert not issubclass(session["span_type"], tracing.Span)
    assert issubclass(session["span_type"], jax.profiler.TraceAnnotation)


def test_event_record_span_and_context_span_are_annotations_too(session):
    (ev,) = _named(session, "recovery:workers")
    assert ev[3]["dead"] == 2
    (wait,) = _named(session, "feeder:queue_wait")
    assert wait[3]["depth"] == 1
    assert float(wait[3]["seconds"]) == pytest.approx(0.25, abs=1e-3)
    (batch,) = _named(session, "serve:batch")
    assert batch[3]["n"] == 7


def test_enabled_under_a_session_gives_both_outputs(session):
    (record,) = session["spans_enabled"]
    assert record["name"] == "learn:superstep"
    assert record["attributes"] == {"k": 8, "recompiles": 0}
    (ev,) = _named(session, "learn:superstep")
    assert ev[3]["k"] == 8 and ev[3]["recompiles"] == 0


def test_program_is_named_after_its_labels_family(session):
    assert label_family(LABEL) == "replay_insert"
    assert label_family("superstep[DQNJaxPolicy:512x8]") == "superstep"
    assert label_family("tree_draw_sets[default_policy:8x512]") == "tree_draw_sets"
    assert label_family("a-b.c[d]") == "a_b_c"
    assert _named(session, "PjitFunction(replay_insert)")
    assert not [e for e in session["events"] if "_counted" in e[0]]
    assert "jit_replay_insert" in session["fn"].lower(jnp.ones(4)).as_text()


def test_compile_stats_keep_the_full_label_and_count_one_trace(session):
    fn = session["fn"]
    assert fn.label == LABEL
    (stats,) = [
        s for s in compile_stats()["per_function"] if s["label"] == LABEL
    ]
    assert stats["traces"] == 1
    assert stats["calls"] == 2


def test_diet_dispatch_emits_no_jit_event_under_a_profiler_only_session(
    session,
):
    assert not [e for e in session["events"] if e[0].startswith("jit:")]


def test_neither_on_yields_the_null_span():
    assert not tracing.is_enabled() and not tracing.profiling()
    with tracing.start_span("replay:insert", bytes=3) as span:
        span.set_attribute("rows", 5)
    assert span is tracing._NULL_SPAN
    with tracing.context_span(None, "serve:batch") as span:
        pass
    assert span is tracing._NULL_SPAN
    tracing.event("recovery:workers")
    tracing.record_span("feeder:queue_wait", 0.0, 1.0)
    assert tracing.get_spans() == []


def test_a_process_without_jax_opens_spans_without_importing_it():
    code = (
        "import sys\n"
        "from ray_tpu.util import tracing\n"
        "with tracing.start_span('replay:insert', bytes=3) as span:\n"
        "    pass\n"
        "assert span is tracing._NULL_SPAN\n"
        "tracing.event('recovery:workers')\n"
        "tracing.enable()\n"
        "with tracing.start_span('rollout:sample') as span:\n"
        "    span.set_attribute('steps', 4)\n"
        "assert tracing.get_spans()[0]['attributes'] == {'steps': 4}\n"
        "assert not tracing.profiling()\n"
        "assert not [m for m in sys.modules if m.split('.')[0] == 'jax']\n"
        "print('no jax')\n"
    )
    env = {k: v for k, v in os.environ.items() if k != "RAY_TPU_TRACE"}
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "no jax"


# -- set-up and compiles in the profiler's trace (PR 36) ---------------------


@pytest.fixture(scope="module")
def setup_session(tmp_path_factory):
    """A profiler-only session around one ``phase`` site and the first
    call of a program (``tracing.enable()`` never called)."""
    tracing.disable()
    tracing.clear()
    fn = sharded_jit(lambda x: x * 3.0 + 1.0, label="acct_profiled[p:1]")
    x = jnp.ones(4)
    log_dir = str(tmp_path_factory.mktemp("setup_profile"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        with tracing.phase("setup:replay", bytes=7) as span:
            jax.block_until_ready(fn(x))
        span_type = type(span)
    finally:
        jax.profiler.stop_trace()
    out = {
        "events": _host_events(log_dir),
        "spans": tracing.get_spans(),
        "phases": tracing.phases(),
        "stats": fn.stats(),
        "span_type": span_type,
    }
    tracing.clear()
    return out


def test_a_phase_is_an_annotation_and_a_row_and_no_span(setup_session):
    (ev,) = _named(setup_session, "setup:replay")
    assert ev[3]["bytes"] == 7
    assert setup_session["spans"] == []
    assert not issubclass(setup_session["span_type"], tracing.Span)
    (row,) = setup_session["phases"]
    assert row["name"] == "setup:replay" and row["parent"] is None
    assert row["seconds"] == pytest.approx(ev[2] / 1e9, rel=0.2, abs=0.01)


def test_a_compile_is_annotated_by_family_and_phase(setup_session):
    """The profiler's clock cannot be back-dated: each is an annotation
    of no length with jax's own seconds as its ``seconds``."""
    stats = setup_session["stats"]
    (family,) = _named(setup_session, "compile:acct_profiled")
    assert family[3]["label"] == "acct_profiled[p:1]"
    assert float(family[3]["seconds"]) >= stats["compile_time_s"] * 0.999
    seconds = {}
    for phase in ("trace", "lower", "backend"):
        (ev,) = _named(setup_session, "compile:" + phase)
        seconds[phase] = float(ev[3]["seconds"])
        assert seconds[phase] >= stats[phase + "_s"] * 0.999 > 0.0
    # inside the phase that was open while the program compiled
    (outer,) = _named(setup_session, "setup:replay")
    assert outer[1] <= family[1] <= outer[1] + outer[2]


# -- named scopes in the lowered programs ------------------------------------


@pytest.fixture(scope="module")
def lowered():
    """``{program family: lowered text with locations}`` of every
    ``sharded_jit`` program that the prioritized DQN device lane and
    the fused on-policy PPO lane dispatch, each taken at its first
    call."""
    from ray_tpu.algorithms.dqn.dqn import DQNConfig
    from ray_tpu.algorithms.ppo.ppo import PPOConfig

    texts = {}
    original = ShardedFunction.__call__

    def capturing(self, *args, **kwargs):
        family = label_family(self.label)
        if family not in texts:
            texts[family] = ""
            with self.uncounted_traces():
                texts[family] = self.lower(*args, **kwargs).as_text(
                    debug_info=True
                )
        return original(self, *args, **kwargs)

    dqn = (
        DQNConfig()
        .environment("CartPoleJax-v0", env_backend="jax")
        .resources(learner_devices=1)
        .rollouts(
            num_rollout_workers=0,
            rollout_fragment_length=8,
            num_envs_per_worker=4,
        )
        .training(
            train_batch_size=32,
            num_steps_sampled_before_learning_starts=32,
            replay_buffer_config={
                "prioritized_replay": True,
                "capacity": 256,
            },
            replay_device_resident=True,
            replay_device_tree=True,
            training_intensity=2.0,
            superstep=2,
            model={"fcnet_hiddens": [16, 16]},
        )
        .debugging(seed=0)
    )
    ppo = (
        PPOConfig()
        .environment(
            "CartPoleJax-v0", env_backend="jax", jax_fused_rollout=True
        )
        .resources(learner_devices=1)
        .rollouts(
            num_rollout_workers=0,
            num_envs_per_worker=8,
            rollout_fragment_length=8,
        )
        .training(
            train_batch_size=64,
            sgd_minibatch_size=32,
            num_sgd_iter=2,
            model={"fcnet_hiddens": [16, 16]},
        )
        .debugging(seed=0)
    )
    ShardedFunction.__call__ = capturing
    try:
        for config, iterations in ((dqn, 3), (ppo, 1)):
            algo = config.build()
            try:
                for _ in range(iterations):
                    algo.train()
            finally:
                algo.cleanup()
    finally:
        ShardedFunction.__call__ = original
    return texts


@pytest.mark.parametrize(
    "family, scopes",
    [
        ("replay_insert", ["replay/insert"]),
        ("tree_draw_sets", ["replay/draw"]),
        ("tree_update", ["replay/refresh"]),
        (
            "superstep",
            [
                # one minibatch of all 32 rows: no learn/minibatch
                "replay/gather", "sgd_nest",
                "learn/loss_grad", "learn/allreduce", "learn/optimizer",
                "learn/grad_norm", "learn/commit", "learn/td_error",
                "/fc/", "/head/",
            ],
        ),
        (
            "jax_rollout",
            ["rollout/act", "rollout/env_step", "rollout/postprocess"],
        ),
        (
            "rollout_superstep",
            [
                "rollout/act", "rollout/env_step",
                "rollout/postprocess/gae", "sgd_nest", "learn/minibatch",
                "learn/loss_grad",
                "learn/allreduce", "learn/optimizer", "learn/commit",
            ],
        ),
    ],
)
def test_lowered_program_names_its_scopes(lowered, family, scopes):
    assert family in lowered, sorted(lowered)
    text = lowered[family]
    assert f"jit({family})" in text
    missing = [s for s in scopes if s not in text]
    assert not missing, missing


def test_pixel_model_names_its_layers():
    from ray_tpu.algorithms.dqn.dqn_model import DQNModel
    from ray_tpu.models.cnn import VisionNet

    obs = jnp.zeros((2, 84, 84, 4), jnp.uint8)
    for model, method in (
        (
            DQNModel(num_outputs=3, hiddens=(8,), use_conv=True),
            DQNModel.q_dist,
        ),
        (VisionNet(num_outputs=3, post_fcnet_hiddens=(8,)), None),
    ):
        params = model.init(jax.random.PRNGKey(0), obs)
        text = (
            jax.jit(lambda p, o: model.apply(p, o, method=method))
            .lower(params, obs)
            .as_text(debug_info=True)
        )
        for scope in ("conv0/", "conv1/", "conv2/", "fc/", "head/"):
            assert scope in text, (type(model).__name__, scope)
