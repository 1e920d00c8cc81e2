"""Set-up accounts for itself (PR 36): jax's own seconds of every
compile by phase, on the ``ShardedFunction`` that compiled and on its
family's row, ``other`` for what no ``ShardedFunction`` compiled, the
persistent cache's verdicts; the steps of building an Algorithm as
``setup:`` phases that are kept with tracing off; and none of it on
the path of a steady dispatch or of an iteration."""

import functools
import inspect
import os
import subprocess
import sys
import threading

import pytest

jax = pytest.importorskip("jax")

import jax.numpy as jnp  # noqa: E402

from ray_tpu.sharding.compile import (  # noqa: E402
    ShardedFunction,
    compile_stats,
    sharded_jit,
)
from ray_tpu.telemetry import device as device_ledger  # noqa: E402
from ray_tpu.telemetry import metrics as telemetry_metrics  # noqa: E402
from ray_tpu.util import tracing  # noqa: E402

PHASES = ("trace_s", "lower_s", "backend_s")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _quiet():
    tracing.disable()
    tracing.clear()
    device_ledger.disable()
    yield
    tracing.disable()
    tracing.clear()
    device_ledger.disable()


def _family(name):
    return dict(compile_stats()["families"].get(name) or {})


def _body(x):
    return jnp.tanh(x @ x.T).sum(axis=0)


def test_a_first_call_fills_the_three_phases_and_a_second_nothing():
    fn = sharded_jit(_body, label="acct_first[a:1]")
    x = jnp.ones((32, 32))
    assert all(fn.stats()[k] == 0.0 for k in PHASES)
    fn(x)
    first = fn.stats()
    assert all(first[k] > 0.0 for k in PHASES), first
    assert first["compile_time_s"] == pytest.approx(
        sum(first[k] for k in PHASES)
    )
    assert first["analysis_s"] == 0.0
    assert _family("acct_first")["compile_time_s"] == pytest.approx(
        first["compile_time_s"]
    )
    fn(x)
    second = fn.stats()
    assert second["calls"] == 2 and second["traces"] == 1
    assert {k: second[k] for k in PHASES} == {k: first[k] for k in PHASES}
    # the process-wide summary adds the live functions' rows up
    total = compile_stats()
    assert total["trace_s"] >= first["trace_s"]
    assert total["compile_time_s"] == pytest.approx(
        total["trace_s"] + total["lower_s"] + total["backend_s"]
    )


def test_a_thousand_steady_dispatches_call_no_listener_and_build_no_span(
    monkeypatch,
):
    fn = sharded_jit(lambda x: x + 1.0, label="acct_steady[a]")
    x = jnp.ones(8)
    jax.block_until_ready(fn(x))
    heard = []
    listeners = {
        "event": lambda event, **kw: heard.append(event),
        "event_duration_secs": lambda event, secs, **kw: heard.append(event),
        "event_time_span": lambda event, t0, t1, **kw: heard.append(event),
        "scalar": lambda event, value, **kw: heard.append(event),
    }
    built = []
    init = tracing.Span.__init__

    def counting(self, *args, **kwargs):
        built.append(args)
        init(self, *args, **kwargs)

    monkeypatch.setattr(tracing.Span, "__init__", counting)
    for kind, fn_ in listeners.items():
        getattr(jax.monitoring, f"register_{kind}_listener")(fn_)
    try:
        before = fn.stats()
        for _ in range(1000):
            out = fn(x)
        jax.block_until_ready(out)
    finally:
        jax.monitoring.unregister_event_listener(listeners["event"])
        jax.monitoring.unregister_event_duration_listener(
            listeners["event_duration_secs"]
        )
        jax.monitoring.unregister_event_time_span_listener(
            listeners["event_time_span"]
        )
        jax.monitoring.unregister_scalar_listener(listeners["scalar"])
    # jax calls EVERY registered listener on an event: none heard one,
    # so the account's own were not called either
    assert heard == []
    assert built == []
    after = fn.stats()
    assert after["calls"] == before["calls"] + 1000
    assert {k: after[k] for k in PHASES} == {k: before[k] for k in PHASES}


def test_the_steady_branch_holds_no_setup_or_compile_site():
    """The text of ``__call__`` up to the first ``return out`` is the
    warmed-up dispatch: a clock read, the jitted call, a counter."""
    source = inspect.getsource(ShardedFunction.__call__)
    steady = "\n".join(
        line for line in source[: source.index("return out")].splitlines()
        if not line.lstrip().startswith("#")
    )
    for word in ("compile:", "setup:", "phase(", "_compiled", "account",
                 "record_span", "time.time()", "_lock"):
        assert word not in steady, word
    assert "self._jitted(*args, **kwargs)" in steady


def test_two_families_compiled_on_two_threads_get_their_own_seconds():
    barrier = threading.Barrier(2)
    fns = {
        name: sharded_jit(
            lambda x, _p=power: jnp.linalg.matrix_power(jnp.cos(x), _p),
            label=f"{name}[t]",
        )
        for name, power in (("acct_left", 5), ("acct_right", 7))
    }
    x = jnp.ones((16, 16))  # made here: an eager op is a bare jit
    other_before = _family("other")
    errors = []

    def compile_one(name):
        try:
            barrier.wait(timeout=60)
            jax.block_until_ready(fns[name](x))
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [
        threading.Thread(target=compile_one, args=(n,)) for n in fns
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors
    for name, fn in fns.items():
        row = fn.stats()
        assert all(row[k] > 0.0 for k in PHASES), row
        # each thread's events went to the function tracing on it: the
        # family's row is that function's, to the last digit
        fam = _family(name)
        assert {k: fam[k] for k in PHASES} == {k: row[k] for k in PHASES}
    # and nothing of theirs leaked into ``other``
    assert _family("other").get("backend_s", 0.0) == other_before.get(
        "backend_s", 0.0
    )


def test_a_retrace_found_after_the_fact_lands_on_its_family_with_its_cause():
    fn = sharded_jit(lambda x: x * 2.0, label="acct_retrace[r]")
    device_ledger.enable(analyze=False)  # the ledger sees the first trace
    fn(jnp.ones((4, 4)))
    device_ledger.disable()
    fn(jnp.ones((4, 4)))  # warmed up: the fast path from here on
    warm = fn.stats()
    assert not tracing.is_enabled() and not device_ledger.enabled()
    fn(jnp.ones((8, 4)))  # the shape moved: jit retraces inside the call
    row = fn.stats()
    assert row["traces"] == 2 and row["calls"] == 3
    assert all(row[k] > warm[k] for k in PHASES), (warm, row)
    (cause,) = row["retrace_causes"]
    assert "float32[4,4] -> float32[8,4]" in cause
    assert _family("acct_retrace")["trace_s"] == pytest.approx(row["trace_s"])
    assert tracing.get_spans() == []  # tracing is off: no span was built


def test_a_compile_is_a_span_over_its_phases_while_tracing_is_on():
    fn = sharded_jit(_body, label="acct_span[s:2]")
    device_ledger.enable(analyze=False)
    tracing.enable()
    fn(jnp.ones((8, 8)))
    fn(jnp.ones((16, 16)))
    tracing.disable()
    spans = tracing.get_spans()
    families = [s for s in spans if s["name"] == "compile:acct_span"]
    assert len(families) == 2
    first, second = families
    assert first["attributes"]["label"] == "acct_span[s:2]"
    assert "cause" not in first["attributes"]
    assert "float32[8,8] -> float32[16,16]" in second["attributes"]["cause"]
    by_id = {s["span_id"]: s for s in spans}
    for fam in families:
        kids = [s for s in spans if s["parent_id"] == fam["span_id"]]
        assert [k["name"] for k in kids] == [
            "compile:trace", "compile:lower", "compile:backend"
        ]
        for kid in kids:
            assert fam["start"] <= kid["start"] <= kid["end"] <= fam["end"]
        # the compile lies inside the call that compiled
        assert by_id[fam["parent_id"]]["name"] == "jit:acct_span[s:2]"
    # the spans' seconds are the account's (jax's own stamps; a span is
    # inclusive of what ran inside it, the account exclusive)
    spanned = sum(
        s["end"] - s["start"] for s in spans
        if s["name"] in ("compile:trace", "compile:lower", "compile:backend")
        and by_id.get(s["parent_id"], {}).get("name") == "compile:acct_span"
    )
    assert spanned >= fn.stats()["compile_time_s"] * 0.999


def test_a_bare_jit_lands_under_other():
    def programs():
        rows = compile_stats()["families"]
        return {k: v for k, v in rows.items() if k != "other"}

    before = _family("other")
    ours = programs()
    jax.block_until_ready(
        jax.jit(lambda x: jnp.sinh(x) * 3.0 + jnp.flip(x))(jnp.ones(13))
    )
    after = _family("other")
    assert all(after[k] > before.get(k, 0.0) for k in PHASES), (before, after)
    assert programs() == ours  # no program family's row moved


def test_the_two_counter_families_carry_the_account():
    fn = sharded_jit(lambda x: x - 1.0, label="acct_counter[c]")
    fn(jnp.ones(5))
    series = {
        tuple(sorted(dict(tags).items())): v
        for tags, v in telemetry_metrics.get_metric(
            telemetry_metrics.COMPILE_PHASE_SECONDS_TOTAL
        ).series()
    }
    for phase in ("trace", "lower", "backend"):
        key = (("family", "acct_counter"), ("phase", phase))
        assert series[key] == pytest.approx(fn.stats()[phase + "_s"])


# What is compiled twice against ONE persistent cache directory, in a
# child process whose ``JAX_COMPILATION_CACHE_DIR`` names it (the one
# answer to "where does a compiled program come from":
# ``utils/platform.ensure_compile_cache()``). ``jax.clear_caches()``
# between the two stands for the second process: nothing compiled is
# left in memory, so the second build traces and lowers again and asks
# the directory for the executable.
_CACHE_PRELUDE = """
import os
import jax, jax.numpy as jnp
import numpy as np
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from ray_tpu.sharding.compile import sharded_jit, compile_stats
from ray_tpu.utils.platform import ensure_compile_cache

# the variable is set: the code places no directory of its own
assert ensure_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
assert (
    jax.config.jax_compilation_cache_dir
    == os.environ["JAX_COMPILATION_CACHE_DIR"]
)

def verdicts(fn):
    row = fn.stats()
    return row["cache_hits"], row["cache_misses"]

def body(x):
    return jnp.tanh(x @ x.T).sum()

x = jnp.ones((16, 16))
"""

_POLICY = """
import gymnasium as gym
from ray_tpu import sharding as sharding_lib
from ray_tpu.algorithms.ppo.ppo import PPOJaxPolicy

def policy(**over):
    return PPOJaxPolicy(
        gym.spaces.Box(-1.0, 1.0, (4,), np.float32),
        gym.spaces.Discrete(2),
        {
            "seed": 7, "num_workers": 0, "train_batch_size": 16,
            "sgd_minibatch_size": 16, "num_sgd_iter": 1, "lr": 3e-4,
            "model": {"fcnet_hiddens": [16, 16]},
            "_mesh": sharding_lib.get_mesh(devices=jax.devices()[:1]),
            **over,
        },
    )
"""

_CACHE_CASES = {
    # the bare program
    "program": """
first = sharded_jit(body, label="acct_cache[p]")
first(x)
assert verdicts(first) == (0, 1), first.stats()
jax.clear_caches()
again = sharded_jit(body, label="acct_cache[p]")
again(x)
b = again.stats()
assert verdicts(again) == (1, 0), b
assert b["backend_s"] > 0.0  # the retrieval
assert b["traces"] == 1 and b["trace_s"] > 0.0  # a hit still traces
fam = compile_stats()["families"]["acct_cache"]
assert (fam["cache_hits"], fam["cache_misses"]) == (1, 1), fam
""",
    # a replica's cold start: every bucket of a second server's warmup
    # is a hit, and it serves what the first served
    "serve_warmup": _POLICY + """
from ray_tpu.serve.policy_server import BatchedPolicyServer

obs = np.random.default_rng(0).uniform(-1, 1, (5, 4)).astype(np.float32)

def replica():
    srv = BatchedPolicyServer(
        policy(), name="policy", max_batch_size=8, explore=True,
        start=False,
    )
    assert srv.warmup() == len(srv.buckets) > 1
    srv.start()
    served = [srv.submit(o).result(60.0) for o in obs]
    srv.stop()
    return srv, served

seeder, want = replica()
assert [verdicts(f) for f in seeder._fns.values()] == [(0, 1)] * len(
    seeder.buckets
)
jax.clear_caches()
joiner, got = replica()
for fn in joiner._fns.values():
    row = fn.stats()
    # it traced and lowered, and retrieved instead of compiling
    assert (row["traces"], row["recompiles"]) == (1, 0), row
    assert verdicts(fn) == (1, 0), row
for (a, ea), (b, eb) in zip(want, got):
    assert np.array_equal(a, b)
    assert np.array_equal(ea["action_logp"], eb["action_logp"])
""",
    # the joiner's case: a second policy's learn program is a hit and
    # its update is the first's, bit for bit
    "learn_program": _POLICY + """
from ray_tpu.data.sample_batch import SampleBatch

rng = np.random.default_rng(3)
B = 16
batch = {
    SampleBatch.OBS: rng.standard_normal((B, 4)).astype(np.float32),
    SampleBatch.ACTIONS: rng.integers(0, 2, B).astype(np.int64),
    SampleBatch.ACTION_LOGP: np.full(B, -0.7, np.float32),
    SampleBatch.ACTION_DIST_INPUTS: rng.standard_normal((B, 2)).astype(
        np.float32
    ),
    SampleBatch.ADVANTAGES: rng.standard_normal(B).astype(np.float32),
    SampleBatch.VALUE_TARGETS: rng.standard_normal(B).astype(np.float32),
}

def learner(**over):
    pol = policy(**over)
    pol.learn_on_batch(SampleBatch(batch))
    leaves = jax.tree_util.tree_leaves(pol.get_weights())
    return pol.learn_fn(B), [np.asarray(v) for v in leaves]

seeder, want = learner()
assert verdicts(seeder) == (0, 1), seeder.stats()
jax.clear_caches()
joiner, got = learner()
assert joiner.label == seeder.label
assert verdicts(joiner) == (1, 0), joiner.stats()
assert joiner.stats()["traces"] == 1
assert all(a.tobytes() == b.tobytes() for a, b in zip(want, got))
# the same label and shapes under other loss coefficients is ANOTHER
# program: a miss, and an update of its own
jax.clear_caches()
other, moved = learner(vf_loss_coeff=0.0, entropy_coeff=0.5)
assert other.label == seeder.label
assert verdicts(other) == (0, 1), other.stats()
assert any(a.tobytes() != b.tobytes() for a, b in zip(want, moved))
""",
    # a directory that holds nothing of this program: a miss that
    # compiles live and answers the same
    "second_directory": """
from jax.experimental.compilation_cache import compilation_cache

first = sharded_jit(body, label="acct_cache[d]")
want = float(first(x))
assert verdicts(first) == (0, 1), first.stats()
other = os.environ["JAX_COMPILATION_CACHE_DIR"] + "_other"
os.makedirs(other)
jax.clear_caches()
compilation_cache.reset_cache()
jax.config.update("jax_compilation_cache_dir", other)
again = sharded_jit(body, label="acct_cache[d]")
assert float(again(x)) == want
assert verdicts(again) == (0, 1), again.stats()
assert len(os.listdir(other)) == 1  # and seeds the new directory
""",
    # what the key is made from: the same label and shapes around
    # another constant is a miss, and answers for itself
    "other_constant": """
def scaled(c):
    return sharded_jit(lambda x: body(x) * c, label="acct_cache[c]")

one = scaled(2.0)
assert float(one(x)) == 2.0 * float(body(x))
assert verdicts(one) == (0, 1)
jax.clear_caches()
other = scaled(3.0)
assert float(other(x)) == 3.0 * float(body(x))
assert verdicts(other) == (0, 1), other.stats()
jax.clear_caches()
same = scaled(2.0)
assert float(same(x)) == 2.0 * float(body(x))
assert verdicts(same) == (1, 0), same.stats()
""",
}


@pytest.mark.parametrize("what", sorted(_CACHE_CASES))
def test_the_persistent_cache_says_miss_then_hit_for_the_same_program(
    tmp_path, what
):
    cache = tmp_path / "cache"
    cache.mkdir()
    env = dict(
        os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache)
    )
    code = _CACHE_PRELUDE + _CACHE_CASES[what] + '\nprint("miss then hit")\n'
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300, cwd=ROOT,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip().endswith("miss then hit")


# The serialized-executable cache (PR 14 to PR 58) is gone with every
# name it went by: no module of the package keeps a second answer to
# "where does a compiled program come from". (Spelled in halves so that
# a grep for them over the tree finds this file no more than the rest.)
_GONE = ("aot_" + "cache", "aot_" + "warmup", "aot_" + "source", "PRE" + "SEED")


@functools.cache
def _package_lines():
    """Every line of the package's Python, read once for all cases."""
    lines = []
    for folder, _, names in os.walk(os.path.join(ROOT, "ray_tpu")):
        for name in names:
            if name.endswith(".py"):
                path = os.path.join(folder, name)
                with open(path, encoding="utf-8") as f:
                    lines += [
                        (f"{os.path.relpath(path, ROOT)}:{n}", line)
                        for n, line in enumerate(f, 1)
                    ]
    return lines


def _naming(name, lines):
    return [where for where, line in lines if name.lower() in line.lower()]


@pytest.mark.parametrize("name", _GONE)
def test_the_package_names_no_second_compile_cache(name):
    assert _naming(name, _package_lines()) == []


def test_the_walk_over_the_package_finds_a_planted_name():
    planted = [
        ("a.py:1", "        self." + _GONE[0] + " = resolve_cache(root)\n"),
        ("a.py:2", "    fn." + _GONE[1] + "(cache, *args)\n"),
        ("b.py:7", 'os.environ.get("RAY_TPU_FLEET_' + _GONE[3] + '", "1")\n'),
        ("b.py:8", "placed by ensure_compile_cache()\n"),
    ]
    assert [_naming(name, planted) for name in _GONE] == [
        ["a.py:1"], ["a.py:2"], [], ["b.py:7"],
    ]
    # and it walks the package: the one placement it must find
    (placed,) = _naming("def ensure_compile_cache", _package_lines())
    assert placed.startswith("ray_tpu/utils/platform.py:")


# -- building an Algorithm, by phase ----------------------------------------

NESTING = {
    "setup:algorithm": None,
    "setup:workers": "setup:algorithm",
    "setup:policy": "setup:workers",
    "setup:model_init": "setup:policy",
    "setup:optimizer_init": "setup:policy",
    "setup:rollout_engine": None,  # built where the lane first needs it
    "setup:replay": None,  # the ring is allocated at its first rows
}


def _small_dqn():
    from ray_tpu.algorithms.dqn.dqn import DQNConfig

    return (
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
            replay_buffer_config={"prioritized_replay": True, "capacity": 256},
            replay_device_resident=True,
            replay_device_tree=True,
            training_intensity=2.0,
            superstep=2,
            model={"fcnet_hiddens": [16, 16]},
        )
        .debugging(seed=0)
    )


@pytest.fixture(scope="module")
def built():
    """One small Algorithm built and run with tracing OFF, then the
    same again with ``tracing.enable()`` on."""
    out = {}
    for mode in ("off", "on"):
        tracing.disable()
        tracing.clear()
        if mode == "on":
            tracing.enable()
        algo = _small_dqn().build()
        try:
            results = [algo.train() for _ in range(4)]
            out[mode] = {
                "phases": tracing.phases(),
                "spans": tracing.get_spans(),
                "results": results,
            }
        finally:
            algo.cleanup()
            tracing.disable()
    tracing.clear()
    return out


def test_building_with_tracing_off_fills_the_phase_table_nested(built):
    rows = built["off"]["phases"]
    assert built["off"]["spans"] == []
    assert {r["name"] for r in rows} == set(NESTING)
    for row in rows:
        assert row["parent"] == NESTING[row["name"]], row
        assert row["seconds"] > 0.0
    seconds = {r["name"]: r["seconds"] for r in rows}
    assert seconds["setup:algorithm"] >= seconds["setup:workers"]
    assert seconds["setup:workers"] >= seconds["setup:policy"]
    assert seconds["setup:policy"] >= (
        seconds["setup:model_init"] + seconds["setup:optimizer_init"]
    )
    assert tracing.phase_seconds("setup:never") is None


def test_with_tracing_on_the_same_names_are_in_the_span_list(built):
    spans = built["on"]["spans"]
    by_id = {s["span_id"]: s for s in spans}
    named = {}
    for s in spans:
        if s["name"].startswith("setup:"):
            named.setdefault(s["name"], s)
    assert set(named) == set(NESTING)
    for name, parent in NESTING.items():
        got = by_id.get(named[name]["parent_id"])
        if parent is not None:
            assert got is not None and got["name"] == parent, (name, got)
    init = named["setup:model_init"]["attributes"]
    assert init["params"] > 0 and init["bytes"] == 4 * init["params"]
    assert named["setup:replay"]["attributes"]["bytes"] > 0
    # and the table is kept all the same
    assert {r["name"] for r in built["on"]["phases"]} == set(NESTING)
    # every compile of the run is a span too
    assert [s for s in spans if s["name"] == "compile:superstep"]


def test_the_first_result_carries_the_account_and_only_the_first(built):
    first, *later = built["off"]["results"]
    setup = first["info"]["setup"]
    assert {r["name"] for r in setup["phases"]} >= {
        "setup:algorithm", "setup:model_init", "setup:rollout_engine"
    }
    assert setup["compile"]["jax_rollout"]["trace_s"] > 0.0
    assert "other" in setup["compile"]
    assert all("setup" not in r["info"] for r in later)


def test_no_setup_or_compile_site_is_reachable_from_a_steady_iteration(built):
    """By text: ``Algorithm.step`` and the result it builds open no
    ``setup:`` / ``compile:`` site. By running: the iterations after
    the first add no row to the table and no such span."""
    from ray_tpu.algorithms.algorithm import Algorithm

    for fn in (Algorithm.step, Algorithm._iteration_result):
        source = inspect.getsource(fn)
        for word in ('"setup:', '"compile:', "tracing.phase("):
            assert word not in source, (fn.__name__, word)
    spans = built["on"]["spans"]
    iterations = sorted(
        (s for s in spans if s["name"] == "train:iteration"),
        key=lambda s: s["start"],
    )
    assert len(iterations) == 4
    steady_from = iterations[2]["start"]
    late = [
        s["name"] for s in spans
        if s["name"].startswith(("setup:", "compile:"))
        and s["start"] >= steady_from
    ]
    assert late == []
    # the table holds one Algorithm's seven rows, not a row an iteration
    assert len(built["off"]["phases"]) == len(NESTING)


def test_the_roll_up_reads_each_span_once():
    """``spans_since``: the cursor hands a consumer what was appended
    since its last call, whatever the bounded buffer dropped."""
    tracing.enable()
    with tracing.start_span("rollout:sample"):
        pass
    got, cursor = tracing.spans_since(0)
    assert [s["name"] for s in got] == ["rollout:sample"]
    assert tracing.spans_since(cursor) == ([], cursor)
    with tracing.start_span("learn:nest"):
        pass
    tracing.event("recovery:workers")
    got, cursor2 = tracing.spans_since(cursor)
    assert [s["name"] for s in got] == ["learn:nest", "recovery:workers"]
    assert cursor2 == cursor + 2
    # a drained buffer (a worker ships its spans) never hands one twice
    tracing.drain_finished()
    with tracing.start_span("learn:nest"):
        pass
    got, _ = tracing.spans_since(cursor2)
    assert [s["name"] for s in got] == ["learn:nest"]
    got, _ = tracing.spans_since(0)  # a stale cursor reads what is left
    assert [s["name"] for s in got] == ["learn:nest"]
