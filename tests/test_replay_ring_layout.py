"""The stored row width of a packed uint8 replay column
(docs/data_plane.md "stored row width"): a row is rounded up to whole
128-word lanes where that is cheap, so the TPU client lays the ring
out row-major and neither the insert nor a gather copies it.

Two halves: CPU parity cases over row widths (the pad never reaches a
consumer), and compiles for a described v5e (no chip needed) that hold
the layout itself — no ``copy`` of the ring, the insert in place."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu import sharding as sharding_lib
from ray_tpu.data.sample_batch import SampleBatch
from ray_tpu.execution.replay_buffer import (
    DevicePrioritizedReplayBuffer,
    DeviceReplayBuffer,
    PrioritizedReplayBuffer,
    ReplayBuffer,
    _stored_words,
)

CAPACITY = 24  # 8 simulated devices: 3 ring rows a shard

# row shape -> words its ring stores a row in
ROW_WIDTHS = [
    pytest.param((84, 84, 4), 7168, id="7056w-padded"),
    pytest.param((84, 84), 1792, id="1764w-padded"),
    pytest.param((16, 8, 4), 128, id="128w-whole-lane"),
    pytest.param((4, 4, 4), 16, id="16w-small-unpadded"),
    pytest.param((40, 20), 200, id="200w-pad-too-dear"),
]


def _tree(n, base, rng, row_shape):
    return {
        "obs": base + np.arange(n * 6, dtype=np.float32).reshape(n, 6),
        "pix": rng.integers(0, 255, (n,) + row_shape, dtype=np.uint8),
        "rewards": np.arange(n, dtype=np.float32) + base,
    }


def _assert_rows_equal(want, got, what):
    got = jax.device_get(got)
    for k, col in want.items():
        assert got[k].dtype == col.dtype, (what, k)
        assert np.array_equal(got[k], col), (what, k)


@pytest.mark.parametrize("row_shape,stored", ROW_WIDTHS)
def test_rows_bit_identical_to_host_ring(row_shape, stored):
    """Insert from the host and from the device (past capacity, so
    the scatter wraps), then every read path — ``gather``, ``sample``
    and the superstep feed's ``gather_fn`` — returns the host ring's
    rows bit for bit, whatever width the ring stores them at (the
    feed gathers rows as stored and unpacks an update's: a packed
    column is words, pad and all, between the two)."""
    assert _stored_words(row_shape) == stored
    rng = np.random.default_rng(0)
    host = ReplayBuffer(CAPACITY, seed=9)
    from_host = DeviceReplayBuffer(CAPACITY, seed=9)
    from_dev = DeviceReplayBuffer(CAPACITY, seed=9)
    for i in range(5):  # 35 rows into 24: wraps
        t = _tree(7, float(100 * i), rng, row_shape)
        host.add(SampleBatch(t))
        from_host.add_tree(t)
        from_dev.add_device_tree(
            {k: jnp.asarray(v) for k, v in t.items()}
        )
    idx2 = np.arange(16, dtype=np.int32).reshape(2, 8) * 3 % CAPACITY
    for what, dev in (("host", from_host), ("device", from_dev)):
        assert dev._store["pix"].shape == (CAPACITY, stored), what
        assert dev._store["pix"].dtype == jnp.uint32, what
        assert (len(dev), dev._idx, dev.num_added) == (
            len(host), host._idx, host.num_added
        )
        # what the gauge reports is what is allocated, pad included
        assert dev.storage_bytes == sum(
            ring.nbytes for ring in dev._store.values()
        )
        _assert_rows_equal(
            host._cols, dev.gather(np.arange(CAPACITY)).tree, what
        )
        feed = dev.superstep_feed(idx2)
        words = jax.jit(feed.gather_fn)(feed.store, feed.idx)
        assert words["pix"].shape == idx2.shape + (stored,), what
        for i, idx1 in enumerate(idx2):  # an update's rows at a time
            _assert_rows_equal(
                {k: col[idx1] for k, col in host._cols.items()},
                jax.jit(feed.unpack_fn)({k: v[i] for k, v in words.items()}),
                what,
            )
    hs = host.sample(8)
    _assert_rows_equal(
        {k: np.asarray(v) for k, v in hs.items()},
        from_host.sample(8).tree,
        "sample",
    )


@pytest.mark.parametrize("row_shape,stored", ROW_WIDTHS)
def test_prioritized_tree_sample_bit_identical(row_shape, stored):
    """The fused device-tree sample (draw, weights and row gather in
    one program) hands out the host prioritized ring's rows."""
    rng = np.random.default_rng(2)
    host = PrioritizedReplayBuffer(CAPACITY, alpha=0.6, seed=4)
    dev = DevicePrioritizedReplayBuffer(
        CAPACITY, alpha=0.6, seed=4, device_tree=True
    )
    for i in range(4):
        t = _tree(7, float(i), rng, row_shape)
        pri = rng.uniform(0.1, 3.0, 7)
        host.add_with_priorities(SampleBatch(t), pri)
        dev.add_tree(t, priorities=pri)
    assert dev._store["pix"].shape == (CAPACITY, stored)
    hs = host.sample(8, beta=0.4)
    ds = dev.sample(8, beta=0.4)
    assert np.array_equal(hs["batch_indexes"], jax.device_get(ds.indices))
    _assert_rows_equal(
        {k: np.asarray(hs[k]) for k in ("obs", "pix", "rewards", "weights")},
        ds.tree,
        "tree sample",
    )


@pytest.mark.parametrize("row_shape,stored", ROW_WIDTHS)
def test_state_holds_logical_rows(row_shape, stored):
    """``get_state`` strips the pad and ``set_state`` adds it: the
    round trip keeps the ring, and a state dict with unpadded ``cols``
    — what the host ring writes, and what a checkpoint from before
    the stored width existed holds — loads into a padded ring."""
    rng = np.random.default_rng(4)
    host = ReplayBuffer(CAPACITY, seed=2)
    dev = DeviceReplayBuffer(CAPACITY, seed=2)
    for i in range(2):  # 20 rows: size < capacity, so cols are cut
        t = _tree(10, float(i), rng, row_shape)
        host.add(SampleBatch(t))
        dev.add_tree(t)
    state = dev.get_state()
    assert state["cols"]["pix"].shape == (20,) + row_shape
    assert state["cols"]["pix"].dtype == np.uint8
    _assert_rows_equal(host.get_state()["cols"], state["cols"], "state")
    for what, st in (
        ("round trip", state),
        ("older checkpoint", {**host.get_state(), "spilled": False}),
    ):
        dev2 = DeviceReplayBuffer(CAPACITY, seed=2)
        dev2.set_state(st)
        assert dev2._store["pix"].shape == (CAPACITY, stored), what
        assert dev2.storage_bytes == dev.storage_bytes, what
        assert (len(dev2), dev2._idx, dev2.num_added) == (
            len(dev), dev._idx, dev.num_added
        )
        _assert_rows_equal(
            host._cols, dev2.gather(np.arange(CAPACITY)).tree, what
        )


# -- the layout itself, from the v5e compiler (no chip) -----------------
#
# The benchmark cell's ring: 131,072 rows of 84x84x4 uint8, a 512-row
# insert, an (8, 512) superstep gather. Abstract arguments only; the
# topology is described inside a fixture, never at import.

CELL_ROWS = 131072


@pytest.fixture(scope="module")
def v5e_mesh():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or its lock is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return sharding_lib.get_mesh(devices=topo.devices[:1])


def _abstract_buffer(mesh, capacity, cols):
    """A buffer whose rings are shapes on the described chip: what
    ``_ensure_storage`` would allocate, with nothing allocated."""
    buf = DeviceReplayBuffer(capacity, mesh=mesh)
    for k, (row_shape, dtype) in cols.items():
        shape, ring_dtype = buf._ring_shape_dtype(
            capacity, row_shape, dtype
        )
        ring = jax.ShapeDtypeStruct(shape, ring_dtype)
        buf._store[k] = jax.ShapeDtypeStruct(
            shape,
            ring_dtype,
            sharding=sharding_lib.leaf_sharding(ring, mesh),
        )
        buf._meta[k] = (
            row_shape, np.dtype(dtype), buf._packable(row_shape, dtype)
        )
    return buf


def _on(mesh, shape, dtype):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding_lib.replicated(mesh)
    )


def _ring_copies(compiled, capacity):
    """HLO ``copy`` instructions whose result has the ring's rows."""
    return [
        line.strip()[:160]
        for line in compiled.as_text().splitlines()
        if re.search(rf"= \w+\[{capacity},[^\]]*\]\S* copy\(", line)
    ]


RING_CASES = [
    pytest.param(
        CELL_ROWS,
        {"obs": ((84, 84, 4), np.uint8), "new_obs": ((84, 84, 4), np.uint8),
         "rewards": ((), np.float32)},
        CELL_ROWS * (2 * 4 * 7168 + 4),
        id="cell-pixel-ring",
    ),
    pytest.param(
        1 << 20,
        {"obs": ((17,), np.float32), "new_obs": ((17,), np.float32),
         "actions": ((6,), np.float32), "rewards": ((), np.float32)},
        (1 << 20) * 4 * (17 + 17 + 6 + 1),
        id="vector-ring",
    ),
]


@pytest.mark.parametrize("capacity,cols,ring_bytes", RING_CASES)
def test_v5e_insert_runs_in_place(v5e_mesh, capacity, cols, ring_bytes):
    buf = _abstract_buffer(v5e_mesh, capacity, cols)
    rows = {
        k: _on(v5e_mesh, (512,) + shape, dtype)
        for k, (shape, dtype) in cols.items()
    }
    compiled = (
        buf._build_insert_fn()
        .lower(buf._store, rows, _on(v5e_mesh, (512,), np.int32))
        .compile()
    )
    assert _ring_copies(compiled, capacity) == []
    mem = compiled.memory_analysis()
    assert ring_bytes == sum(
        int(np.prod(r.shape)) * r.dtype.itemsize
        for r in buf._store.values()
    )
    # every ring is written where it lies. Device bytes: equal to the
    # ring's for lane-whole rows (the cell: 7,516,717,056), more where
    # a narrow column-major row is tiled up to 8 words
    assert mem.alias_size_in_bytes >= ring_bytes
    assert mem.alias_size_in_bytes <= mem.output_size_in_bytes
    assert mem.temp_size_in_bytes < 0.5e9


@pytest.mark.parametrize("capacity,cols,ring_bytes", RING_CASES)
def test_v5e_superstep_gather_reads_rows(
    v5e_mesh, capacity, cols, ring_bytes
):
    buf = _abstract_buffer(v5e_mesh, capacity, cols)
    feed = buf.superstep_feed(np.zeros((8, 512), np.int32))
    compiled = (
        jax.jit(feed.gather_fn)
        .lower(buf._store, _on(v5e_mesh, (8, 512), np.int32))
        .compile()
    )
    assert _ring_copies(compiled, capacity) == []
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def _instructions(text):
    """``(name, dtype, dims, opcode, operand names, called body)`` of every
    instruction of an optimized HLO text that stands on its own (not
    inside a fused computation); ``("tuple", ())`` for the dtype and
    dims of one with several results."""
    bodies = {}
    for comp in re.split(r"\n(?=(?:ENTRY )?%[\w.\-]+ \()", text):
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", comp)
        if m:
            bodies[m.group(1)] = comp
    fused = set(re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", text))
    out = []
    for name, comp in bodies.items():
        if name in fused:
            continue
        for line in comp.splitlines()[1:]:
            m = re.match(
                r"\s*(?:ROOT )?%([\w.\-]+) = "
                r"(?:(\w+)\[([\d,]*)\]\S*|\(.*?\)) ([\w\-]+)\((.*)",
                line,
            )
            if not m:
                continue
            inst, dtype, dims, opcode, rest = m.groups()
            dtype, dims = dtype or "tuple", dims or ""
            called = re.search(r"calls=%([\w.\-]+)", rest)
            out.append((
                inst, dtype, tuple(int(d) for d in dims.split(",") if d),
                opcode,
                re.findall(r"%([\w.\-]+)", rest.split("), ")[0]),
                bodies.get(called.group(1), "") if called else "",
            ))
    return out


def test_v5e_superstep_makes_a_pixel_rows_bytes_once(v5e_mesh):
    """The benchmark cell's whole replay superstep (the DQN policy's
    ``_device_update_fn(512)``, the ring feed of 131,072 x ``u32[7168]``
    rows, the priority pass, K=8) as the chip's compiler leaves it.
    What is held is the program's, not this compiler's count of
    instructions: the rings are gathered once, as ``u32[4096,7168]``
    words, before the scan; the scan carries words (no
    ``u8[8,512,84,84,4]``); nothing packs bytes into words again; a
    column's bytes are made ONCE an update, and the update's and the
    priority pass's first convolutions all read those bytes; and since
    they do, the target network's forward over ``new_obs`` stands once,
    for the loss and the priorities (same bytes, same weights). The
    parent fails four of these: it made the bytes of all 8 updates
    before the scan, packed them into ``u32[512,84,84]`` in the body,
    gathered every row by a permutation, unpacked a second time, and
    ran a seventh convolution (the priority pass's own target forward,
    over bytes that were another array). Between a ring and a
    convolution libtpu 0.0.34 leaves six instructions a column (the
    gather; the update's slice of it, a transposing ``copy``, the pad's
    ``slice``, an 84 -> 88 ``reshape``, the words -> bytes fusion: the
    first convolution wants the BATCH in the lanes), the parent ten;
    docs/data_plane.md has them, no assertion counts them."""
    import json
    import os

    import gymnasium as gym

    from ray_tpu.algorithms.dqn.dqn import DQN, DQNJaxPolicy
    from ray_tpu.sharding import superstep as superstep_lib

    k, rows = 8, 512
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf/configs/nature_cnn_dqn_per.json")) as f:
        cell = json.load(f)
    cfg = DQN.get_default_config().to_dict()
    cfg.update(cell["algo_config"])
    cfg.update(seed=1, _mesh=sharding_lib.get_mesh(devices=jax.devices()[:1]))
    policy = DQNJaxPolicy(
        gym.spaces.Box(0, 255, (84, 84, 4), np.uint8), gym.spaces.Discrete(3), cfg
    )
    shapes = jax.tree_util.tree_map(
        lambda x: _on(v5e_mesh, x.shape, x.dtype),
        (policy.params, policy.opt_state, policy.aux_state, policy._coeff_array()),
    )
    policy.mesh = v5e_mesh
    buf = _abstract_buffer(v5e_mesh, CELL_ROWS, {
        "obs": ((84, 84, 4), np.uint8), "new_obs": ((84, 84, 4), np.uint8),
        "actions": ((), np.int32), "rewards": ((), np.float32),
        "dones": ((), np.float32),
    })
    feed = buf.superstep_feed(np.zeros((k, rows), np.int32))
    fn = superstep_lib.build_superstep_fn(
        policy._device_update_fn(rows), mesh=v5e_mesh, k=k, label="superstep[cell]",
        rings=feed, extra_cols=("weights",),
        priority_fn=policy._td_error_device_fn(),
    )
    params, opt_state, aux, coeffs = shapes
    text = fn._jitted.lower(
        params, opt_state, aux,
        (dict(buf._store), _on(v5e_mesh, (k, rows), np.int32),
         {"weights": _on(v5e_mesh, (k, rows), np.float32)}),
        _on(v5e_mesh, (k,), np.float32), _on(v5e_mesh, (k, 2), np.uint32),
        _on(v5e_mesh, (k, 2), np.uint32), coeffs,
    ).compile().as_text()
    assert not re.search(r"u8\[8,512,84,84,4\]", text)  # the scan carries words
    # every instruction that writes an update's worth of pixels or more
    by_name = {inst[0]: inst for inst in _instructions(text)}
    views = ("parameter", "get-tuple-element", "tuple", "bitcast", "while")
    pixels = [
        inst for inst in by_name.values()
        if inst[1] in ("u8", "u32") and inst[3] not in views
        and int(np.prod(inst[2])) * (4 if inst[1] == "u32" else 1) >= rows * 84 * 84 * 4
    ]

    def reads(inst, dtype):
        return any(by_name[o][1] == dtype for o in inst[4] if o in by_name)

    gathers = [i for i in pixels if " gather(" in i[5]]
    assert [(i[1], i[2]) for i in gathers] == [("u32", (k * rows, 7168))] * 2
    packs = [i for i in pixels if i[1] == "u32" and reads(i, "u8")]
    assert packs == []
    unpacks = [i for i in pixels if i[1] == "u8"]
    assert [i[2] for i in unpacks] == [(rows, 84, 84, 4)] * 2
    assert all(reads(i, "u32") for i in unpacks)
    # and the bytes are what the convolutions read: three forwards and
    # a backward of the loss (online on obs; target and, for double-Q,
    # online on new_obs), two forwards of the priority pass on the
    # updated weights. A seventh would be its own target forward.
    made = {u[0] for u in unpacks}
    convs = [
        i for i in by_name.values()
        if " convolution(" in i[5] and made & set(i[4])
    ]
    assert 4 <= len(convs) <= 6, [(i[0], i[4]) for i in convs]
    assert len({tuple(i[4]) for i in convs}) == len(convs)


@pytest.mark.parametrize("b, decay", [
    pytest.param(64, "head", id="qwen3next-a-decay-a-head"),
    pytest.param(16, "channel", id="ling3flash-a-decay-a-key-channel"),
])
def test_v5e_delta_step_kernel_compiles_in_place(v5e_mesh, b, decay):
    """The one-token gated-delta kernel (ops/deltanet.py) at a sequence
    cell's width, its streams x 32 heads of 128 x 128, chained in a scan
    under ``shard_map`` like the lane's decode steps (the call has to
    say how its outputs vary over the mesh), with a decay a head (Gated
    DeltaNet) and a decay a key channel (Kimi Delta Attention, whose row
    is turned in the kernel as ``k`` is): Mosaic takes both, and the
    matrices (0.13 GB at 64 streams) are written where they lie (no
    second copy, no scratch)."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops import deltanet

    h, dk, dv = 32, 128, 128
    axis = sharding_lib.data_axis(v5e_mesh)
    rows = sharding_lib.batch_sharded(v5e_mesh)
    on = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32, sharding=rows)

    def steps(state, q, k, v, g, beta):
        def one(s, _):
            with jax.named_scope("rollout/act/linear_attn"):
                return deltanet.gated_delta_step_kernel(s, q, k, v, g, beta)

        return jax.lax.scan(one, state, None, length=2)

    sharded = jax.shard_map(
        steps, mesh=v5e_mesh, in_specs=(P(axis),) * 6,
        out_specs=(P(axis), P(None, axis)),
    )
    compiled = (
        jax.jit(sharded, donate_argnums=(0,))
        .lower(on(b, h, dk, dv), on(b, h, dk), on(b, h, dk), on(b, h, dv),
               on(b, h, dk) if decay == "channel" else on(b, h), on(b, h))
        .compile()
    )
    calls = [
        line for line in compiled.as_text().splitlines()
        if "tpu_custom_call" in line and "custom-call(" in line
    ]
    # the device op carries the caller's scope (the trace files its
    # time under it) though the kernel is a jit of its own
    assert calls and all("rollout/act/linear_attn" in line for line in calls)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == b * h * dk * dv * 4
    assert mem.temp_size_in_bytes < 16e6


# streams, the run's layers, and the shape of a stream's ``B`` / ``C``
SSD_STEP_CELLS = [
    pytest.param(16, 5, (128,), id="granite4h-rows-every-head-shares"),
    pytest.param(32, 1, (8, 128), id="nemotron3nano-8-groups-a-run-of-one"),
]


@pytest.mark.parametrize("b,layers,rows_shape", SSD_STEP_CELLS)
def test_v5e_ssd_step_kernel_compiles_in_place(v5e_mesh, b, layers, rows_shape):
    """The one-token state-space kernel (ops/ssd.py) at the granite
    cell's width, a run of 5 layers of 16 streams x 64 heads of 64 x 128,
    and at the Nemotron-H cell's, a run of ONE layer of 32 streams with
    ``B`` and ``C`` in 8 groups (a group's 8 heads a grid step, the
    group's row squeezed out of a ``(B, G, 1, N)`` block), as the lane
    runs it: the run's scan over layers chained in a scan of 2 steps
    under ``shard_map``, the leaf donated. Mosaic takes it, the leaf is
    written where it lies (aliased through both scans and the call: no
    second copy, no scratch), and the device op carries the caller's
    scopes, under which the trace files its time."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops import ssd

    h, p, n = 64, 64, 128
    axis = sharding_lib.data_axis(v5e_mesh)
    rows = sharding_lib.batch_sharded(v5e_mesh)
    on = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32, sharding=rows)

    def steps(leaf, x, dt, a, bb, cc):
        def run(leaf, _):
            def layer(carry, i):
                leaf, y = carry
                with jax.named_scope("rollout/act"), jax.named_scope("ssm/step"):
                    return ssd.ssd_step_kernel(leaf, i, x + y, dt, a, bb, cc), None

            return jax.lax.scan(
                layer, (leaf, jnp.zeros_like(x)), jnp.arange(layers))[0]

        return jax.lax.scan(run, leaf, None, length=2)

    sharded = jax.shard_map(
        steps, mesh=v5e_mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P(axis), P(axis)),
        out_specs=(P(axis), P(None, axis)),
    )
    compiled = (
        jax.jit(sharded, donate_argnums=(0,))
        .lower(on(b, layers, h, p, n), on(b, h, p), on(b, h),
               _on(v5e_mesh, (h,), np.float32), on(b, *rows_shape), on(b, *rows_shape))
        .compile()
    )
    calls = [
        line for line in compiled.as_text().splitlines()
        if "tpu_custom_call" in line and "custom-call(" in line
    ]
    assert calls and all(
        re.search(r"rollout/act.*ssm/step", line) for line in calls)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == b * layers * h * p * n * 4
    assert mem.temp_size_in_bytes < 16e6


# a group of the learn form's streams, tokens, key heads, query heads a
# key head, head, cache depth, window: the four sequence cells' layers
FRAGMENT_LAYERS = {
    "smallthinker_full": (16, 256, 4, 7, 128, 8192, None),
    "smallthinker_ring": (16, 256, 4, 7, 128, 4096, 4096),
    "qwen3next": (16, 128, 2, 8, 256, 2048, None),
    "granite4h": (16, 256, 8, 4, 64, 2048, None),
    # the mixed-geometry cell: six query heads a key head over the
    # episode's rows, eight over a ring of one key block
    "laguna_full": (16, 256, 8, 6, 128, 4096, None),
    "laguna_ring": (16, 256, 8, 8, 128, 512, 512),
    # the latent cell: ONE key head of 576 lanes (the array's whole minor
    # dimension, no whole lane tiles) for 32 query heads in four tiles,
    # the value the row's leading 512 lanes, read out of the key cache
    "xing4_latent": (8, 128, 1, 32, 576, 2048, None, 512),
    "ling3flash_latent": (16, 256, 1, 32, 576, 4096, None, 512),
    # the block-diffusion cell: eight query heads a key head under the
    # block rule (its clean pass), and with a clean pass's rows as a
    # second block of own keys (its noisy passes)
    "sdar_clean": (16, 256, 4, 8, 128, 4096, None),
    "sdar_noisy": (16, 256, 4, 8, 128, 4096, None),
}
# the mask's block and whether a clean pass's rows come beside the own
FRAGMENT_BLOCKS = {"sdar_clean": (4, False), "sdar_noisy": (4, True)}


@pytest.mark.parametrize("layer", list(FRAGMENT_LAYERS))
def test_v5e_fragment_attention_kernel_compiles(v5e_mesh, layer):
    """The learn form's attention kernel (ops/flash_attention.py) at a
    sequence cell's width, forward, recomputation and backward under
    ``shard_map`` and a checkpoint as the learn program runs it. Mosaic
    takes all three heads (64 rides two key heads a block) and the
    latent row's 576 lanes as one key head in four query tiles; the three
    custom calls carry the caller's scope, under which the trace files
    their time; and no float32 array of (tokens, stored rows) exists
    anywhere in the compiled program: the score matrices are never
    written."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops import flash_attention

    b, t, kv, group, d, depth, window, *dv = FRAGMENT_LAYERS[layer]
    (dv,) = dv or (d,)
    block, noisy = FRAGMENT_BLOCKS.get(layer, (1, False))
    axis = sharding_lib.data_axis(v5e_mesh)
    rows = sharding_lib.batch_sharded(v5e_mesh)
    on = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=rows)
    bf, i32 = jnp.bfloat16, jnp.int32

    def grads(q, k, v, kc, vc, pos0, seg, positions):
        @jax.checkpoint
        def attention(q, k, v):
            with jax.named_scope("learn/attn"):
                return flash_attention.fragment_attention(
                    q, k, v, kc, kc if dv != d else vc, pos0, seg, positions,
                    window=window, block=block, clean=(k, v) if noisy else None)

        return jax.grad(
            lambda *qkv: jnp.sum(jnp.square(attention(*qkv))),
            argnums=(0, 1, 2))(q, k, v)

    sharded = jax.shard_map(
        grads, mesh=v5e_mesh, in_specs=(P(axis),) * 8, out_specs=P(axis))
    compiled = jax.jit(sharded).lower(
        on(bf, b, t, kv, group, d), on(bf, b, t, kv, d), on(bf, b, t, kv, dv),
        on(bf, b, depth, kv * d), on(bf, b, depth, kv * d),
        on(i32, b), on(i32, b, t), on(i32, b, t),
    ).compile()
    text = compiled.as_text()
    calls = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "custom-call(" in line
    ]
    assert len(calls) == 3 and all("learn/attn" in line for line in calls)
    assert sum("fragment_attention_bwd" in line for line in calls) == 1
    assert not re.search(rf"f32\[[0-9,]*{t},({depth}|{depth + t})\]", text)


def test_v5e_fragment_attention_kernel_takes_a_choice(v5e_mesh, monkeypatch):
    """The learned-index cell's layer (a group of 16 streams, T 256, 32
    query heads over 4 key heads of 128, depth 8,192, 16 index heads of
    64, 2,048 rows a query) through ``cached_attention(select=)`` where
    the backend is a TPU, under ``shard_map`` and the block's checkpoint:
    the rule admits it, the counter says ``selected_kernel``, Mosaic
    takes the choice's int8 blocks in both kernels, the three custom
    calls (forward, recomputation, backward) stand under
    ``learn/attn/scores``, and the compiled program holds no float32
    array of (tokens, rows) of the ATTENTION's 32 heads: the only score
    tiles left are the index's, a stream at a time."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops import cached_attention
    from ray_tpu.telemetry import metrics

    b, t, kv, group, d, depth, index_heads, index_dim, top_k = (
        16, 256, 4, 8, 128, 8192, 16, 64, 2048)
    h = kv * group
    axis = sharding_lib.data_axis(v5e_mesh)
    rows = sharding_lib.batch_sharded(v5e_mesh)
    on = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=rows)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = metrics.attention_fragment_lowerings()

    def grads(q, k, v, kc, vc, qi, wi, ki, ic, pos0, seg, positions):
        @jax.checkpoint
        def attention(q, k, v):
            return cached_attention.cached_attention(
                q, k, v, (kc, vc), {"seg": seg, "positions": positions, "pos0": pos0},
                scale=d ** -0.5, window=None, dtype=bf, scope="learn/attn",
                select=cached_attention.Selection(qi, wi, ki, ic, top_k))[0]

        return jax.grad(
            lambda *qkv: jnp.sum(jnp.square(attention(*qkv))),
            argnums=(0, 1, 2))(q, k, v)

    sharded = jax.shard_map(
        grads, mesh=v5e_mesh, in_specs=(P(axis),) * 12, out_specs=P(axis))
    compiled = jax.jit(sharded).lower(
        on(f32, b, t, h, d), on(bf, b, t, kv, d), on(bf, b, t, kv, d),
        on(bf, b, depth, kv * d), on(bf, b, depth, kv * d),
        on(bf, b, t, index_heads, index_dim), on(f32, b, t, index_heads),
        on(bf, b, t, index_dim), on(bf, b, depth, index_dim),
        on(i32, b), on(i32, b, t), on(i32, b, t),
    ).compile()
    after = metrics.attention_fragment_lowerings()
    moved = {path: after[path] - before.get(path, 0) for path in after}
    assert moved.get("selected_kernel") and not moved.get("selected_xla")
    text = compiled.as_text()
    calls = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "custom-call(" in line
    ]
    assert len(calls) == 3 and all("learn/attn/scores" in line for line in calls)
    # the forward pass, its recomputation, the backward pass
    assert ["transpose(jvp" in line for line in calls] == [False, True, True]
    assert ["rematted_computation" in line for line in calls] == [False, True, False]
    assert ["fragment_attention_bwd" in line for line in calls] == [False, False, True]
    assert not re.search(rf"f32\[[0-9,]*{h},{t},{depth + t}\]", text)
    assert not re.search(rf"f32\[[0-9,]*{group},{t},({depth}|{depth + t})\]", text)
    # the choice crosses HBM a byte a pair, never widened there
    assert not re.search(rf"(s32|f32)\[{b},{t},({depth}|{depth + t})\]", text)
    # and leaves the fusion that makes it as the kernels' two int8 parts:
    # no whole choice is written beside them
    assert re.search(rf"s8\[{b},{t},{depth}\]", text)
    assert not re.search(rf"(pred|s8)\[{b},{t},{depth + t}\]", text)


# all of a cell's streams, key heads, query heads a key head, head, depth:
# the four cells' full-depth softmax layers as the rollout steps them
STEP_LAYERS = {
    "smallthinker_full": (32, 4, 7, 128, 8192),
    "laguna_full": (16, 8, 6, 128, 4096),
    "qwen3next": (64, 2, 8, 256, 2048),
    "granite4h": (16, 8, 4, 64, 2048),
    # a block of 4 tokens: its 4 x 8 queries of a key head as one tile
    "sdar_block": (16, 4, 32, 128, 4096),
    # Xing4's latent rows: one key head of 576 lanes for 32 query heads,
    # the value its leading 512 lanes (a sixth number: no value cache)
    "xing4_latent": (32, 1, 32, 576, 2048, 512),
    "ling3flash_latent": (16, 1, 32, 576, 4096, 512),
    # the three ring cells' window layers (Phi-4's differential pairs
    # are ten key heads of 128 lanes): a ring of 512 rows is ONE key block
    "smallthinker_ring": (32, 4, 7, 128, 4096),
    "laguna_ring": (16, 8, 8, 128, 512),
    "phi4flash_ring": (16, 10, 4, 128, 512),
}


@pytest.mark.parametrize("layer", list(STEP_LAYERS))
def test_v5e_step_attention_kernel_compiles(v5e_mesh, layer):
    """The one-token form's kernel (ops/flash_attention.step_attention)
    at a sequence cell's streams and width under ``shard_map``: Mosaic
    takes all the geometries (6 and 7 query heads padded to the
    sublanes, 64 two key heads a block, the latent row's one key head
    of 4.5 lane tiles with its leading lanes for a value) as ONE custom
    call under the caller's scope, and no float32 array over the cache's
    slots exists in the compiled program: no score of a slot past a
    stream's depth is computed."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops import flash_attention

    b, kv, group, d, depth, *value = STEP_LAYERS[layer]
    axis = sharding_lib.data_axis(v5e_mesh)
    rows = sharding_lib.batch_sharded(v5e_mesh)
    on = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=rows)
    bf = jnp.bfloat16

    def step(q, held, kc, vc=None):
        with jax.named_scope("rollout/act/attn/scores"):
            return flash_attention.step_attention(
                q, kc, vc, held, value_dim=value[0] if value else None)

    caches = (on(bf, b, depth, kv * d),) * (1 if value else 2)
    sharded = jax.shard_map(
        step, mesh=v5e_mesh, in_specs=(P(axis),) * (2 + len(caches)),
        out_specs=P(axis))
    text = jax.jit(sharded).lower(
        on(bf, b, 1, kv, group, d), on(jnp.int32, b), *caches,
    ).compile().as_text()
    calls = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "custom-call(" in line
    ]
    assert len(calls) == 1 and "rollout/act/attn/scores" in calls[0]
    assert "step_attention" in calls[0]
    assert not re.search(rf"f32\[[0-9,]*{depth}\]", text)


def test_v5e_eva_step_kernel_compiles(v5e_mesh):
    """The two-store one-token kernel (ops/eva_attention.step_attention)
    at the EvaByte cell's streams and width (16 streams, 8 heads of 128,
    a window store of 2,048 rows and a summary store of 640) under
    ``shard_map``: Mosaic takes it as ONE custom call under the caller's
    scope, and no float32 array over either store's slots exists in the
    compiled program: no score of a slot outside the two masks is
    computed by XLA beside it."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops import eva_attention

    b, h, d, window, chunk, summaries = 16, 8, 128, 2048, 16, 640
    axis = sharding_lib.data_axis(v5e_mesh)
    rows = sharding_lib.batch_sharded(v5e_mesh)
    on = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype, sharding=rows)
    bf = jnp.bfloat16

    def step(q, positions, *stores):
        with jax.named_scope("rollout/act/eva/scores"):
            return eva_attention.step_attention(
                q, stores, positions, window=window, chunk=chunk)

    stores = (on(bf, b, window, h * d),) * 2 + (on(bf, b, summaries, h * d),) * 2
    sharded = jax.shard_map(
        step, mesh=v5e_mesh, in_specs=(P(axis),) * 6, out_specs=P(axis))
    text = jax.jit(sharded).lower(
        on(bf, b, h, d), on(jnp.int32, b), *stores).compile().as_text()
    calls = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "custom-call(" in line
    ]
    assert len(calls) == 1 and "rollout/act/eva/scores" in calls[0]
    assert "eva_step_attention" in calls[0]
    assert not re.search(rf"f32\[[0-9,]*({window}|{summaries})\]", text)


def test_v5e_selective_scan_kernels_compile(v5e_mesh):
    """The fragment-form selective scan (ops/selective_scan.py) at the
    Phi-4-mini-flash cell's size (16 streams x 256 tokens, 16 states x
    5,120 channels) under ``shard_map`` and a block's ``jax.checkpoint``,
    value and gradient of every operand: Mosaic takes the forward kernel
    (the pass and its recomputation) and the backward kernel, all under
    the caller's ``learn/scan/step`` scope, where the trace files their
    time (perf/layer_metrics/scan.fragment_hbm_roofline_pct.py), and no
    buffer of a fragment's states ``(16, 256, 16, 5120)`` nor of its
    chunks' starts exists."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops import selective_scan

    b, t, n, c = 16, 256, 16, 5120
    axis = sharding_lib.data_axis(v5e_mesh)
    rows = sharding_lib.batch_sharded(v5e_mesh)
    on = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32, sharding=rows)

    def loss(state, u, dt, a, bb, cc, resets):
        @jax.checkpoint
        def block(state, u, dt, a, bb, cc):
            with jax.named_scope("learn/scan/step"):
                y, after = selective_scan.selective_scan_kernel(
                    state, u, dt, a, bb, cc, resets)
            return jnp.tanh(y) * u, after

        y, after = block(state, u, dt, sharding_lib.varying(a, axis), bb, cc)
        return jnp.sum(y) + jnp.sum(after)

    def value_and_grad(*operands):
        value, grads = jax.value_and_grad(loss, argnums=tuple(range(6)))(*operands)
        return value[None], grads

    sharded = jax.shard_map(
        value_and_grad, mesh=v5e_mesh,
        in_specs=(P(axis), P(axis), P(axis), P(), P(axis), P(axis), P(axis)),
        out_specs=(P(axis), (P(axis),) * 6))
    compiled = jax.jit(sharded).lower(
        on(b, n, c), on(b, t, c), on(b, t, c), _on(v5e_mesh, (n, c), np.float32),
        on(b, t, n), on(b, t, n), on(b, t)).compile()
    text = compiled.as_text()
    calls = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "custom-call(" in line
    ]
    assert len(calls) == 3 and all("learn/scan/step" in line for line in calls)
    assert sum("selective_scan_fwd" in line for line in calls) == 2
    assert sum("selective_scan_bwd" in line for line in calls) == 1
    assert not re.search(rf"f32\[{b},({t}|{t // 16}),{n},{c}\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 400e6


def test_v5e_delta_rule_fragment_kernels_compile(v5e_mesh):
    """The fragment form of the gated delta rule with a decay a head
    (ops/deltanet.gated_delta_chunked_kernel) at the Qwen3-Next cell's
    size (16 streams a call x 128 tokens, 32 heads of 128 x 128, two
    chunks of 64 side by side) under ``shard_map`` and a block's
    ``jax.checkpoint``, value and gradient of every operand: Mosaic
    takes the forward kernel (the pass and its recomputation) and the
    backward kernel, all under the caller's ``learn/linear_attn`` and its
    ``rule`` (jax wraps ``jvp(...)`` around the outer scope alone in the
    first pass), where the trace files their time
    (perf/layer_metrics/linear_attn.rule_device_ms_per_update.py), and no
    ``(C, C)`` product of the text's, ``f32[16,32,64,64]``, exists."""
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops import deltanet

    b, t, h, dk, dv = 16, 128, 32, 128, 128
    axis = sharding_lib.data_axis(v5e_mesh)
    rows = sharding_lib.batch_sharded(v5e_mesh)
    on = lambda *shape: jax.ShapeDtypeStruct(shape, np.float32, sharding=rows)

    def loss(state, q, k, v, g, beta, resets):
        @jax.checkpoint
        def block(state, q, k, v, g, beta):
            with jax.named_scope("learn/linear_attn"), jax.named_scope("rule"):
                o, after = deltanet.gated_delta_chunked_kernel(
                    state, q, k, v, g, beta, resets, 64)
            return jnp.tanh(o) * v, after

        o, after = block(state, q, k, v, g, beta)
        return jnp.sum(o) + jnp.sum(after)

    def value_and_grad(*operands):
        value, grads = jax.value_and_grad(loss, argnums=tuple(range(6)))(*operands)
        return value[None], grads

    sharded = jax.shard_map(
        value_and_grad, mesh=v5e_mesh, in_specs=(P(axis),) * 7,
        out_specs=(P(axis), (P(axis),) * 6))
    compiled = jax.jit(sharded).lower(
        on(b, h, dk, dv), on(b, t, h, dk), on(b, t, h, dk), on(b, t, h, dv),
        on(b, t, h), on(b, t, h), on(b, t)).compile()
    text = compiled.as_text()
    calls = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and "custom-call(" in line
    ]
    assert len(calls) == 3 and all(
        "learn/linear_attn/rule/" in line or "learn/linear_attn)/rule/" in line
        for line in calls)
    assert sum("gated_delta_chunked_fwd/" in line for line in calls) == 2
    assert sum("gated_delta_chunked_bwd/" in line for line in calls) == 1
    assert not re.search(rf"f32\[{b},{h},64,64\]", text)


def test_v5e_tree_update_pays_for_its_levels(v5e_mesh):
    """The DQN cell's priority refresh (ops/segment_tree.py: an
    (8, 512) update of a 131,072-leaf f64 tree pair) as the chip's
    compiler leaves it: no array with a minor dimension of 2, which
    the chip pads to 128 lanes (PR 37: ``reshape f32[131072,2]`` of
    the WHOLE array at every level, 64 x 90 us of a 6.9 ms program),
    and no gather wider than an update's 512 leaves (how a strided
    ``jax.numpy`` index lowers: 4.1 ms at the widest level)."""
    from ray_tpu.ops.segment_tree import DeviceSumTree

    tree = DeviceSumTree.__new__(DeviceSumTree)  # no array on a described chip
    tree.capacity, tree.mesh, tree.label = CELL_ROWS, v5e_mesh, "default_policy"
    with sharding_lib.f64_scope():
        full = _on(v5e_mesh, (2 * CELL_ROWS,), np.float64)
        text = (
            tree._build_update_fn(8, 512)
            .lower(full, full, _on(v5e_mesh, (8, 512), np.int32),
                   _on(v5e_mesh, (8, 512), np.float64),
                   _on(v5e_mesh, (8, 512), np.bool_))
            .compile()
            .as_text()
        )
    assert re.findall(r"= \w+\[\d+,2\]\{\S* \w+\(", text) == []
    gathers = [int(n) for n in re.findall(r"= \w+\[(\d+)\]\S* gather\(", text)]
    assert max(gathers, default=0) <= 512, gathers
    # the rebuild is there, as strided slices of every width
    assert len(re.findall(r"= f32\[65536\]\S* slice\(", text)) >= 8
