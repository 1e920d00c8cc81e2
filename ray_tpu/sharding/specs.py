"""NamedSharding / PartitionSpec builders.

The placement vocabulary of the learner plane, as first-class
functions instead of per-call-site constructions:

  - params / optimizer state / aux (target nets, frame pools):
    replicated by default — every shard holds the full tree — or
    **per-leaf partitioned** over the mesh's ``"model"`` axis via
    ordered name-pattern rules (:func:`param_pspecs`, megatron-style
    defaults in :func:`default_partition_rules`);
  - SampleBatch columns: sharded over the leading (row) dim on the
    mesh's data axis;
  - ragged leading dims (a column whose row count doesn't divide the
    shard count) fall back to replication rather than erroring — the
    ``get_naive_sharding`` pattern from the retrieved references. The
    fallback is **observable**: it fires a
    ``jit:fallback_replicated`` trace event and bumps
    ``ray_tpu_sharding_fallback_replicated_total`` so a mis-sharded
    hot path shows in the Prometheus scrape instead of just running
    slow.

Everything derives the data axis from the mesh object (its first
axis: ``"batch"`` on the meshes this package builds).
"""

from __future__ import annotations

import collections
import functools
import re
import threading
from typing import Dict, Iterable, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.sharding.mesh import MODEL_AXIS, data_axis, num_shards

# -- dispatch-diet caches (benchmarks/MFU.md "dispatch overhead") ------
#
# NamedSharding construction is pure but not free, and the hot call
# sites (per-batch ``sharding_tree`` in JaxPolicy.batch_shardings, the
# per-call replicated()/batch_sharded() in feeders and supersteps)
# used to rebuild identical objects every dispatch. Both builders
# memoize on the (hashable) mesh; ``sharding_tree`` additionally keeps
# a bounded signature-keyed memo of resolved trees with an
# object-identity fast path for the immediately-previous tree. A
# genuinely changed sharding (new mesh, a column whose leading dim
# stops dividing the shard count, a changed replicate set) changes the
# signature and misses to the full derivation — the invalidation
# contract tests/test_dispatch_diet.py pins.


@functools.lru_cache(maxsize=128)
def replicated(mesh: Mesh) -> NamedSharding:
    """Full copy on every device (params, opt state, scalars)."""
    return NamedSharding(mesh, P())


@functools.lru_cache(maxsize=128)
def batch_sharded(mesh: Mesh, ndim_prefix: int = 1) -> NamedSharding:
    """Leading-dim row sharding over the data axis. ``ndim_prefix``
    places the axis deeper, e.g. 2 -> P(None, axis) for (T, B, ...)
    layouts."""
    spec = (None,) * (ndim_prefix - 1) + (data_axis(mesh),)
    return NamedSharding(mesh, P(*spec))


def _note_fallback_replicated(shape) -> None:
    """A batch leaf that SHOULD row-shard fell back to replication
    (ragged leading dim on a multi-shard mesh): emit the
    ``jit:fallback_replicated`` event + counter so the degraded
    placement is visible in the scrape, not just slow."""
    try:
        from ray_tpu.telemetry import metrics as _tm

        _tm.inc_sharding_fallback()
        from ray_tpu.util import tracing as _tr

        if _tr.is_enabled():
            _tr.event(
                "jit:fallback_replicated", shape=str(tuple(shape))
            )
    except Exception:  # telemetry must never break placement
        pass


def leaf_sharding(x, mesh: Mesh) -> NamedSharding:
    """Per-array placement: shard rows when the leading dim divides
    the shard count, otherwise replicate (uneven-dim fallback —
    counted, see :func:`_note_fallback_replicated`)."""
    shape = getattr(x, "shape", ())
    if len(shape) >= 1 and shape[0] % num_shards(mesh) == 0 and shape[0] > 0:
        return batch_sharded(mesh)
    if len(shape) >= 1 and shape[0] > 0 and num_shards(mesh) > 1:
        _note_fallback_replicated(shape)
    return replicated(mesh)


# lazily-bound telemetry.fleetview module; the cross-process put_global
# path stamps a collective drain-point arrival there so the fleet
# aggregator can attribute which host reached the placement last
# (record_arrival is one flag check when no exporter runs)
_FLEETVIEW = None


def _note_collective_arrival(point: str) -> None:
    global _FLEETVIEW
    if _FLEETVIEW is None:
        try:
            from ray_tpu.telemetry import fleetview

            _FLEETVIEW = fleetview
        except Exception:  # telemetry must never break placement
            return
    try:
        _FLEETVIEW.record_arrival(point)
    except Exception:
        pass


@functools.lru_cache(maxsize=128)
def mesh_spans_processes(mesh: Mesh) -> bool:
    """Whether this mesh's devices live in more than one jax process —
    the DCN case, where a plain ``device_put`` of a host value cannot
    address the remote shards and placement must go through
    :func:`put_global` instead."""
    try:
        return (
            len({d.process_index for d in mesh.devices.flat}) > 1
        )
    except Exception:
        return False


def put_global(x, sharding: NamedSharding):
    """``device_put`` that also works when the sharding's mesh spans
    processes (multi-host learner fleets, docs/fleet.md).

    Single-process meshes take the plain ``jax.device_put`` path —
    byte-identical behavior to before. On a cross-process mesh, every
    process must call this with the SAME host value (the lockstep SPMD
    contract the multi-host tests pin): each process carves out the
    row block its addressable shards own and the global array is
    assembled via ``jax.make_array_from_process_local_data`` — the
    device-replay rings allocate their cross-host shards through
    exactly this path."""
    mesh = getattr(sharding, "mesh", None)
    if mesh is None or not mesh_spans_processes(mesh):
        return jax.device_put(x, sharding)
    # collective drain point: every process reaches this placement in
    # lockstep, so the arrival stamp lets the fleet aggregator name
    # the straggler (telemetry/fleetview.py)
    _note_collective_arrival("put_global")
    import numpy as np

    arr = np.asarray(x)
    # the union of this process's shard index-boxes (contiguous per
    # dim for the 1-D row layouts the learner uses)
    idx_map = sharding.addressable_devices_indices_map(arr.shape)
    local = arr
    if idx_map:
        slices = []
        for d in range(arr.ndim):
            starts = [
                (idx[d].start or 0) for idx in idx_map.values()
            ]
            stops = [
                (
                    idx[d].stop
                    if idx[d].stop is not None
                    else arr.shape[d]
                )
                for idx in idx_map.values()
            ]
            slices.append(slice(min(starts), max(stops)))
        local = arr[tuple(slices)]
    return jax.make_array_from_process_local_data(
        sharding, local, arr.shape
    )


# signature -> (resolved tree, fallback shapes) LRU; one entry per
# distinct (mesh, column-name, placement-kind, replicate-set) batch
# signature — steady training resolves its per-batch tree with dict
# lookups instead of per-leaf reconstruction
_TREE_MEMO: "collections.OrderedDict" = collections.OrderedDict()
_TREE_MEMO_MAX = 256
_TREE_MEMO_LOCK = threading.Lock()
# object-identity fast path: (id(tree), signature-independent reuse is
# NOT safe — ids recycle), so the identity memo pins the tree object
# itself alongside its resolved result
_LAST_TREE: Optional[Tuple[object, Mesh, frozenset, dict, tuple]] = None


def clear_sharding_caches() -> None:
    """Drop the resolved-tree memos (tests; mesh teardown)."""
    global _LAST_TREE
    with _TREE_MEMO_LOCK:
        _TREE_MEMO.clear()
        _LAST_TREE = None
    replicated.cache_clear()
    batch_sharded.cache_clear()


def _flat_signature(tree: dict, mesh: Mesh, replicate_keys) -> Optional[tuple]:
    """Placement signature of a flat dict-of-arrays batch: per column,
    which of the three leaf_sharding outcomes applies (replicate /
    row-shard / ragged-fallback-replicate). None when the tree isn't
    the flat prepared-batch shape — the caller takes the full path."""
    n = num_shards(mesh)
    sig = []
    for k, v in tree.items():
        shape = getattr(v, "shape", None)
        if shape is None or isinstance(v, dict):
            return None
        if k in replicate_keys:
            kind = 0
        elif len(shape) >= 1 and shape[0] > 0 and shape[0] % n == 0:
            kind = 1
        elif len(shape) >= 1 and shape[0] > 0 and n > 1:
            kind = 2  # ragged: replicate + counted fallback
        else:
            kind = 0
        sig.append((k, kind) if kind != 2 else (k, 2, tuple(shape)))
    return tuple(sig)


def sharding_tree(tree, mesh: Mesh, replicate_keys: Iterable[str] = ()):
    """Per-leaf sharding tree for a (possibly nested) batch tree.
    Top-level dict keys in ``replicate_keys`` pin to replication no
    matter their shape — e.g. the deduplicated frame pool, which every
    shard gathers from locally.

    Flat dict-of-arrays trees (every prepared train batch) resolve
    through a signature-keyed memo: the NamedSharding tree is built
    once per distinct placement signature and reused, with the ragged
    fallback still counted per call (the degraded placement stays
    visible in the scrape). Nested trees take the full per-leaf
    derivation every time."""
    global _LAST_TREE
    replicate_keys = frozenset(replicate_keys)
    if type(tree) is dict:
        # identity fast path: the same tree object re-resolved against
        # the same mesh (feeders re-deriving placement for a batch they
        # already resolved) costs three `is` checks
        last = _LAST_TREE
        if (
            last is not None
            and last[0] is tree
            and last[1] is mesh
            and last[2] == replicate_keys
        ):
            for shape in last[4]:
                _note_fallback_replicated(shape)
            return dict(last[3])
        sig = _flat_signature(tree, mesh, replicate_keys)
        if sig is not None:
            key = (mesh, sig, replicate_keys)
            with _TREE_MEMO_LOCK:
                hit = _TREE_MEMO.get(key)
                if hit is not None:
                    _TREE_MEMO.move_to_end(key)
            if hit is None:
                out = {}
                fallbacks = []
                for entry in sig:
                    k, kind = entry[0], entry[1]
                    out[k] = (
                        batch_sharded(mesh)
                        if kind == 1
                        else replicated(mesh)
                    )
                    if kind == 2:
                        fallbacks.append(entry[2])
                hit = (out, tuple(fallbacks))
                with _TREE_MEMO_LOCK:
                    _TREE_MEMO[key] = hit
                    while len(_TREE_MEMO) > _TREE_MEMO_MAX:
                        _TREE_MEMO.popitem(last=False)
            for shape in hit[1]:
                _note_fallback_replicated(shape)
            _LAST_TREE = (tree, mesh, replicate_keys, hit[0], hit[1])
            return dict(hit[0])
    if isinstance(tree, dict) and replicate_keys:
        return {
            k: (
                jax.tree_util.tree_map(
                    lambda x: replicated(mesh), v
                )
                if k in replicate_keys
                else jax.tree_util.tree_map(
                    lambda x: leaf_sharding(x, mesh), v
                )
            )
            for k, v in tree.items()
        }
    return jax.tree_util.tree_map(lambda x: leaf_sharding(x, mesh), tree)


def tree_nbytes(tree) -> int:
    """Total array bytes of a (host or device) pytree — the H2D
    payload accounting unit behind ``ray_tpu_h2d_bytes_total``
    (telemetry/metrics.py): callers count a tree right before its
    ``device_put`` so the counter reflects what actually crosses the
    wire."""
    return int(
        sum(
            int(getattr(x, "nbytes", 0))
            for x in jax.tree_util.tree_leaves(tree)
        )
    )


# -- per-leaf partitioned param trees (2-D data x model meshes) --------
#
# The rule grammar (docs/sharding.md "2-D mesh & param partitioning"):
# an ordered sequence of ``(pattern, spec)`` pairs. ``pattern`` is a
# regex searched against the leaf's "/"-joined key path (e.g.
# "layer_0/attn/wq"); the FIRST match wins. ``spec`` is a
# PartitionSpec (or a plain tuple of axis names / None) naming, per
# array dimension, the mesh axis that splits it. Axes absent from the
# mesh prune to None, so rules written against "model" degrade to
# replication on a 1-D data mesh. Anything unmatched replicates.


def default_partition_rules() -> Tuple:
    """Megatron-style defaults for the transformer torso
    (``models/transformer.py`` naming): attention QKV projections
    split on the head dim, the output projection on its input (head)
    dim, MLP up on its output dim, MLP down on its input dim —
    embeddings, layernorms, heads and biases-of-reduced-outputs
    replicated. Ordered; first match wins; ``.*`` -> replicate is the
    implicit tail."""
    return (
        (r"attn/w[qkv]$", P(None, MODEL_AXIS, None)),
        (r"attn/b[qkv]$", P(MODEL_AXIS)),
        (r"attn/wo$", P(MODEL_AXIS, None, None)),
        (r"mlp/w_up$", P(None, MODEL_AXIS)),
        (r"mlp/b_up$", P(MODEL_AXIS)),
        (r"mlp/w_down$", P(MODEL_AXIS, None)),
    )


def _is_pspec(x) -> bool:
    return isinstance(x, P)


def _path_names(path) -> Tuple[str, ...]:
    out = []
    for k in path:
        if hasattr(k, "key"):
            out.append(str(k.key))
        elif hasattr(k, "name"):
            out.append(str(k.name))
        elif hasattr(k, "idx"):
            out.append(str(k.idx))
        else:  # pragma: no cover - future key kinds
            out.append(str(k))
    return tuple(out)


def _fit_spec(spec, ndim: int, mesh: Mesh):
    """Normalize one rule spec against a concrete leaf: tuple -> P,
    axes the mesh doesn't have -> None, rank mismatches that would
    drop a named axis -> replicate (never silently mis-place)."""
    entries = list(spec) if not isinstance(spec, P) else list(spec)
    entries = [
        (e if e is None or e in mesh.axis_names else None)
        for e in entries
    ]
    if len(entries) > ndim:
        if any(e is not None for e in entries[ndim:]):
            return P()
        entries = entries[:ndim]
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def param_pspecs(tree, mesh: Mesh, rules: Sequence) -> object:
    """Per-leaf :class:`PartitionSpec` tree for a param tree, from
    ordered ``(pattern, spec)`` name rules (first match wins; no
    match -> replicated). Leaf names are the "/"-joined key paths of
    the tree."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def one(path, x):
        name = "/".join(_path_names(path))
        ndim = len(getattr(x, "shape", ()))
        for pat, spec in compiled:
            if pat.search(name):
                return _fit_spec(spec, ndim, mesh)
        return P()

    return jax.tree_util.tree_map_with_path(one, tree)


def named_tree(mesh: Mesh, pspec_tree):
    """PartitionSpec tree -> NamedSharding tree (same structure) for
    ``sharded_jit`` in/out specs. A bare ``P()`` maps to
    :func:`replicated`."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspec_tree, is_leaf=_is_pspec
    )


def manual_pspecs(mesh: Mesh, pspec_tree):
    """The spec tree a ``shard_map`` over ``mesh`` should take for a
    placement ``pspec_tree``: names of size-1 axes dropped. On such an
    axis a slice IS the leaf and the model emits no collectives
    (models/transformer._bound_parallel_axis), so typing the leaf as
    varying over it would only make jax insert single-device
    reductions the replicated program does not have — the
    ``model_parallel=1`` program must stay literally the replicated
    one (the bitwise-parity geometry)."""
    sizes = dict(mesh.shape)

    def keep(e):
        if e is None:
            return None
        names = tuple(
            n for n in ((e,) if isinstance(e, str) else e)
            if sizes[n] > 1
        )
        if not names:
            return None
        return names[0] if len(names) == 1 else names

    def one(spec):
        entries = [keep(e) for e in spec]
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    return jax.tree_util.tree_map(one, pspec_tree, is_leaf=_is_pspec)


def param_sharding(tree, mesh: Mesh, rules: Sequence):
    """Per-leaf :class:`NamedSharding` tree for a param tree — the
    builder the learn/serve/rollout call sites hand to
    ``jax.device_put`` and ``sharded_jit`` (tentpole surface of
    docs/sharding.md)."""
    return named_tree(mesh, param_pspecs(tree, mesh, rules))


def state_pspecs(state, params, params_pspecs) -> object:
    """Spec tree for a params-derived state tree (optimizer moments,
    target networks): each state leaf inherits the spec of the param
    whose key path is a suffix of the leaf's path with the same shape
    (longest suffix wins); everything else — step counts, scalars —
    replicates. This is how per-leaf placement flows through
    ``optax`` states and aux target trees without those containers
    knowing about rules."""
    pairs = []
    pflat, _ = jax.tree_util.tree_flatten_with_path(params)
    specs_flat = jax.tree_util.tree_leaves(
        params_pspecs, is_leaf=_is_pspec
    )
    for (path, leaf), spec in zip(pflat, specs_flat):
        pairs.append(
            (_path_names(path), tuple(getattr(leaf, "shape", ())), spec)
        )

    def one(path, x):
        names = _path_names(path)
        shape = tuple(getattr(x, "shape", ()))
        best = None
        for pnames, pshape, spec in pairs:
            if (
                len(pnames) <= len(names)
                and names[len(names) - len(pnames):] == pnames
                and pshape == shape
            ):
                if best is None or len(pnames) > best[0]:
                    best = (len(pnames), spec)
        return best[1] if best is not None else P()

    return jax.tree_util.tree_map_with_path(one, state)


def tree_shard_nbytes(tree, pspec_tree, mesh: Mesh) -> int:
    """Per-device bytes of a partitioned tree: each leaf's bytes
    divided by the product of the mesh-axis sizes its spec names
    (replicated leaves count full size on every shard) — the number
    behind ``ray_tpu_params_bytes{placement="per_shard"}``."""
    leaves = jax.tree_util.tree_leaves(tree)
    specs = jax.tree_util.tree_leaves(pspec_tree, is_leaf=_is_pspec)
    total = 0
    for x, spec in zip(leaves, specs):
        denom = 1
        for entry in spec:
            for ax in (
                entry if isinstance(entry, (tuple, list)) else (entry,)
            ):
                if ax is not None:
                    denom *= int(mesh.shape[ax])
        total += int(getattr(x, "nbytes", 0)) // max(1, denom)
    return int(total)


def shard_batch(
    tree,
    mesh: Mesh,
    replicate_keys: Iterable[str] = (),
    *,
    block: bool = False,
):
    """``jax.device_put`` a host tree onto the mesh with per-leaf
    shardings. ``block=True`` waits for the transfer (honest timing;
    otherwise dispatch is async and overlaps the caller)."""
    dev = jax.device_put(
        tree, sharding_tree(tree, mesh, replicate_keys)
    )
    if block:
        jax.block_until_ready(dev)
    return dev


# -- varying-axes typing inside shard_map bodies ------------------------
#
# jax types every value in a ``shard_map`` body by the mesh axes it
# varies over (``check_vma``): a replicated input (``P()``) is
# *invarying*, a row-sharded one varies over the data axis. Two
# consequences the learn programs depend on:
#
#   - differentiating a per-shard loss w.r.t. an INVARYING param makes
#     jax insert the cross-shard ``psum`` itself (the transpose of the
#     implicit broadcast), so the gradient comes back already SUMMED
#     over shards and a following ``pmean`` is the identity — N× the
#     intended mean gradient on an N-shard mesh. The update bodies
#     therefore differentiate a :func:`varying` view of the params:
#     the gradients stay per-shard and the explicit ``pmean`` is the
#     one real collective, as written;
#   - a ``lax.scan`` carry (and both ``lax.cond`` branches) must keep
#     one type, and ``optimization_barrier`` types ALL its outputs by
#     the union of its inputs' axes — :func:`vma_barrier` pins the
#     same boundary without retyping the replicated carry.


def vma_of(tree) -> frozenset:
    """Union of the mesh axes the leaves of ``tree`` vary over (empty
    outside ``shard_map``)."""
    axes: frozenset = frozenset()
    for x in jax.tree_util.tree_leaves(tree):
        axes |= jax.typeof(x).vma
    return axes


def varying(tree, axes):
    """``tree`` with every leaf typed as varying over ``axes`` (a name
    or an iterable of names); leaves already varying over an axis keep
    it. A type-level cast: no data moves."""
    axes = (axes,) if isinstance(axes, str) else tuple(axes)

    def cast(x):
        missing = tuple(a for a in axes if a not in jax.typeof(x).vma)
        if not missing:
            return x
        return jax.lax.pcast(x, missing, to="varying")

    return jax.tree_util.tree_map(cast, tree)


def vma_barrier(tree):
    """``lax.optimization_barrier`` over ``tree`` that keeps each
    leaf's varying-axes type: leaves are pinned in groups of equal
    type (one barrier per group), so a replicated scan carry that
    shares a fusion boundary with a row-sharded batch stays
    replicated."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    groups: Dict[frozenset, list] = {}
    for i, x in enumerate(leaves):
        groups.setdefault(jax.typeof(x).vma, []).append(i)
    out = list(leaves)
    for idxs in groups.values():
        pinned = jax.lax.optimization_barrier([leaves[i] for i in idxs])
        for i, v in zip(idxs, pinned):
            out[i] = v
    return jax.tree_util.tree_unflatten(treedef, out)
