"""Superstep builder: K learner updates fused into ONE compiled program.

The Podracer/Anakin lesson applied to this learner plane: the host
boundary (per-dispatch host cost + the stats readback's D2H sync) is
crossed once per *superstep* of K updates, not
once per update, so the fixed per-call overhead amortizes 1/K. This
module generalizes what used to be a SAC special case
(``sac.py learn_on_stacked_batch``) into the uniform learner contract:
an outer ``lax.scan`` over any policy's single-update device body.

Mechanics (all inside one ``sharded_jit`` program):

  - the scan carry threads (params, opt_state, aux) — target nets,
    polyak blends, step counters ride the carry; no weights bounce
    through the host between updates. ``opt_state`` is donated.
  - the scan consumes either a **stacked** ``(K, B, ...)`` batch tree
    (PPO's prefetched device batches, host-replay gathers — one H2D
    for the whole superstep) or the **device replay rings in place**:
    host-pre-drawn index arrays ``(K, B)`` ship once per superstep and
    the program gathers each update's rows from the
    ``DeviceReplayBuffer`` store with explicit row-sharded
    out-shardings matching the scan body's batch sharding, so no
    resharding collective fires at the scan-body boundary.
  - the program is compiled once at a static ``K`` with an ``active``
    mask: any ``k_actual <= K`` runs through the SAME executable
    (masked slots pass params through unchanged), so varying chain
    lengths never retrace (``compile_stats()``-asserted).
  - stats stack to ``(K, ...)`` device arrays and drain in ONE
    device→host readback at superstep end; with ``priority_fn`` the
    per-update TD errors for prioritized replay stack to ``(K, B)``
    and ride the same drain.
  - ``nan_guard=True`` moves the non-finite batch guard INSIDE the
    scan body (device-resident batches never pass the host choke
    points in train_ops): a non-finite batch's update is a masked
    no-op and the per-update skip flag lands in the stats tree.

Index draws and rng splits stay HOST-side in the exact per-update call
order (the caller's responsibility — see
``JaxPolicy.learn_superstep``), so a fixed seed produces bit-identical
params/opt-state to K individual learn calls.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ray_tpu.sharding.compile import ShardedFunction, sharded_jit
from ray_tpu.sharding.mesh import all_cpu, data_axis, num_shards
from ray_tpu.sharding.specs import (
    batch_sharded,
    manual_pspecs,
    named_tree,
    replicated,
    vma_barrier,
)

# stats-tree key for the in-scan nan_guard skip flag (1.0 = the slot's
# update was suppressed because its batch contained non-finite floats)
SKIP_KEY = "superstep_skipped"


def resolve_superstep(config: Dict, mesh=None) -> int:
    """Resolve ``AlgorithmConfig.superstep`` (``"auto" | int``) to the
    K this run fuses per dispatch (1 = off).

    ``"auto"`` engages (K=8) exactly where the amortization pays: a
    learner behind a real accelerator boundary, where the
    per-dispatch RTT is the measured bottleneck (benchmarks/MFU.md).
    On the CPU client dispatch is cheap and the K-step scan is pure
    compile time, so auto resolves off — mirroring
    ``resolve_device_resident``. An explicit int forces that K
    anywhere (tests, benchmarks)."""
    mode = config.get("superstep", "auto")
    if mode in (None, False, 0, 1):
        return 1
    if mode == "auto":
        return 1 if all_cpu(mesh) else 8
    return max(1, int(mode))


def batch_finite(batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Scalar 1.0/0.0: every float column of ``batch`` is NaN/Inf-free
    (the device-side counterpart of ``resilience.recovery
    .batch_is_finite`` — same column selection: floats only)."""
    ok = jnp.float32(1.0)
    for v in jax.tree_util.tree_leaves(batch):
        if jnp.issubdtype(v.dtype, jnp.floating):
            ok = ok * jnp.isfinite(v).all().astype(jnp.float32)
    return ok


def build_superstep_fn(
    update_fn: Callable,
    *,
    mesh,
    k: int,
    label: str,
    stacked_cols: Optional[Sequence[str]] = None,
    replicated_cols: Sequence[str] = (),
    rings=None,
    extra_cols: Sequence[str] = (),
    rollout_fn: Optional[Callable] = None,
    priority_fn: Optional[Callable] = None,
    nan_guard: bool = False,
    carry_pspecs=None,
    donate_rollout_state: bool = False,
) -> ShardedFunction:
    """Compile the K-update superstep program around ``update_fn``.

    ``update_fn(params, opt_state, aux, batch, rng, coeffs) ->
    (params, opt_state, aux, stats)`` is the policy's single-update
    device body (it runs inside ``shard_map``: ``lax.pmean`` etc. are
    available) — the SAME body the per-update learn program wraps, so
    the fused chain is bit-identical to K individual calls.

    Feed modes (mutually exclusive):
      - ``stacked_cols``: the program takes a ``(K, B, ...)`` column
        tree; columns named in ``replicated_cols`` (e.g. the
        deduplicated frame pool) replicate instead of row-sharding.
      - ``rings`` (a ``SuperstepRingFeed``; only its two functions
        and its shardings are kept, not its arrays): the program takes
        the device replay rings plus a host ``(K, B)`` index array and
        gathers the batches in place, ``rings.gather_fn(store, idx) ->
        (K, B, ...) tree``; ``extra_cols`` names host-shipped stacked
        columns merged after the gather (PER importance weights). The
        scan carries the rows as the gather left them (a packed pixel
        column as words); ``rings.unpack_fn(batch) -> batch`` makes
        ONE update's logical columns of them inside the body, once,
        for both of its readers (the update and ``priority_fn``).
      - ``rollout_fn(params, carry, rollout_rngs, coeffs) -> (carry,
        batch, metrics)``: each slot PRODUCES its own batch by rolling
        out a JAX-native vectorized env on the mesh
        (``execution/jax_rollout.py``) — rollout(T) + postprocess +
        update fuse into the scan body, so the whole
        rollout+learn superstep is ONE dispatch with zero batch H2D.
        ``carry`` (env state + carried obs, row-sharded) threads
        through the scan alongside the learner state: slot k acts with
        the params slot k-1 produced — the on-policy contract.
        ``metrics`` (per-slot episode-completion arrays, any pytree of
        ``(..., N)`` leaves sharded on the last axis) stack to
        ``(K, ..., N)`` outputs and ride the single stats drain.
        ``donate_rollout_state`` also donates ``params`` and ``carry``
        (a policy whose per-stream state is a cache per env: a second
        copy of either does not fit beside the first).

    ``priority_fn(params, aux, batch, rng) -> (B,)`` runs after each
    update on the post-update state (per-update PER refresh order) and
    its outputs stack to a ``(K, B)`` program output (stacked/gather
    feeds only).

    Compiled signature::

        fn(params, opt_state, aux, feed, active, rngs[, pri_rngs |
           rollout_rngs], coeffs)
          -> (params, opt_state, aux[, carry], stats[, priorities |
              metrics])

    where ``feed`` is the stacked tree, ``(store, idx, extra)``, or
    the rollout carry; ``active`` is the ``(K,)`` float mask and
    ``rngs`` the host-split ``(K, 2)`` key stack (rollout mode adds
    the ``(K, T, 2)`` rollout key stack). ``opt_state`` is donated.
    """
    if (
        int(stacked_cols is not None)
        + int(rings is not None)
        + int(rollout_fn is not None)
    ) != 1:
        raise ValueError(
            "exactly one of stacked_cols / rings / rollout_fn "
            "must be given"
        )
    if rollout_fn is not None and priority_fn is not None:
        raise ValueError(
            "priority_fn is a replay-feed feature; the rollout feed "
            "is on-policy"
        )
    gather_fn = unpack_fn = None
    if rings is not None:
        # the feed's program half: the closures below keep these, not
        # the feed and its arrays
        gather_fn, unpack_fn = rings.gather_fn, rings.unpack_fn
        store_shardings = dict(rings.shardings)
    axis = data_axis(mesh)
    replicated_cols = set(replicated_cols)
    with_pri = priority_fn is not None
    # (params, opt_state, aux) PartitionSpec trees: P() everywhere on
    # the replicated path; per-leaf trees when the policy's params are
    # partitioned over the model axis — the scan carry, donation, and
    # the one compiled executable all preserve them
    if carry_pspecs is None:
        p_ps = o_ps = a_ps = P()
    else:
        p_ps, o_ps, a_ps = carry_pspecs

    if rollout_fn is not None:
        return _build_rollout_superstep(
            update_fn,
            rollout_fn,
            mesh=mesh,
            axis=axis,
            label=label,
            nan_guard=nan_guard,
            carry_pspecs=(p_ps, o_ps, a_ps),
            donate=(0, 1, 3) if donate_rollout_state else (1,),
        )

    def multi_fn(params, opt_state, aux, stacked, active, *rest):
        if with_pri:
            rngs, pri_rngs, coeffs = rest
            xs = (stacked, active, rngs, pri_rngs)
        else:
            rngs, coeffs = rest
            xs = (stacked, active, rngs)

        def body(carry, x):
            params, opt_state, aux = carry
            if with_pri:
                batch, act, rng, pri_rng = x
            else:
                batch, act, rng = x
            # pin the fusion boundary: the standalone per-update
            # program sees its inputs as opaque parameters, while the
            # scan body would see carries and xs slices XLA may fuse
            # into the update math differently (last-ulp drift on some
            # backends). The barrier makes the body compile like the
            # standalone program, keeping the chain bit-identical to K
            # individual calls.
            params, opt_state, aux, batch, rng = vma_barrier(
                (params, opt_state, aux, batch, rng)
            )
            if unpack_fn is not None:
                # after the barrier: the conversion's consumers are the
                # update's and the priority pass's own first layers
                batch = unpack_fn(batch)
            new_p, new_o, new_a, stats = update_fn(
                params, opt_state, aux, batch, rng, coeffs
            )
            ok = act
            if nan_guard:
                # device-resident batches never pass the host nan
                # guard choke points; check inside the scan body and
                # agree across shards (each sees only its row slice)
                fin = jax.lax.pmin(batch_finite(batch), axis)
                ok = ok * fin
                stats = dict(stats, **{SKIP_KEY: 1.0 - fin})
            elif SKIP_KEY not in stats:
                stats = dict(stats, **{SKIP_KEY: jnp.float32(0.0)})

            def keep(new, old):
                return jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok > 0.5, n, o), new, old
                )

            with jax.named_scope("learn/commit"):
                params = keep(new_p, params)
                opt_state = keep(new_o, opt_state)
                aux = keep(new_a, aux)
            if with_pri:
                # post-update state, matching the per-update path's
                # learn -> compute_td_error -> update_priorities order
                with jax.named_scope("learn/td_error"):
                    pri = priority_fn(params, aux, batch, pri_rng)
                return (params, opt_state, aux), (stats, pri)
            return (params, opt_state, aux), stats

        # default unroll (a real loop): every iteration — and every
        # (k_actual, slot) combination — runs the ONE compiled body,
        # so splitting a chain across dispatches is bit-identical to
        # fusing it (scan(k)=scan(1)^k through this program), which is
        # what the zero-recompile/all-K-one-program contract promises.
        (params, opt_state, aux), ys = jax.lax.scan(
            body, (params, opt_state, aux), xs
        )
        if with_pri:
            stats, pri = ys
            return params, opt_state, aux, stats, pri
        return params, opt_state, aux, ys

    # per-column shard_map specs for the stacked tree the scan consumes
    if stacked_cols is not None:
        cols = tuple(stacked_cols)
    else:
        cols = tuple(sorted(store_shardings)) + tuple(extra_cols)
    stacked_spec = {
        c: (P() if c in replicated_cols else P(None, axis))
        for c in cols
    }
    bp, bo, ba = manual_pspecs(mesh, (p_ps, o_ps, a_ps))
    sm_in = (bp, bo, ba, stacked_spec, P(), P()) + (
        (P(), P()) if with_pri else (P(),)
    )
    sm_out = (bp, bo, ba, P()) + (
        (P(None, axis),) if with_pri else ()
    )
    sharded = jax.shard_map(
        multi_fn, mesh=mesh, in_specs=sm_in, out_specs=sm_out
    )

    dat2 = batch_sharded(mesh, ndim_prefix=2)
    rep = replicated(mesh)

    if gather_fn is not None:

        def program(params, opt_state, aux, feed, active, *rest):
            store, idx, extra = feed
            # layout-matched gather: emit rows already in the scan
            # body's row-sharded batch layout, so no resharding
            # collective fires at the scan-body boundary
            stacked = {
                c: jax.lax.with_sharding_constraint(v, dat2)
                for c, v in gather_fn(store, idx).items()
            }
            stacked.update(extra)
            return sharded(
                params, opt_state, aux, stacked, active, *rest
            )

    else:

        def program(params, opt_state, aux, stacked, active, *rest):
            return sharded(
                params, opt_state, aux, stacked, active, *rest
            )

    if gather_fn is not None:
        feed_spec = (
            store_shardings,
            rep,
            {c: dat2 for c in extra_cols},
        )
    else:
        feed_spec = {
            c: (rep if c in replicated_cols else dat2) for c in cols
        }
    p_sh = named_tree(mesh, p_ps)
    o_sh = named_tree(mesh, o_ps)
    a_sh = named_tree(mesh, a_ps)
    in_specs = (p_sh, o_sh, a_sh, feed_spec, rep, rep) + (
        (rep, rep) if with_pri else (rep,)
    )
    out_specs = (p_sh, o_sh, a_sh, rep) + (
        (dat2,) if with_pri else ()
    )
    return sharded_jit(
        program,
        in_specs=in_specs,
        out_specs=out_specs,
        donate_argnums=(1,),
        label=label,
    )


def _build_rollout_superstep(
    update_fn: Callable,
    rollout_fn: Callable,
    *,
    mesh,
    axis: str,
    label: str,
    nan_guard: bool,
    carry_pspecs=(P(), P(), P()),
    donate=(1,),
) -> ShardedFunction:
    """The rollout-producing feed of :func:`build_superstep_fn`: slot
    k of the scan rolls out the env carry with the CURRENT params,
    builds its train batch in place, and updates — rollout+learn as
    one compiled chain (docs/data_plane.md "fused rollout").

    Masked slots (``active`` 0) revert params/opt/aux AND the env
    carry, so running ``k < k_max`` through the one executable neither
    trains nor advances the envs for the padded slots."""

    def multi_fn(params, opt_state, aux, carry0, active, rngs, ro_rngs, coeffs):
        def body(scan_carry, x):
            params, opt_state, aux, env_carry = scan_carry
            act, rng, ro_rng = x
            # same fusion-boundary pin as the batch feeds: the body
            # compiles like the standalone rollout + update programs,
            # keeping the fused chain bit-identical to dispatching the
            # pieces separately
            params, opt_state, aux, env_carry, rng, ro_rng = vma_barrier(
                (params, opt_state, aux, env_carry, rng, ro_rng)
            )
            new_carry, batch, metrics = rollout_fn(
                params, env_carry, ro_rng, coeffs
            )
            new_p, new_o, new_a, stats = update_fn(
                params, opt_state, aux, batch, rng, coeffs
            )
            ok = act
            if nan_guard:
                fin = jax.lax.pmin(batch_finite(batch), axis)
                ok = ok * fin
                stats = dict(stats, **{SKIP_KEY: 1.0 - fin})
            elif SKIP_KEY not in stats:
                stats = dict(stats, **{SKIP_KEY: jnp.float32(0.0)})

            def keep(new, old):
                return jax.tree_util.tree_map(
                    lambda n, o: jnp.where(ok > 0.5, n, o), new, old
                )

            with jax.named_scope("learn/commit"):
                params = keep(new_p, params)
                opt_state = keep(new_o, opt_state)
                aux = keep(new_a, aux)
            # a nan-guarded slot keeps its ROLLOUT (those env steps
            # happened; the host counts them) but reverts the update;
            # only an INACTIVE slot reverts the env advance
            env_carry = jax.tree_util.tree_map(
                lambda n, o: jnp.where(act > 0.5, n, o),
                new_carry,
                env_carry,
            )
            return (params, opt_state, aux, env_carry), (stats, metrics)

        (params, opt_state, aux, carry0), (stats, metrics) = (
            jax.lax.scan(
                body,
                (params, opt_state, aux, carry0),
                (active, rngs, ro_rngs),
            )
        )
        return params, opt_state, aux, carry0, stats, metrics

    # carry leaves are per-env rows (leading dim N); metrics leaves
    # end in the env dim (engine contract) so they shard on axis -1
    p_ps, o_ps, a_ps = carry_pspecs
    bp, bo, ba = manual_pspecs(mesh, (p_ps, o_ps, a_ps))
    sharded = jax.shard_map(
        multi_fn,
        mesh=mesh,
        in_specs=(bp, bo, ba, P(axis), P(), P(), P(), P()),
        out_specs=(
            bp,
            bo,
            ba,
            P(axis),
            P(),
            P(*([None] * 2 + [axis])),
        ),
    )
    rep = replicated(mesh)
    dat = batch_sharded(mesh)
    met = batch_sharded(mesh, ndim_prefix=3)
    p_sh = named_tree(mesh, p_ps)
    o_sh = named_tree(mesh, o_ps)
    a_sh = named_tree(mesh, a_ps)
    return sharded_jit(
        sharded,
        in_specs=(p_sh, o_sh, a_sh, dat, rep, rep, rep, rep),
        out_specs=(p_sh, o_sh, a_sh, dat, rep, met),
        donate_argnums=donate,
        label=label,
    )


def build_stack_fn(mesh, k: int, label: str) -> ShardedFunction:
    """Compile the device-side stacker turning ``k`` already-resident
    ``(B, ...)`` batch trees into one ``(k, B, ...)`` superstep feed
    (PPO's prefetched batches, the IMPALA learner queue) — a pure
    device reshuffle, no host round trip."""
    def stack(*trees):
        return jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *trees
        )

    return sharded_jit(
        stack,
        out_specs=batch_sharded(mesh, ndim_prefix=2),
        label=label,
    )
