"""The delta rule with a decay a key channel ALONE, in both of the
program's forms, against a float64 chain of its four lines.

The cell's other comparisons hold the whole policy to a float32
reference, and the bfloat16 operands the configuration states for the
projections move every number there more than a bfloat16 KDA state
would (PERF.md, PR 61): they cannot tell whether the ONE float32
quantity this model adds, the ``(dk, dv)`` matrix a head carries over a
whole episode, is kept in float32. This comparison can. The operands
are the program's own: the first Kimi-Delta-Attention layer's
``operands`` (its bfloat16 projections, float32 convolutions, L2 norms,
bounded gate and ``beta``) from the policy's seeded weights, on the
embedding rows of one fragment of seeded tokens a stream at unit RMS
(what the layer's norm hands it, up to the norm's weight), from the
convolution tails and the MATRICES THE STREAMS CARRY (brought to depths
0-3,840 in set-up; every second stream, ``STREAMS_STRIDE``). On those
``q``, ``k``, ``v``, ``g``, ``beta``:

- the one-token form, ``ops/deltanet.gated_delta_step`` a token (the
  Pallas kernel on the chip), the fragment's tokens in a scan;
- the fragment form, ``ops/deltanet.gated_delta_chunked`` (chunks of the
  model's size in sub-blocks of 16);
- the chain: ``S <- diag(exp(g_t)) S; d = beta_t (v_t - S^T k_t); S <- S
  + k_t d^T; o_t = S^T q_t`` in numpy float64 on the host, from the same
  start matrices.

``kda_rule_step_state_rel_l2`` / ``kda_rule_chunk_state_rel_l2``: the
matrices after the fragment; ``kda_rule_step_out_rel_l2`` /
``kda_rule_chunk_out_rel_l2``: the fragment's outputs. Rounding only:
the operands are the same numbers on both sides.

The controls put the chain, one precision step down, in the system's
place: ``bf16_state`` rounds the chain's matrix to bfloat16 after every
token (the step below what the configuration states for it); ``int8``
and ``fp8`` round ``q``, ``k`` and ``v`` per tensor to 127 levels or to
float8 e4m3, as those controls round every product's operands
elsewhere."""

import numpy as np

STAGE = "after_first_iterations"
LIMITS = (
    "kda_rule_step_state_rel_l2", "kda_rule_chunk_state_rel_l2",
    "kda_rule_step_out_rel_l2", "kda_rule_chunk_out_rel_l2",
)
TOKENS_FOLD = 23  # the seed's key folded with it draws the tokens
# every second stream (eight depths of the sixteen): the chain is 0.09 s
# a token of sixteen streams on the host, and counts as set-up
STREAMS_STRIDE = 2
_FNS = {}  # jitted programs, by name: one compile a process


def _layer(state):
    """``(segment, its carried leaves)`` of the first layer whose mixer
    computes the rule's operands apart."""
    model = state.policy.model
    carried = state.algo._jax_engine()._carry["state"]
    for seg, leaves in model._by_segment(carried):
        if hasattr(seg.mixer, "operands"):
            return seg, leaves
    raise ValueError("kda_rule: the policy has no layer with the rule's operands")


def _programs(state, mixer):
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import deltanet

    model = state.policy.model

    def operands(p, embedding, tokens, tails):
        x = jnp.take(embedding, tokens, axis=0).astype(jnp.float32)
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True))
        ctx = {"dtype": model.dtype, "seg": jnp.zeros(tokens.shape, jnp.int32)}
        return mixer.operands(p, x, tails, ctx)[0]

    def step_form(s0, q, k, v, g, beta):
        def one(s, xs):
            s, o = deltanet.gated_delta_step(s, *xs)
            return s, o

        s1, o = jax.lax.scan(
            one, s0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
        return s1, jnp.moveaxis(o, 0, 1)

    def chunk_form(s0, q, k, v, g, beta):
        o, s1 = deltanet.gated_delta_chunked(s0, q, k, v, g, beta, chunk=model.chunk)
        return s1, o

    for name, fn in (("operands", operands), ("step", step_form), ("chunk", chunk_form)):
        _FNS.setdefault(name, jax.jit(fn))
    return _FNS["operands"], _FNS["step"], _FNS["chunk"]


def _round_int8(x):
    scale = max(float(np.max(np.abs(x))), 1e-30) / 127.0
    return np.clip(np.round(x / scale), -127.0, 127.0) * scale


def _round_fp8(x):
    import ml_dtypes

    scale = max(float(np.max(np.abs(x))), 1e-30) / 448.0
    return (x / scale).astype(ml_dtypes.float8_e4m3fn).astype(np.float64) * scale


def _round_bf16(x):
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float64)


_same = lambda x: x
# (what q, k and v go through, what the matrix goes through after every token)
_CONTROLS = {
    "float64": (_same, _same),
    "int8": (_round_int8, _same),
    "fp8": (_round_fp8, _same),
    "bf16_state": (_same, _round_bf16),
}


def chain(s0, q, k, v, g, beta, precision="float64"):
    """The four lines, token by token, in float64: ``(matrices after
    the fragment (B, H, dk, dv), outputs (B, T, H, dv))``."""
    of_operand, of_matrix = _CONTROLS[precision]
    s = np.array(s0, np.float64)
    q, k, v = (of_operand(np.asarray(a, np.float64)) for a in (q, k, v))
    decay = np.exp(np.asarray(g, np.float64))
    beta = np.asarray(beta, np.float64)
    out = np.empty(v.shape, np.float64)
    for t in range(q.shape[1]):
        s *= decay[:, t, :, :, None]  # a row of S a key channel
        read = np.matmul(k[:, t, :, None, :], s)[:, :, 0]  # S^T k
        delta = beta[:, t, :, None] * (v[:, t] - read)
        s += k[:, t, :, :, None] * delta[:, :, None, :]
        s = of_matrix(s)
        out[:, t] = np.matmul(q[:, t, :, None, :], s)[:, :, 0]
    return s, out


def _rel_l2(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _numbers(got_step, got_chunk, want):
    return {
        "kda_rule_step_state_rel_l2": _rel_l2(got_step[0], want[0]),
        "kda_rule_chunk_state_rel_l2": _rel_l2(got_chunk[0], want[0]),
        "kda_rule_step_out_rel_l2": _rel_l2(got_step[1], want[1]),
        "kda_rule_chunk_out_rel_l2": _rel_l2(got_chunk[1], want[1]),
    }


def _system(state):
    import jax

    seg, (s0, *tails) = _layer(state)
    eng = state.algo._jax_engine()
    key = jax.random.fold_in(
        jax.random.PRNGKey(int(state.seed) % (2**31 - 1)), TOKENS_FOLD)
    s0, tails = s0[::STREAMS_STRIDE], [tail[::STREAMS_STRIDE] for tail in tails]
    tokens = jax.random.randint(key, (s0.shape[0], eng.T), 0, state.num_actions)
    operands, step_form, chunk_form = _programs(state, seg.mixer)
    ops = operands(
        state.policy.params[seg.name],
        state.policy.params["embed"]["embedding"], tokens, tails)
    got_step = jax.device_get(step_form(s0, *ops))
    got_chunk = jax.device_get(chunk_form(s0, *ops))
    start, ops = jax.device_get((s0, ops))
    want = chain(start, *ops)
    g = ops[3]
    note = (
        f"layer {seg.name}: {s0.shape[0]} streams x {eng.T} tokens, log-decays "
        f"{float(g.min()):.3f} to {float(g.max()):.3g}; the carried matrices' "
        f"norm {float(np.linalg.norm(start)):.4g}, after the fragment "
        f"{float(np.linalg.norm(want[0])):.4g}"
    )
    return _numbers(got_step, got_chunk, want), note, (start, ops, want)


def run(state):
    got, note, _ = _system(state)
    for name in LIMITS:
        state.checks.at_most(name, got[name], state.cell.limit(name), note)
        note = ""
    return got


def readings(state):
    """``{"system": {...}, "<precision>": {...}}`` for ``perf.control``:
    a control is the chain one precision step down in the place of BOTH
    of the system's forms."""
    got, _, (start, ops, want) = _system(state)
    out = {"system": got}
    for precision in state.cell.control_precisions:
        low = chain(start, *ops, precision=precision)
        out[precision] = _numbers(low, low, want)
    return out
