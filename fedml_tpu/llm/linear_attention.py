"""Kimi delta attention (KDA): the gated delta rule with a decay for every
key channel (Kimi Linear, arXiv:2510.26692), in chunks.

A head keeps a state ``S`` in ``R^{d_k x d_v}``, zero at a row's start:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,          alpha_t = exp(g_t), g_t <= 0 per key channel

Training never runs that token by token. In a chunk of C positions with
``G_r = sum_{i<=r} g_i`` and the entering state ``S_0``:

    A[r, j] = beta_r sum_c k_rc k_jc exp(G_rc - G_jc)            (j < r)
    (I + A) U = beta * (V - (K * e^G) S_0)
    o_r = (q_r * e^{G_r})^T S_0
          + sum_{j<=r} (sum_c q_rc k_jc exp(G_rc - G_jc)) u_j
    S_C = Diag(e^{G_C}) S_0 + sum_j (k_j * exp(G_C - G_j)) u_j^T

``exp(G_r - G_j)`` is at most 1, but its factors ``e^{G_r} e^{-G_j}`` leave
float32 over a chunk, so each sub-chunk of ``SUB`` rows measures its decays
from its own first row: with ``g >= -5`` (the model's bounded gate) no
exponent passes ``SUB * 5 = 80``. A is nilpotent, so ``(I + A)^-1`` is the
sum of the powers of ``-A`` below C, doubled ``log2 C`` times on the MXU.

:func:`_chunk` is that chunk step as plain ``jax.numpy``; both forms run it
and its ``jax.vjp``: ``dense`` under ``lax.scan`` over the chunks, ``flash``
inside two Pallas kernels (``kda_fwd``: chunks in order, the state in VMEM
scratch, every chunk's entering state written out; ``kda_bwd``: chunks in
reverse order, the state's cotangent in VMEM scratch). One ``custom_vjp``
over the whole sequence holds the pair together: the backward of a chunked
scan, with the entering states as its only residual beyond the inputs.
The kernels read ``[b, s, h * d]`` as the projections leave it (a head is
a 128-lane column block): nothing is transposed around them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core import kernels
from ..core.obs import metrics as obs_metrics

# the two kernels' names in a device trace (forward; backward)
KDA_KERNEL_NAMES = ("kda_fwd", "kda_bwd")
CHUNK = 64
SUB = 16
# exp() of a sub-chunk's decays stays finite up to here; columns past a
# row's own sub-chunk are clamped to it and masked after the product
_MAX_EXPONENT = 80.0
# the steepest log-decay a position for which that clamp touches masked
# columns alone: a configuration with a lower bound under it is refused
MIN_LOG_DECAY = -_MAX_EXPONENT / SUB
# positions the short convolution before q, k and v reads
SHORT_CONV_TAPS = 4

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_HIGHEST = jax.lax.Precision.HIGHEST


def _exact(a, b, dims=(((1,), (0,)), ((), ()))):
    """A float32 product at full precision (the small ``[C, C]`` algebra)."""
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _mm(a, b, dims, dtype):
    """A product with its operands in the compute dtype and a float32
    result: bfloat16 goes to the MXU in one pass, float32 at full
    precision."""
    if dtype == jnp.float32:
        return _exact(a, b, dims)
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               preferred_element_type=jnp.float32)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular ``a`` [C, C]. With
    ``b = -a`` (nilpotent) the inverse is ``S_C = sum_{k<C} b^k``, and
    ``S_2n = S_n + b^n S_n``. ``[b^n | S_n]`` stays one ``[C, 2C]`` array (at
    C = 64 one weight tile, and no lane of it moves between doublings): one
    product ``b^n @ [b^n | S_n]`` a doubling gives the next power and the
    next sum's second half together. ``b^n`` is zero in its first ``n`` rows
    and last ``n`` columns: whole registers of them (8 rows) stay out of
    the product."""
    c = a.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, 2 * c), 1)
    both = jnp.where(col == row + c, 1.0,                      # [b | I]
                     jnp.concatenate([-a, jnp.zeros_like(a)], 1))
    n = 1
    while n < c:
        dead = n // 8 * 8
        step = _exact(both[dead:, :c - dead], both[:c - dead])
        if dead:
            step = jnp.concatenate(
                [jnp.zeros((dead, 2 * c), jnp.float32), step], 0)
        both = step + jnp.where(col < c, 0.0, both)
        n *= 2
    return both[:, c:]


@jax.custom_vjp
def _unit_lower_solve(a, rhs):
    """``u`` with ``(I + a) u = rhs`` for strictly lower triangular ``a``
    [C, C] and ``rhs`` [C, d]. The pull-back is the solve's own (two
    products with what the forward pass holds), not an inverse's."""
    return _unit_lower_solve_fwd(a, rhs)[0]


def _unit_lower_solve_fwd(a, rhs):
    inv = _unit_lower_inverse(a)
    u = _exact(inv, rhs)
    return u, (inv, u)


def _unit_lower_solve_bwd(res, du):
    # u = X^-1 r: dr = X^-T du, dX = -dr u^T, and only a's strict lower
    # triangle is free
    inv, u = res
    c = inv.shape[0]
    drhs = _exact(inv, du, _TN)
    lower = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
             > jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
    return jnp.where(lower, -_exact(drhs, u, _NT), 0.0), drhs


_unit_lower_solve.defvjp(_unit_lower_solve_fwd, _unit_lower_solve_bwd)


def _chunk(q, k, kb, vb, gc, st, dtype):
    """One chunk of one head. ``q``, ``k`` [C, d_k]; ``kb = beta * k``;
    ``vb = beta * v`` [C, d_v]; ``gc`` [C, d_k] the chunk's running sum of
    log-decays (float32, its own row included); ``st`` [d_v, d_k] the
    entering state, transposed so that a decay scales its lanes. ->
    (o [C, d_v] float32, the state the chunk leaves)."""
    f32 = jnp.float32
    q, k, kb, vb, gc = (a.astype(f32) for a in (q, k, kb, vb, gc))
    c = q.shape[0]
    sub = min(SUB, c)
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)

    # within the chunk, row r against column j <= r: each sub-chunk of rows
    # measures both sides from its own first row
    firsts = [gc[a:a + 1] for a in range(0, c, sub)]
    from_first = jnp.exp(gc - jnp.concatenate(
        [jnp.broadcast_to(f, (sub, f.shape[1])) for f in firsts], 0))
    kb_row, q_row = kb * from_first, q * from_first
    a_rows, p_rows = [], []
    for i, first in enumerate(firsts):
        rows = slice(i * sub, (i + 1) * sub)
        k_col = k * jnp.exp(jnp.minimum(first - gc, _MAX_EXPONENT))
        both = _mm(jnp.concatenate([kb_row[rows], q_row[rows]], 0), k_col,
                   _NT, dtype)                               # [2 sub, C]
        a_rows.append(both[:sub])
        p_rows.append(both[sub:])
    a_mat = jnp.where(row > col, jnp.concatenate(a_rows, 0), 0.0)
    p_mat = jnp.where(row >= col, jnp.concatenate(p_rows, 0), 0.0)

    # against the entering state: decays from the chunk's start, all <= 1
    from_start = jnp.exp(gc)
    seen = _mm(jnp.concatenate([kb * from_start, q * from_start], 0), st,
               _NT, dtype)                                   # [2C, d_v]
    u = _unit_lower_solve(a_mat, vb - seen[:c])
    o = seen[c:] + _mm(p_mat, u, (((1,), (0,)), ((), ())), dtype)
    last = gc[c - 1:c]
    st_new = st * jnp.exp(last) + _mm(u, k * jnp.exp(last - gc), _TN, dtype)
    return o, st_new


def _chunk_grads(q, k, kb, vb, gc, st, do, dst, dtype):
    """``_chunk`` rebuilt and ``(do, dst)`` pulled back to its six inputs,
    each in float32."""
    f32 = jnp.float32
    args = tuple(a.astype(f32) for a in (q, k, kb, vb, gc, st))
    _, pull = jax.vjp(functools.partial(_chunk, dtype=dtype), *args)
    return pull((do.astype(f32), dst))


# ----------------------------------------------------- the jax.numpy form ---

def _by_chunk(a, chunk):
    """[b, s, h, d] -> [n, b, h, chunk, d]."""
    b, s, h, d = a.shape
    return a.reshape(b, s // chunk, chunk, h, d).transpose(1, 0, 3, 2, 4)


def _from_chunks(a):
    """[n, b, h, chunk, d] -> [b, s, h, d]."""
    n, b, h, c, d = a.shape
    return a.transpose(1, 0, 3, 2, 4).reshape(b, n * c, h, d)


def _scan_fwd(q, k, kb, vb, gc, chunk):
    dtype = q.dtype
    step = jax.vmap(jax.vmap(functools.partial(_chunk, dtype=dtype)))
    b, _, h, dk = q.shape

    def body(st, xs):
        o, st_new = step(*xs, st)
        return st_new, (o, st)

    st0 = jnp.zeros((b, h, vb.shape[-1], dk), jnp.float32)
    _, (o, states) = jax.lax.scan(
        body, st0, tuple(_by_chunk(a, chunk) for a in (q, k, kb, vb, gc)))
    return _from_chunks(o).astype(dtype), states       # states [n, b, h, ..]


def _scan_bwd(q, k, kb, vb, gc, states, do, chunk):
    dtype = q.dtype
    step = jax.vmap(jax.vmap(functools.partial(_chunk_grads, dtype=dtype)))

    def body(dst, xs):
        *ins, st, do_c = xs
        dq, dk, dkb, dvb, dgc, dst0 = step(*ins, st, do_c, dst)
        return dst0, (dq, dk, dkb, dvb, dgc)

    xs = tuple(_by_chunk(a, chunk) for a in (q, k, kb, vb, gc)) \
        + (states, _by_chunk(do, chunk))
    _, grads = jax.lax.scan(body, jnp.zeros_like(states[0]), xs, reverse=True)
    return tuple(_from_chunks(g).astype(a.dtype)
                 for g, a in zip(grads, (q, k, kb, vb, gc)))


# ------------------------------------------------------ the Pallas kernels ---

def _heads_per_step(h: int) -> int:
    """Heads a grid step works through: independent chains, so that one
    head's products fill the other's waits."""
    return 2 if h % 2 == 0 else 1


def _kda_fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, gc_ref, o_ref, states_ref,
                    st_ref, *, heads: int, dk: int, dv: int):
    """One (row, head group, chunk) program; chunks run in order and the
    state stays in ``st_ref`` [heads, d_v, d_k] between them. Blocks are
    ``[chunk, heads * d]`` columns of the ``[b, s, h * d]`` arrays."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    dtype = q_ref.dtype
    for j in range(heads):
        ck, cv = pl.ds(j * dk, dk), pl.ds(j * dv, dv)
        st = st_ref[j]
        states_ref[j] = st
        o, st_new = _chunk(q_ref[:, ck], k_ref[:, ck], kb_ref[:, ck],
                           vb_ref[:, cv], gc_ref[:, ck], st, dtype)
        o_ref[:, cv] = o.astype(o_ref.dtype)
        st_ref[j] = st_new


def _kda_bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, gc_ref, states_ref, do_ref,
                    dq_ref, dk_ref, dkb_ref, dvb_ref, dgc_ref, dst_ref,
                    *, heads: int, dk: int, dv: int):
    """The same grid with the chunks in reverse order; ``dst_ref`` holds
    the cotangent of the state a chunk leaves."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    dtype = q_ref.dtype
    for j in range(heads):
        ck, cv = pl.ds(j * dk, dk), pl.ds(j * dv, dv)
        dq, dk_, dkb, dvb, dgc, dst0 = _chunk_grads(
            q_ref[:, ck], k_ref[:, ck], kb_ref[:, ck], vb_ref[:, cv],
            gc_ref[:, ck], states_ref[j], do_ref[:, cv], dst_ref[j], dtype)
        dq_ref[:, ck] = dq.astype(dq_ref.dtype)
        dk_ref[:, ck] = dk_.astype(dk_ref.dtype)
        dkb_ref[:, ck] = dkb.astype(dkb_ref.dtype)
        dvb_ref[:, cv] = dvb.astype(dvb_ref.dtype)
        dgc_ref[:, ck] = dgc
        dst_ref[j] = dst0


def _pallas_specs(pl, b, s, h, dk, dv, chunk, reverse):
    hb = _heads_per_step(h)
    n = s // chunk
    at = (lambda c: n - 1 - c) if reverse else (lambda c: c)
    spec_k = pl.BlockSpec((None, chunk, hb * dk),
                          lambda i, j, c: (i, at(c), j))
    spec_v = pl.BlockSpec((None, chunk, hb * dv),
                          lambda i, j, c: (i, at(c), j))
    spec_st = pl.BlockSpec((None, hb, None, dv, dk),
                           lambda i, j, c: (i, j, at(c), 0, 0))
    return hb, (b, h // hb, n), spec_k, spec_v, spec_st


def _pallas_fwd(q, k, kb, vb, gc, chunk):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s, h, dk = q.shape
    dv = vb.shape[-1]
    hb, grid, spec_k, spec_v, spec_st = _pallas_specs(
        pl, b, s, h, dk, dv, chunk, False)
    flat = lambda a: a.reshape(b, s, -1)  # noqa: E731
    o, states = pl.pallas_call(
        functools.partial(_kda_fwd_kernel, heads=hb, dk=dk, dv=dv),
        grid=grid,
        in_specs=[spec_k, spec_k, spec_k, spec_v, spec_k],
        out_specs=[spec_v, spec_st],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, s // chunk, dv, dk),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        name=KDA_KERNEL_NAMES[0],
    )(flat(q), flat(k), flat(kb), flat(vb), flat(gc))
    return o.reshape(b, s, h, dv), states


def _pallas_bwd(q, k, kb, vb, gc, states, do, chunk):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s, h, dk = q.shape
    dv = vb.shape[-1]
    hb, grid, spec_k, spec_v, spec_st = _pallas_specs(
        pl, b, s, h, dk, dv, chunk, True)
    flat = lambda a: a.reshape(b, s, -1)  # noqa: E731
    like = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        (b, s, a.shape[2] * a.shape[3]), a.dtype)
    grads = pl.pallas_call(
        functools.partial(_kda_bwd_kernel, heads=hb, dk=dk, dv=dv),
        grid=grid,
        in_specs=[spec_k, spec_k, spec_k, spec_v, spec_k, spec_st, spec_v],
        out_specs=[spec_k, spec_k, spec_k, spec_v, spec_k],
        out_shape=[like(q), like(k), like(kb), like(vb), like(gc)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        name=KDA_KERNEL_NAMES[1],
    )(flat(q), flat(k), flat(kb), flat(vb), flat(gc), states, flat(do))
    return tuple(g.reshape(a.shape)
                 for g, a in zip(grads, (q, k, kb, vb, gc)))


# --------------------------------------------------------- the whole row ---

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda_chunks(q, k, kb, vb, gc, chunk: int, impl: str):
    return _kda_chunks_fwd(q, k, kb, vb, gc, chunk, impl)[0]


def _kda_chunks_fwd(q, k, kb, vb, gc, chunk, impl):
    if impl == "flash":
        o, states = _pallas_fwd(q, k, kb, vb, gc, chunk)
    else:
        o, states = _scan_fwd(q, k, kb, vb, gc, chunk)
    return o, (q, k, kb, vb, gc, states)


def _kda_chunks_bwd(chunk, impl, res, do):
    bwd = _pallas_bwd if impl == "flash" else _scan_bwd
    return bwd(*res, do, chunk)


_kda_chunks.defvjp(_kda_chunks_fwd, _kda_chunks_bwd)


def chunk_size(s: int) -> int:
    """Positions a chunk: ``CHUNK`` where the row has them, else the row
    rounded up to whole sub-chunks."""
    return CHUNK if s >= CHUNK else -(-s // SUB) * SUB


def kda_attention(q, k, v, g, beta, impl: str = "dense"):
    """``q``, ``k`` [b, s, h, d_k] (normalised and scaled by the caller),
    ``v`` [b, s, h, d_v], ``g`` [b, s, h, d_k] float32 log-decays in
    ``[MIN_LOG_DECAY, 0]``, ``beta`` [b, s, h] -> o [b, s, h, d_v]:
    the recurrence of the module's docstring from a zero state, in chunks.
    ``impl`` ``flash`` runs the chunks in the Pallas kernels (``d_k`` and
    ``d_v`` multiples of 128), anything else as ``jax.numpy`` under a
    scan."""
    b, s, h, dk = q.shape
    chunk = chunk_size(s)
    impl = "flash" if impl == "flash" else "dense"
    if impl == "flash" and (dk % 128 or v.shape[-1] % 128):
        raise ValueError(f"the KDA kernels take head sizes on the 128 grid; "
                         f"got d_k {dk}, d_v {v.shape[-1]}")
    obs_metrics.record_kda_plan(chunk)
    beta = beta[..., None].astype(jnp.float32)
    kb = (k.astype(jnp.float32) * beta).astype(k.dtype)
    vb = (v.astype(jnp.float32) * beta).astype(v.dtype)
    pad = -s % chunk
    if pad:     # zeros after the row: no decay, no write, read by nobody
        q, k, kb, vb, g = (jnp.pad(a, [(0, 0), (0, pad), (0, 0), (0, 0)])
                           for a in (q, k, kb, vb, g))
    n = (s + pad) // chunk
    gc = jnp.cumsum(g.astype(jnp.float32).reshape(b, n, chunk, h, dk),
                    axis=2).reshape(b, s + pad, h, dk)
    o = _kda_chunks(q, k, kb, vb, gc, chunk, impl)
    return o[:, :s] if pad else o


def kda_recurrence(q, k, v, g, beta):
    """The same map as :func:`kda_attention`, token by token in float32:
    the definition the chunked forms are tested against (never the timed
    path)."""
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    b, _, h, dk = q.shape

    def step(st, xs):
        q_t, k_t, v_t, g_t, b_t = xs                  # [b, h, .]
        st = st * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.sum(st * k_t[..., None], -2))
        st = st + k_t[..., None] * u[..., None, :]
        return st, jnp.sum(st * q_t[..., None], -2)

    st0 = jnp.zeros((b, h, dk, v.shape[-1]), f32)
    _, o = jax.lax.scan(step, st0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def short_conv(x, w):
    """Causal depthwise convolution over the last ``K`` positions, no bias:
    ``y_t = sum_i w[i] * x_{t - (K - 1) + i}``. x [b, s, c], w [K, c]."""
    kk = w.shape[0]
    s = x.shape[1]
    xp = jnp.pad(x, [(0, 0), (kk - 1, 0), (0, 0)])
    return sum(xp[:, i:i + s] * w[i].astype(x.dtype) for i in range(kk))
