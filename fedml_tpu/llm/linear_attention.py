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
exponent passes ``SUB * 5 = 80``. Where the gate has no lower bound
(``unbounded``: Kimi Linear's ``-exp(A_log) softplus(.)``) the blocks of a
sub-chunk against its own columns are made element by element over the key
channels instead, every exponent a difference <= 0 taken before its
``exp``; the blocks against earlier sub-chunks are exact at any decay
either way. A is nilpotent, so ``(I + A)^-1`` is the sum of the powers of
``-A`` below C, doubled ``log2 C`` times on the MXU.

:func:`_chunk` is that chunk step as plain ``jax.numpy``; both forms run it
and its ``jax.vjp``: ``dense`` under ``lax.scan`` over the chunks, ``flash``
inside two Pallas kernels (``kda_fwd``: chunks in order, the state in VMEM
scratch, every chunk's entering state written out; ``kda_bwd``: chunks in
reverse order, the state's cotangent in VMEM scratch). One ``custom_vjp``
over the whole sequence holds the pair together: the backward of a chunked
scan, whose residuals beyond the inputs are the entering states and each
chunk's ``(I + A)^-1`` and ``P`` (the scores of q against the chunk's keys),
which depend on no state: :func:`_chunk_grads` pulls back through the
products that made them without making them, or the inverse, again.
The kernels read ``[b, s, h * d]`` as the projections leave it (a head is
a 128-lane column block): nothing is transposed around them.

:func:`kda_layer` is the whole layer between its frozen products: what the
module does element by element before the kernels (the short convolutions,
SiLU, the L2 norms, the decay gate and its running sum within a chunk,
beta) and after them (the head norm and gate) runs as one pass a direction
each, ``_pre_rows`` / ``_post_rows`` and their hand-written pull-backs,
under ``custom_vjp``s whose residuals are the products' outputs and the
kernels' output: see "the passes around the kernels" below.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core import kernels

# the two kernels' names in a device trace (forward; backward)
KDA_KERNEL_NAMES = ("kda_fwd", "kda_bwd")
# the element-wise passes around them (before the kernels: forward,
# backward; after them: forward, backward); none contains a kernel's name
KDA_PASS_NAMES = ("kda_pre_fwd", "kda_pre_bwd", "kda_post_fwd",
                  "kda_post_bwd")
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
# float32 rows (one register) a chunk of the fused pass takes from the chunk
# before it (the convolution's earlier rows) or hands the one before it (the
# convolution's transpose), and the rows of the block they are read from
# (one bfloat16 tile)
_HALO = 8
_HALO_BLOCK = 16
# elements of one operand's block, and chunks a block, at most, in the
# pass before the kernels (128 rows of the benchmark's 4,096 lanes: 33 MB of
# VMEM for its pull-back's sixteen blocks, twice buffered; 256 rows do not
# fit); the pass after them has four blocks and takes ``_TILE_WIDER`` times
# the rows (a step's loop over the heads costs what it costs however few
# rows a head has: 64-row blocks took 1.6 times as long as 128-row ones)
_TILE_ELEMENTS = 128 * 4096
_TILE_CHUNKS = 4
_TILE_WIDER = 4

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
def _unit_lower_solve(a, inv, rhs):
    """``u`` with ``(I + a) u = rhs`` for strictly lower triangular ``a``
    [C, C] and ``rhs`` [C, d], given ``inv = (I + a)^-1``
    (:func:`_unit_lower_inverse`): ``a`` is read by the pull-back alone,
    which is the solve's own (two products with ``inv`` and ``u``), not an
    inverse's; ``inv`` takes no cotangent."""
    return _exact(inv, rhs)


def _unit_lower_solve_fwd(a, inv, rhs):
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
    return jnp.where(lower, -_exact(drhs, u, _NT), 0.0), None, drhs


_unit_lower_solve.defvjp(_unit_lower_solve_fwd, _unit_lower_solve_bwd)


def _within(both, x, k, g, k_all, first: int, dtype):
    """``both`` [2 sub, C]: one sub-chunk's rows of ``x = [beta k | q]``
    against every column of the chunk, with the columns of the sub-chunk
    itself (``first`` on) made exact at any decay: ``sum_c x_rc k_jc
    exp(G_rc - G_jc)`` for ``j <= r``, each exponent a difference <= 0 (1
    above the diagonal, which the caller masks). ``k``, ``g`` [sub, d] the
    sub-chunk's own rows, ``k_all`` [C, d] the chunk's: ONE product of the
    ``sub`` scaled copies of ``x`` against every column, then column ``first
    + j`` from the j-th copy (a lane select, nothing sliced by lanes)."""
    sub = k.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, both.shape, 1)
    copies = []
    for j in range(sub):
        e = jnp.exp(jnp.where(r >= j, g - g[j:j + 1], 0.0))
        copies.append(x * jnp.concatenate([e, e], 0))
    cols = _mm(jnp.concatenate(copies, 0), k_all, _NT, dtype)
    for j in range(sub):
        both = jnp.where(lane == first + j,
                         cols[j * 2 * sub:(j + 1) * 2 * sub], both)
    return both


@jax.custom_jvp
def _kept(made, kept):
    """``kept``, the value ``made`` had when the forward pass made it, with
    ``made``'s derivative: the pull-back runs through what made it, and
    the primal leaves ``made`` unread (dead code, which the compiler
    drops)."""
    return kept


@_kept.defjvp
def _kept_jvp(primals, tangents):
    return primals[1], tangents[0]


def _chunk_mats(q, k, kb, gc, dtype, unbounded: bool):
    """The chunk's matrices that do not depend on the state, from float32
    ``q``, ``k``, ``kb``, ``gc`` [C, d_k]: ``A`` (strictly lower) and
    ``P`` (on and below the diagonal), float32 [C, C] each."""
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
        if unbounded:
            both = _within(both, jnp.concatenate([kb[rows], q[rows]], 0),
                           k[rows], gc[rows], k, i * sub, dtype)
        a_rows.append(both[:sub])
        p_rows.append(both[sub:])
    a_mat = jnp.where(row > col, jnp.concatenate(a_rows, 0), 0.0)
    p_mat = jnp.where(row >= col, jnp.concatenate(p_rows, 0), 0.0)
    return a_mat, p_mat


def _chunk_state(q, k, kb, vb, gc, st, a_mat, inv, p_mat, dtype):
    """The rest of the step, against the entering state ``st``, given the
    chunk's matrices and ``inv = (I + A)^-1`` (None: made here, after the
    state's product, where the schedule wants it) -> (o, the state the
    chunk leaves, ``inv``)."""
    c = q.shape[0]
    # against the entering state: decays from the chunk's start, all <= 1
    from_start = jnp.exp(gc)
    seen = _mm(jnp.concatenate([kb * from_start, q * from_start], 0), st,
               _NT, dtype)                                   # [2C, d_v]
    if inv is None:
        inv = _unit_lower_inverse(a_mat)
    u = _unit_lower_solve(a_mat, inv, vb - seen[:c])
    o = seen[c:] + _mm(p_mat, u, (((1,), (0,)), ((), ())), dtype)
    last = gc[c - 1:c]
    st_new = st * jnp.exp(last) + _mm(u, k * jnp.exp(last - gc), _TN, dtype)
    return o, st_new, inv


def _chunk(q, k, kb, vb, gc, st, dtype, unbounded: bool = False):
    """One chunk of one head. ``q``, ``k`` [C, d_k]; ``kb = beta * k``;
    ``vb = beta * v`` [C, d_v]; ``gc`` [C, d_k] the chunk's running sum of
    log-decays (float32, its own row included); ``st`` [d_v, d_k] the
    entering state, transposed so that a decay scales its lanes. ->
    (o [C, d_v] float32, the state the chunk leaves, and what the backward
    pass reads of the chunk: ``(I + A)^-1`` float32 and ``P`` in ``dtype``,
    [C, C] each, as the products used them). ``unbounded``: the
    log-decays may lie under ``MIN_LOG_DECAY``, and each sub-chunk's block
    against itself is made by :func:`_within`."""
    f32 = jnp.float32
    q, k, kb, vb, gc = (a.astype(f32) for a in (q, k, kb, vb, gc))
    a_mat, p_mat = _chunk_mats(q, k, kb, gc, dtype, unbounded)
    o, st_new, inv = _chunk_state(q, k, kb, vb, gc, st, a_mat, None, p_mat,
                                  dtype)
    return o, st_new, inv, p_mat.astype(dtype)


def _chunk_grads(q, k, kb, vb, gc, st, inv, p, do, dst, dtype,
                 unbounded: bool = False):
    """``(do, dst)`` pulled back through ``_chunk`` to its six inputs, each
    in float32, given what the forward pass kept of the chunk (``inv``,
    ``p``): ``A``, ``P`` and the inverse are not made again, only the
    pull-backs of the products that made ``A`` and ``P`` run (on their
    operands, rebuilt element by element)."""
    f32 = jnp.float32
    args = tuple(a.astype(f32) for a in (q, k, kb, vb, gc, st))
    p = p.astype(f32)

    def step(q, k, kb, vb, gc, st):
        a_mat, p_mat = _chunk_mats(q, k, kb, gc, dtype, unbounded)
        return _chunk_state(q, k, kb, vb, gc, st, a_mat, inv,
                            _kept(p_mat, p), dtype)[:2]

    _, pull = jax.vjp(step, *args)
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


def _scan_fwd(q, k, kb, vb, gc, chunk, unbounded):
    dtype = q.dtype
    step = jax.vmap(jax.vmap(functools.partial(_chunk, dtype=dtype,
                                               unbounded=unbounded)))
    b, _, h, dk = q.shape

    def body(st, xs):
        o, st_new, inv, p = step(*xs, st)
        return st_new, (o, st, inv, p)

    st0 = jnp.zeros((b, h, vb.shape[-1], dk), jnp.float32)
    _, (o, states, inv, p) = jax.lax.scan(
        body, st0, tuple(_by_chunk(a, chunk) for a in (q, k, kb, vb, gc)))
    # states [n, b, h, d_v, d_k]; inv, p [n, b, h, C, C]
    return _from_chunks(o).astype(dtype), states, inv, p


def _scan_bwd(q, k, kb, vb, gc, states, inv, p, do, chunk, unbounded):
    dtype = q.dtype
    step = jax.vmap(jax.vmap(functools.partial(_chunk_grads, dtype=dtype,
                                               unbounded=unbounded)))

    def body(dst, xs):
        *ins, st, inv, p, do_c = xs
        dq, dk, dkb, dvb, dgc, dst0 = step(*ins, st, inv, p, do_c, dst)
        return dst0, (dq, dk, dkb, dvb, dgc)

    xs = tuple(_by_chunk(a, chunk) for a in (q, k, kb, vb, gc)) \
        + (states, inv, p, _by_chunk(do, chunk))
    _, grads = jax.lax.scan(body, jnp.zeros_like(states[0]), xs, reverse=True)
    return tuple(_from_chunks(g).astype(a.dtype)
                 for g, a in zip(grads, (q, k, kb, vb, gc)))


# ------------------------------------------------------ the Pallas kernels ---

def _heads_per_step(h: int) -> int:
    """Heads a grid step works through: independent chains, so that one
    head's products fill the other's waits."""
    return 2 if h % 2 == 0 else 1


def _kda_fwd_kernel(q_ref, k_ref, kb_ref, vb_ref, gc_ref, o_ref, states_ref,
                    inv_ref, p_ref, st_ref, *, heads: int, dk: int, dv: int,
                    unbounded: bool):
    """One (row, head group, chunk) program; chunks run in order and the
    state stays in ``st_ref`` [heads, d_v, d_k] between them. Blocks are
    ``[chunk, heads * d]`` columns of the ``[b, s, h * d]`` arrays; the
    chunk's kept ``[C, C]`` matrices go to ``[C, heads * C]`` blocks, the
    group's heads side by side."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    dtype = q_ref.dtype
    c = q_ref.shape[0]
    for j in range(heads):
        ck, cv, cc = pl.ds(j * dk, dk), pl.ds(j * dv, dv), pl.ds(j * c, c)
        st = st_ref[j]
        states_ref[j] = st
        o, st_new, inv, p = _chunk(q_ref[:, ck], k_ref[:, ck], kb_ref[:, ck],
                                   vb_ref[:, cv], gc_ref[:, ck], st, dtype,
                                   unbounded)
        o_ref[:, cv] = o.astype(o_ref.dtype)
        inv_ref[:, cc] = inv
        p_ref[:, cc] = p
        st_ref[j] = st_new


def _kda_bwd_kernel(q_ref, k_ref, kb_ref, vb_ref, gc_ref, states_ref,
                    inv_ref, p_ref, do_ref, dq_ref, dk_ref, dkb_ref, dvb_ref,
                    dgc_ref, dst_ref, *, heads: int, dk: int, dv: int,
                    unbounded: bool):
    """The same grid with the chunks in reverse order; ``dst_ref`` holds
    the cotangent of the state a chunk leaves."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    dtype = q_ref.dtype
    c = q_ref.shape[0]
    for j in range(heads):
        ck, cv, cc = pl.ds(j * dk, dk), pl.ds(j * dv, dv), pl.ds(j * c, c)
        dq, dk_, dkb, dvb, dgc, dst0 = _chunk_grads(
            q_ref[:, ck], k_ref[:, ck], kb_ref[:, ck], vb_ref[:, cv],
            gc_ref[:, ck], states_ref[j], inv_ref[:, cc], p_ref[:, cc],
            do_ref[:, cv], dst_ref[j], dtype, unbounded)
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
    spec_c = pl.BlockSpec((None, None, None, chunk, hb * chunk),
                          lambda i, j, c: (i, j, at(c), 0, 0))
    return hb, (b, h // hb, n), spec_k, spec_v, spec_st, spec_c


# the Pallas forms are ``jit``s of their own: a model's layers share one
# trace and one lowering of each kernel (``interpret`` is an argument so
# that a process which both interprets and compiles keeps two entries)

@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _pallas_fwd(q, k, kb, vb, gc, chunk, interpret, unbounded):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s, h, dk = q.shape
    dv = vb.shape[-1]
    hb, grid, spec_k, spec_v, spec_st, spec_c = _pallas_specs(
        pl, b, s, h, dk, dv, chunk, False)
    flat = lambda a: a.reshape(b, s, -1)  # noqa: E731
    kept = lambda dtype: jax.ShapeDtypeStruct(  # noqa: E731
        (b, h // hb, s // chunk, chunk, hb * chunk), dtype)
    o, states, inv, p = pl.pallas_call(
        functools.partial(_kda_fwd_kernel, heads=hb, dk=dk, dv=dv,
                          unbounded=unbounded),
        grid=grid,
        in_specs=[spec_k, spec_k, spec_k, spec_v, spec_k],
        out_specs=[spec_v, spec_st, spec_c, spec_c],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dv), q.dtype),
                   jax.ShapeDtypeStruct((b, h, s // chunk, dv, dk),
                                        jnp.float32),
                   kept(jnp.float32), kept(q.dtype)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        interpret=interpret,
        compiler_params=kernels.tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        name=KDA_KERNEL_NAMES[0],
    )(flat(q), flat(k), flat(kb), flat(vb), flat(gc))
    return o.reshape(b, s, h, dv), states, inv, p


@functools.partial(jax.jit, static_argnums=(9, 10, 11))
def _pallas_bwd(q, k, kb, vb, gc, states, inv, p, do, chunk, interpret,
                unbounded):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s, h, dk = q.shape
    dv = vb.shape[-1]
    hb, grid, spec_k, spec_v, spec_st, spec_c = _pallas_specs(
        pl, b, s, h, dk, dv, chunk, True)
    flat = lambda a: a.reshape(b, s, -1)  # noqa: E731
    like = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        (b, s, a.shape[2] * a.shape[3]), a.dtype)
    grads = pl.pallas_call(
        functools.partial(_kda_bwd_kernel, heads=hb, dk=dk, dv=dv,
                          unbounded=unbounded),
        grid=grid,
        in_specs=[spec_k, spec_k, spec_k, spec_v, spec_k, spec_st, spec_c,
                  spec_c, spec_v],
        out_specs=[spec_k, spec_k, spec_k, spec_v, spec_k],
        out_shape=[like(q), like(k), like(kb), like(vb), like(gc)],
        scratch_shapes=[pltpu.VMEM((hb, dv, dk), jnp.float32)],
        interpret=interpret,
        compiler_params=kernels.tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        name=KDA_KERNEL_NAMES[1],
    )(flat(q), flat(k), flat(kb), flat(vb), flat(gc), states, inv, p,
      flat(do))
    return tuple(g.reshape(a.shape)
                 for g, a in zip(grads, (q, k, kb, vb, gc)))


# --------------------------------------------------------- the whole row ---

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _kda_chunks(q, k, kb, vb, gc, chunk: int, impl: str,
                unbounded: bool = False):
    return _kda_chunks_fwd(q, k, kb, vb, gc, chunk, impl, unbounded)[0]


def _kda_chunks_fwd(q, k, kb, vb, gc, chunk, impl, unbounded):
    if impl == "flash":
        o, *kept = _pallas_fwd(q, k, kb, vb, gc, chunk, kernels.interpret(),
                               unbounded)
    else:
        o, *kept = _scan_fwd(q, k, kb, vb, gc, chunk, unbounded)
    return o, (q, k, kb, vb, gc, *kept)


def _kda_chunks_bwd(chunk, impl, unbounded, res, do):
    if impl == "flash":
        return _pallas_bwd(*res, do, chunk, kernels.interpret(), unbounded)
    return _scan_bwd(*res, do, chunk, unbounded)


_kda_chunks.defvjp(_kda_chunks_fwd, _kda_chunks_bwd)


def chunk_size(s: int) -> int:
    """Positions a chunk: ``CHUNK`` where the row has them, else the row
    rounded up to whole sub-chunks."""
    return CHUNK if s >= CHUNK else -(-s // SUB) * SUB


def kda_attention(q, k, v, g, beta, impl: str = "dense",
                  unbounded: bool = False):
    """``q``, ``k`` [b, s, h, d_k] (normalised and scaled by the caller),
    ``v`` [b, s, h, d_v], ``g`` [b, s, h, d_k] float32 log-decays in
    ``[MIN_LOG_DECAY, 0]`` (any finite value <= 0 where ``unbounded``),
    ``beta`` [b, s, h] -> o [b, s, h, d_v]: the recurrence of the module's
    docstring from a zero state, in chunks. ``impl`` ``flash`` runs the
    chunks in the Pallas kernels (``d_k`` and ``d_v`` multiples of 128),
    anything else as ``jax.numpy`` under a scan."""
    b, s, h, dk = q.shape
    chunk = chunk_size(s)
    impl = "flash" if impl == "flash" else "dense"
    if impl == "flash" and (dk % 128 or v.shape[-1] % 128):
        raise ValueError(f"the KDA kernels take head sizes on the 128 grid; "
                         f"got d_k {dk}, d_v {v.shape[-1]}")
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
    o = _kda_chunks(q, k, kb, vb, gc, chunk, impl, unbounded)
    return o[:, :s] if pad else o


# ------------------------------------- the passes around the kernels ---
#
# Everything a KDA layer does element by element between its frozen
# products and the kernels, one chunk of one head at a time and in float32
# throughout: ``_pre_rows`` / ``_post_rows`` and their hand-written
# pull-backs, plain ``jax.numpy`` on ``[chunk, d]`` blocks as ``_chunk`` is.
# ``dense`` maps them over the chunks (the pull-back's carry under a
# reverse scan); ``flash`` runs them inside four ``pallas_call``s
# (``KDA_PASS_NAMES``) whose blocks are whole rows of ``[b, s, h * d]`` as
# the products leave them, so every array is read once and written once a
# direction.

class _Pass(NamedTuple):
    """What a pass knows before it sees an array."""
    heads: int
    chunk: int
    tile: int           # rows a block of the Pallas form
    lower: float        # the bounded gate's floor (log-decay); None: the
    #                     unbounded softplus gate, whose decays are counted
    eps: float          # the head norm's
    impl: str
    interpret: bool     # the Pallas form interpreted (no chip)


def _taps(ext, w, first: int, rows: int, flip: bool = False):
    """``sum_i w[i] * ext[first + i : first + i + rows]`` for ``w`` [K, d];
    ``flip`` reads the taps last to first (the convolution's transpose)."""
    taps = w.shape[0]
    return sum(w[(taps - 1 - i if flip else i):(taps - i if flip else i + 1)]
               * ext[first + i:first + i + rows] for i in range(taps))


def _conv_silu(lead, y, w):
    """``y`` [C, d] after the ``_HALO`` rows before it, through the causal
    convolution and SiLU -> (c, sigmoid(c), c * sigmoid(c))."""
    c = _taps(jnp.concatenate([lead, y], 0), w, _HALO - (w.shape[0] - 1),
              y.shape[0])
    sig = jax.nn.sigmoid(c)
    return c, sig, c * sig


def _unit(a):
    """a / sqrt(sum a^2 + 1e-6) over a head's lanes, and that factor."""
    r = jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    return a * r, r


def _ones_below(c: int, transpose: bool = False):
    """[c, c] ones on and below the diagonal (on and above: transposed):
    a running sum within the chunk as one product."""
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return jnp.where((row <= col) if transpose else (row >= col), 1.0, 0.0)


def _softplus(v):
    return jnp.maximum(v, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(v)))


def _gates(yf, a, dt, logit, keep, lower):
    """sigmoid of the decay gate's argument [C, d] (with ``lower`` None:
    the pair ``softplus``, ``sigmoid`` of ``yf + dt``) and of beta's logit
    [C, 1], and ``keep`` (1 where no mask came)."""
    keep = 1.0 if keep is None else keep
    if lower is None:
        x = yf + dt
        return (_softplus(x), jax.nn.sigmoid(x)), jax.nn.sigmoid(logit), keep
    return jax.nn.sigmoid(a * (yf + dt)), jax.nn.sigmoid(logit), keep


def _pre_rows(leads, ys, yf, ws, a, dt, logit, keep, lower):
    """One chunk of one head before the kernels, float32. ``ys`` the q, k
    and v products' rows [C, d] and ``leads`` the ``_HALO`` rows before
    each (zeros at a row's start), ``ws`` their taps [K, d]; ``yf`` the
    decay product's rows, ``a = exp(A_log)`` and ``dt`` [1, d]; ``logit``
    beta's [C, 1]; ``keep`` [C, 1] or None -> q (at ``d ** -0.5``), k,
    beta k, beta v and the chunk's running log-decay. The log-decay is
    ``lower * sigmoid(a (yf + dt))``, or with ``lower`` None the unbounded
    ``-a softplus(yf + dt)``, whose live and steep (under
    ``MIN_LOG_DECAY``) decays follow as two [1, d] counts a lane."""
    sq, sk, sv = (_conv_silu(lead, y, w)[2]
                  for lead, y, w in zip(leads, ys, ws))
    sig, beta, keep = _gates(yf, a, dt, logit, keep, lower)
    k = _unit(sk)[0]
    beta = beta * keep
    if lower is None:
        g = -(a * sig[0]) * keep
        counts = (jnp.sum(jnp.broadcast_to(keep, g.shape), 0, keepdims=True),
                  jnp.sum(jnp.where(g < MIN_LOG_DECAY, 1.0, 0.0), 0,
                          keepdims=True))
        return (_unit(sq)[0] * sq.shape[-1] ** -0.5, k, k * beta, sv * beta,
                _exact(_ones_below(yf.shape[0]), g), *counts)
    gc = _exact(_ones_below(yf.shape[0]), (lower * keep) * sig)
    return _unit(sq)[0] * sq.shape[-1] ** -0.5, k, k * beta, sv * beta, gc


def _pre_rows_grads(leads, ys, yf, ws, a, dt, logit, keep, lower,
                    dq, dk, dkb, dvb, dgc, tails):
    """``_pre_rows`` rebuilt and pulled back, float32. ``tails``: the
    first ``_HALO`` rows of the NEXT chunk's cotangent toward each
    convolution's output (zeros at a row's end), which this chunk's last
    rows fed -> the cotangents of ``ys``, ``yf`` and ``logit``, and this
    chunk's own first rows for the chunk before it."""
    (cq, gq, sq), (ck, gk, sk), (cv, gv, sv) = (
        _conv_silu(lead, y, w) for lead, y, w in zip(leads, ys, ws))
    sig, sb, keep = _gates(yf, a, dt, logit, keep, lower)
    (qh, rq), (kh, rk) = _unit(sq), _unit(sk)
    beta = sb * keep
    along = lambda x, y: jnp.sum(x * y, -1, keepdims=True)  # noqa: E731
    dqh, dkh = dq * sq.shape[-1] ** -0.5, dk + beta * dkb
    d_s = (rq * (dqh - qh * along(dqh, qh)),
           rk * (dkh - kh * along(dkh, kh)), beta * dvb)
    d_logit = (along(dkb, kh) + along(dvb, sv)) * keep * sb * (1.0 - sb)
    d_ys, heads_ = [], []
    for ds, c, g, w, tail in zip(d_s, (cq, ck, cv), (gq, gk, gv), ws, tails):
        dc = ds * g * (1.0 + c * (1.0 - g))
        d_ys.append(_taps(jnp.concatenate([dc, tail], 0), w, 0, c.shape[0],
                          flip=True))
        heads_.append(dc[:_HALO])
    dg = _exact(_ones_below(yf.shape[0], transpose=True), dgc)
    if lower is None:       # softplus' derivative is the sigmoid
        d_yf = -(dg * a) * sig[1] * keep
    else:
        d_yf = dg * ((lower * keep) * a) * sig * (1.0 - sig)
    return (*d_ys, d_yf, d_logit, tuple(heads_))


def _post_rows(o, scale, logit, eps):
    """One head's rows after the kernels, float32: the head's RMSNorm
    (``scale`` [1, d]) times its gate ``sigmoid(logit)``, one a head [C, 1]
    or one a channel [C, d]."""
    r = jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    return o * r * scale * jax.nn.sigmoid(logit)


def _post_rows_grads(o, scale, logit, eps, dy):
    """``_post_rows`` rebuilt and pulled back -> the cotangents of ``o``
    and ``logit``."""
    r = jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    n, gate = o * r, jax.nn.sigmoid(logit)
    if logit.shape[-1] == 1:
        d_logit = (jnp.sum(dy * n * scale, -1, keepdims=True) * gate
                   * (1.0 - gate))
    else:                   # a gate a channel
        d_logit = dy * n * scale * gate * (1.0 - gate)
    dn = dy * gate * scale
    return r * (dn - n * jnp.mean(dn * n, -1, keepdims=True)), d_logit


# -------------------------------------------------- their jax.numpy form ---

def _tiled(a, h: int, chunk: int):
    """[b, s, h * d] -> [b, h, n, chunk, d] in float32."""
    b, s, hd = a.shape
    return a.astype(jnp.float32).reshape(
        b, s // chunk, chunk, h, hd // h).transpose(0, 3, 1, 2, 4)


def _untiled(a, dtype):
    """[b, h, n, chunk, d] -> [b, s, h * d]."""
    b, h, n, c, d = a.shape
    return a.transpose(0, 2, 3, 1, 4).reshape(b, n * c, h * d).astype(dtype)


def _dense_operands(cfg, yq, yk, yv, yf, logit, keep, ws, a, dt):
    """The arrays by (row, head, chunk) and the parameters by head."""
    h, c = cfg.heads, cfg.chunk
    ys = tuple(_tiled(y, h, c) for y in (yq, yk, yv))
    leads = tuple(jnp.concatenate(
        [jnp.zeros_like(y[:, :, :1, -_HALO:]), y[:, :, :-1, -_HALO:]], 2)
        for y in ys)
    if keep is not None:
        keep = jnp.broadcast_to(_tiled(keep, 1, c),
                                ys[0].shape[:-1] + (1,))
    per_head = lambda p: jnp.moveaxis(  # noqa: E731
        p.reshape(p.shape[:-1] + (h, -1)), -2, 0)
    return (leads, ys, _tiled(yf, h, c), tuple(per_head(w) for w in ws),
            per_head(a), per_head(dt), _tiled(logit, h, c), keep)


def _mapped(fn, n_arrays: int, n_params: int, levels):
    """``fn(*arrays, *params)`` over the leading axes of the arrays, one
    axis a level; a level of 1 also maps the parameters (the head axis)."""
    for with_params in levels:
        fn = jax.vmap(fn, in_axes=(0,) * n_arrays
                      + ((0 if with_params else None),) * n_params)
    return fn


def _pre_dense_fwd(cfg, yq, yk, yv, yf, logit, keep, ws, a, dt):
    leads, ys, yf_, ws, a, dt, logit, keep = _dense_operands(
        cfg, yq, yk, yv, yf, logit, keep, ws, a, dt)

    def one(leads, ys, yf, logit, keep, ws, a, dt):
        return _pre_rows(leads, ys, yf, ws, a, dt, logit, keep, cfg.lower)

    outs = _mapped(one, 5, 3, (False, True, False))(
        leads, ys, yf_, logit, keep, ws, a, dt)
    arrays = tuple(_untiled(o, like.dtype)
                   for o, like in zip(outs, (yq, yk, yk, yv, yf)))
    if cfg.lower is None:   # the live and the steep decays, summed
        return arrays + (jnp.stack([jnp.sum(c) for c in outs[5:]]),)
    return arrays


def _pre_dense_bwd(cfg, yq, yk, yv, yf, logit, keep, ws, a, dt, cots):
    h, c = cfg.heads, cfg.chunk
    leads, ys, yf_, ws, a, dt, logit_, keep = _dense_operands(
        cfg, yq, yk, yv, yf, logit, keep, ws, a, dt)
    cots = tuple(_tiled(g, h, c) for g in cots)

    def one(leads, ys, yf, logit, keep, cots, tails, ws, a, dt):
        return _pre_rows_grads(leads, ys, yf, ws, a, dt, logit, keep,
                               cfg.lower, *cots, tails)

    step = _mapped(one, 7, 3, (True, False))        # heads, then rows

    def body(tails, xs):
        *grads, heads_ = step(*xs, tails, ws, a, dt)
        return heads_, tuple(grads)

    by_chunk = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: jnp.moveaxis(x, 2, 0), t)
    zero = jnp.zeros_like(ys[0][:, :, 0, :_HALO])
    _, grads = jax.lax.scan(
        body, (zero, zero, zero),
        by_chunk((leads, ys, yf_, logit_, keep, cots)), reverse=True)
    grads = [jnp.moveaxis(g, 0, 2) for g in grads]
    return tuple(_untiled(g, like.dtype)
                 for g, like in zip(grads, (yq, yk, yv, yf, logit)))


def _post_dense(cfg, o, logit, scale, dy=None):
    """Forward, or with ``dy`` the pull-back, a head's whole row a block."""
    h, s = cfg.heads, o.shape[1]
    arrays = (_tiled(o, h, s), _tiled(logit, h, s))
    if dy is None:
        fn = lambda o, logit: _post_rows(o, scale, logit, cfg.eps)  # noqa
        return _untiled(_mapped(fn, 2, 0, (False,) * 3)(*arrays), o.dtype)
    fn = lambda o, logit, dy: _post_rows_grads(  # noqa: E731
        o, scale, logit, cfg.eps, dy)
    do, d_logit = _mapped(fn, 3, 0, (False,) * 3)(*arrays, _tiled(dy, h, s))
    return _untiled(do, o.dtype), _untiled(d_logit, logit.dtype)


# ----------------------------------------------------- their Pallas form ---

def _row_tile(s: int, chunk: int, width: int, wider: int = 1) -> int:
    """Rows a block: whole chunks that divide the row, at most
    ``_TILE_CHUNKS`` of them and ``_TILE_ELEMENTS`` elements an operand,
    times ``wider``."""
    n = s // chunk
    most = max(1, wider * min(_TILE_CHUNKS,
                              _TILE_ELEMENTS // (chunk * width)))
    return chunk * max(m for m in range(1, most + 1) if n % m == 0)


def _column(ref, rows, j):
    """Head ``j``'s column of a ``[tile, heads]`` block as [C, 1] float32."""
    block = ref[rows, :].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == j, block, 0.0), -1, keepdims=True)


def _set_column(ref, rows, j, col):
    block = ref[rows, :]
    lane = jax.lax.broadcasted_iota(jnp.int32, block.shape, 1)
    ref[rows, :] = jnp.where(lane == j, col.astype(block.dtype), block)


def _pre_kernel(*refs, cfg: _Pass, masked: bool, backward: bool):
    """One (row, tile of whole chunks) program over every head. Forward:
    tiles in any order; under the unbounded gate the last output is the
    tile's [8, d] block of counts (row 0 the live decays a lane, row 1 the
    steep ones). Backward: tiles last to first, and ``tails_ref``
    [3, _HALO, h * d] hands each head's first rows of the convolutions'
    cotangent to the tile before."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    refs = list(refs)
    ys, halos = refs[:3], refs[3:6]
    yf_ref, w_ref, a_ref, dt_ref, logit_ref = refs[6:11]
    keep_ref = refs[11] if masked else None
    rest = refs[11 + masked:]
    if backward:
        cots, outs, tails_ref = rest[:5], rest[5:10], rest[10]
    else:
        outs = rest
    counting = cfg.lower is None and not backward
    if counting:
        outs, counts_ref = rest[:5], rest[5]
    tile, c = ys[0].shape[0], cfg.chunk
    d = ys[0].shape[1] // cfg.heads
    # the rows before the row's first tile are zeros, not the halo block
    # (the index map clamps it to the first block there)
    first = (pl.program_id(1) == (pl.num_programs(1) - 1 if backward else 0))
    inner = 1.0 - first.astype(f32)

    if backward:
        @pl.when(pl.program_id(1) == 0)
        def _():
            tails_ref[...] = jnp.zeros_like(tails_ref)

    def head(j, carry):
        cols = pl.ds(pl.multiple_of(j * d, d), d)
        ws = [w_ref[x, :, cols] for x in range(3)]
        a, dt = a_ref[:, cols], dt_ref[:, cols]
        tails = [tails_ref[x, :, cols] for x in range(3)] if backward else ()
        chunks = range(tile // c)
        for i in (reversed(chunks) if backward else chunks):
            rows = pl.ds(i * c, c)
            before = pl.ds(i * c - _HALO_BLOCK, _HALO_BLOCK)
            leads = [(y[before, cols].astype(f32) if i
                      else halo[:, cols].astype(f32) * inner)[-_HALO:]
                     for y, halo in zip(ys, halos)]
            args = (leads, [y[rows, cols].astype(f32) for y in ys],
                    yf_ref[rows, cols].astype(f32), ws, a, dt,
                    _column(logit_ref, rows, j),
                    keep_ref[rows, :].astype(f32) if masked else None,
                    cfg.lower)
            if backward:
                *grads, d_logit, tails = _pre_rows_grads(
                    *args, *(g[rows, cols].astype(f32) for g in cots), tails)
                _set_column(outs[4], rows, j, d_logit)
            else:
                grads = _pre_rows(*args)
            if counting:
                *grads, live, steep = grads
                carry = (carry[0] + live, carry[1] + steep)
            for out, g in zip(outs, grads):
                out[rows, cols] = g.astype(out.dtype)
        for x, t in enumerate(tails):
            tails_ref[x, :, cols] = t
        return carry

    if not counting:
        jax.lax.fori_loop(0, cfg.heads, head, 0)
        return
    zero = jnp.zeros((1, d), f32)
    live, steep = jax.lax.fori_loop(0, cfg.heads, head, (zero, zero))
    row = jax.lax.broadcasted_iota(jnp.int32, counts_ref.shape, 0)
    counts_ref[...] = jnp.where(row == 0, live,
                                jnp.where(row == 1, steep, 0.0))


def _post_kernel(*refs, cfg: _Pass, backward: bool, channel: bool):
    """One (row, tile) program over every head: no row needs another.
    ``channel``: the gate's logits are a ``[tile, h * d]`` block read as
    the head's column block, else a ``[tile, heads]`` one."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    o_ref, logit_ref, scale_ref = refs[:3]
    tile, c = o_ref.shape[0], cfg.chunk
    d = o_ref.shape[1] // cfg.heads
    scale = scale_ref[...].astype(f32)

    def head(j, carry):
        cols = pl.ds(pl.multiple_of(j * d, d), d)
        for i in range(tile // c):
            rows = pl.ds(i * c, c)
            o = o_ref[rows, cols].astype(f32)
            logit = (logit_ref[rows, cols].astype(f32) if channel
                     else _column(logit_ref, rows, j))
            if backward:
                dy_ref, do_ref, d_logit_ref = refs[3:]
                do, d_logit = _post_rows_grads(
                    o, scale, logit, cfg.eps, dy_ref[rows, cols].astype(f32))
                do_ref[rows, cols] = do.astype(do_ref.dtype)
                if channel:
                    d_logit_ref[rows, cols] = d_logit.astype(
                        d_logit_ref.dtype)
                else:
                    _set_column(d_logit_ref, rows, j, d_logit)
            else:
                refs[3][rows, cols] = _post_rows(
                    o, scale, logit, cfg.eps).astype(refs[3].dtype)
        return carry

    jax.lax.fori_loop(0, cfg.heads, head, 0)


def _pass_specs(pl, cfg: _Pass, s: int, backward: bool):
    """Block specs over the grid (row, tile): a tile's rows at a given
    width, the ``_HALO_BLOCK`` rows before them, a whole small array."""
    n = s // cfg.tile
    at = (lambda t: n - 1 - t) if backward else (lambda t: t)
    per = cfg.tile // _HALO_BLOCK
    rows = lambda width: pl.BlockSpec(  # noqa: E731
        (None, cfg.tile, width), lambda i, t: (i, at(t), 0))
    halo = lambda width: pl.BlockSpec(  # noqa: E731
        (None, _HALO_BLOCK, width),
        lambda i, t: (i, jnp.maximum(at(t) * per - 1, 0), 0))
    whole = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda i, t: (0,) * len(shape))
    return n, rows, halo, whole


@functools.partial(jax.jit, static_argnums=0)
def _pre_pallas(cfg, yq, yk, yv, yf, logit, keep, ws, a, dt, cots=None):
    """Forward, or with ``cots`` the pull-back (a ``jit`` of its own, as
    the kernels' forms are)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    backward = cots is not None
    b, s, hd = yq.shape
    n, rows, halo, whole = _pass_specs(pl, cfg, s, backward)
    w = jnp.stack(ws)
    masked = keep is not None
    operands = [yq, yk, yv, yq, yk, yv, yf, w, a, dt, logit]
    in_specs = [rows(hd)] * 3 + [halo(hd)] * 3 + [
        rows(hd), whole(w.shape), whole(a.shape), whole(dt.shape),
        rows(cfg.heads)]
    if masked:
        operands.append(keep)
        in_specs.append(rows(1))
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    if backward:
        operands += list(cots)
        in_specs += [rows(hd)] * 5
        out_shape = [like(a) for a in (yq, yk, yv, yf, logit)]
        out_specs = [rows(hd)] * 4 + [rows(cfg.heads)]
        scratch = [pltpu.VMEM((3, _HALO, hd), jnp.float32)]
    else:
        out_shape = [like(a) for a in (yq, yk, yk, yv, yf)]
        out_specs = [rows(hd)] * 5
        scratch = []
    d = hd // cfg.heads
    if cfg.lower is None and not backward:     # a tile's [8, d] counts
        out_shape.append(jax.ShapeDtypeStruct((b, n * 8, d), jnp.float32))
        out_specs.append(pl.BlockSpec((None, 8, d), lambda i, t: (i, t, 0)))
    outs = tuple(pl.pallas_call(
        functools.partial(_pre_kernel, cfg=cfg, masked=masked,
                          backward=backward),
        grid=(b, n), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        interpret=cfg.interpret,
        compiler_params=kernels.tpu_compiler_params(
            ("parallel", "arbitrary" if backward else "parallel")),
        name=KDA_PASS_NAMES[1 if backward else 0],
    )(*operands))
    if len(outs) > 5:       # the live and the steep decays, summed
        outs = outs[:5] + (jnp.sum(outs[5].reshape(b, n, 8, d)[:, :, :2],
                                   (0, 1, 3)),)
    return outs


@functools.partial(jax.jit, static_argnums=0)
def _post_pallas(cfg, o, logit, scale, dy=None):
    """Forward, or with ``dy`` the pull-back."""
    import jax.experimental.pallas as pl

    backward = dy is not None
    b, s, hd = o.shape
    n, rows, _, whole = _pass_specs(pl, cfg, s, False)
    scale = scale.reshape(1, -1)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    channel = logit.shape[-1] == hd
    out = pl.pallas_call(
        functools.partial(_post_kernel, cfg=cfg, backward=backward,
                          channel=channel),
        grid=(b, n),
        in_specs=[rows(hd), rows(logit.shape[-1]), whole(scale.shape)]
        + [rows(hd)] * backward,
        out_specs=([rows(hd), rows(logit.shape[-1])] if backward
                   else rows(hd)),
        out_shape=[like(o), like(logit)] if backward else like(o),
        interpret=cfg.interpret,
        compiler_params=kernels.tpu_compiler_params(("parallel", "parallel")),
        name=KDA_PASS_NAMES[3 if backward else 2],
    )(o, logit, scale, *([dy] if backward else []))
    return tuple(out) if backward else out


# ------------------------------------------------- the layer around them ---

def _by_lane(cfg: _Pass, conv, a_log, dt_bias):
    """The frozen parameters as the passes read them, float32: the three
    convolutions' taps [K, h * d], ``exp(A_log)`` a lane and ``dt_bias``,
    both [1, h * d]."""
    f32 = jnp.float32
    hd = dt_bias.shape[0]
    a = jnp.repeat(jnp.exp(a_log.astype(f32)), hd // cfg.heads)
    return (tuple(w.astype(f32) for w in conv), a.reshape(1, hd),
            dt_bias.astype(f32).reshape(1, hd))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kda_pre(cfg: _Pass, yq, yk, yv, yf, logit, keep, conv, a_log, dt_bias):
    """The q, k, v and decay products ``[b, s, h * d]`` (s whole chunks),
    beta's logits [b, s, h], ``keep`` [b, s, 1] or None and the frozen
    parameters -> q, k, beta k, beta v and the running log-decay, as the
    kernels read them."""
    return _kda_pre_fwd(cfg, yq, yk, yv, yf, logit, keep, conv, a_log,
                        dt_bias)[0]


def _kda_pre_fwd(cfg, yq, yk, yv, yf, logit, keep, conv, a_log, dt_bias):
    run = _pre_pallas if cfg.impl == "flash" else _pre_dense_fwd
    out = run(cfg, yq, yk, yv, yf, logit, keep,
              *_by_lane(cfg, conv, a_log, dt_bias))
    return out, (yq, yk, yv, yf, logit, keep, conv, a_log, dt_bias)


def _kda_pre_bwd(cfg, res, cots):
    yq, yk, yv, yf, logit, keep, conv, a_log, dt_bias = res
    run = _pre_pallas if cfg.impl == "flash" else _pre_dense_bwd
    # (the counts of the unbounded gate, last, have no pull-back)
    grads = run(cfg, yq, yk, yv, yf, logit, keep,
                *_by_lane(cfg, conv, a_log, dt_bias), tuple(cots[:5]))
    return (*grads, *_pre_frozen_grads(cfg, *res, tuple(cots)))


@functools.partial(jax.jit, static_argnums=0)
def _pre_frozen_grads(cfg, yq, yk, yv, yf, logit, keep, conv, a_log, dt_bias,
                      cots):
    """The mask's and the frozen parameters' cotangents: autodiff of the
    mapped form, which is dropped where nobody asks (a LoRA step)."""
    _, pull = jax.vjp(
        lambda keep, *frozen: _pre_dense_fwd(
            cfg, yq, yk, yv, yf, logit, keep, *_by_lane(cfg, *frozen)),
        keep, conv, a_log, dt_bias)
    return pull(cots)


_kda_pre.defvjp(_kda_pre_fwd, _kda_pre_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _kda_post(cfg: _Pass, o, logit, scale):
    """The kernels' output [b, s, h * d], the head gates' logits [b, s, h]
    and the head norm's scale [d] -> the gated, normalised heads."""
    return _kda_post_fwd(cfg, o, logit, scale)[0]


def _kda_post_fwd(cfg, o, logit, scale):
    run = _post_pallas if cfg.impl == "flash" else _post_dense
    return run(cfg, o, logit, scale.astype(jnp.float32)), (o, logit, scale)


def _kda_post_bwd(cfg, res, dy):
    o, logit, scale = res
    run = _post_pallas if cfg.impl == "flash" else _post_dense
    do, d_logit = run(cfg, o, logit, scale.astype(jnp.float32), dy)
    return do, d_logit, _post_scale_grad(cfg, o, logit, scale, dy)


@functools.partial(jax.jit, static_argnums=0)
def _post_scale_grad(cfg, o, logit, scale, dy):
    """The head norm's scale's cotangent, as ``_pre_frozen_grads``."""
    return jax.vjp(lambda sc: _post_dense(
        cfg, o, logit, sc.astype(jnp.float32)), scale)[1](dy)[0]


_kda_post.defvjp(_kda_post_fwd, _kda_post_bwd)


def kda_layer(ys, beta_logits, gate_logits, conv, a_log, dt_bias, o_scale,
              attn_mask=None, *, heads: int, lower, eps: float,
              impl: str = "dense"):
    """A KDA layer between its frozen products: ``ys`` the ``q k v f``
    products [b, s, h * d] as they leave the MXU (``f`` in float32),
    ``beta_logits`` [b, s, h], ``gate_logits`` [b, s, h] (a gate a head)
    or [b, s, h * d] (a gate a channel), ``conv`` the three convolutions'
    taps [K, h * d], ``A_log`` [h], ``dt_bias`` [h * d], ``o_scale`` [d]
    the head norm's, ``attn_mask`` [b, s] or None -> what the output
    product reads, [b, s, h * d]. ``lower``: the bounded gate's floor
    (``lower * sigmoid(exp(A_log) (f + dt_bias))``), or None for the
    unbounded ``-exp(A_log) softplus(f + dt_bias)``; then the result is
    ``(y, counts)``, ``counts`` [2] float32 the live and the steep (under
    ``MIN_LOG_DECAY``) log-decays of every (position, key channel). Three
    ``custom_vjp``s in a row (:func:`_kda_pre`, the kernels'
    :func:`_kda_chunks`, :func:`_kda_post`) keep the products' outputs, the
    kernels' operands, entering states and chunks' ``[C, C]`` matrices, and
    the kernels' output: no intermediate of the layer's width."""
    b, s, hd = ys["q"].shape
    d = hd // heads
    chunk = chunk_size(s)
    impl = "flash" if impl == "flash" else "dense"
    if impl == "flash" and d % 128:
        raise ValueError(f"the KDA kernels take head sizes on the 128 grid; "
                         f"got {d}")
    unbounded = lower is None
    keep = None if attn_mask is None else attn_mask.astype(
        jnp.float32)[:, :, None]
    arrays = [ys[n] for n in "qkvf"] + [beta_logits, gate_logits]
    pad = -s % chunk
    if pad:     # zeros after the row: no decay, no write, read by nobody
        keep = jnp.ones((b, s, 1), jnp.float32) if keep is None else keep
        *arrays, keep = (jnp.pad(a, [(0, 0), (0, pad), (0, 0)])
                         for a in (*arrays, keep))
    cfg = _Pass(heads, chunk, _row_tile(s + pad, chunk, hd),
                None if unbounded else float(lower), float(eps), impl,
                impl == "flash" and kernels.interpret())
    *operands, gates = arrays
    flat = _kda_pre(cfg, *operands, keep, tuple(conv), a_log, dt_bias)
    o = _kda_chunks(*(a.reshape(b, s + pad, heads, d) for a in flat[:5]),
                    chunk, impl, unbounded)
    after = cfg._replace(tile=_row_tile(s + pad, chunk, hd, _TILE_WIDER))
    y = _kda_post(after, o.reshape(b, s + pad, hd), gates, o_scale)
    y = y[:, :s] if pad else y
    return (y, flat[5]) if unbounded else y


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
