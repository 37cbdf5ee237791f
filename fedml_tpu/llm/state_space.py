"""Mamba-2's state-space recurrence (SSD; Dao & Gu 2024, arXiv:2405.21060),
in chunks.

A head ``a`` of ``p`` channels keeps a state ``S`` in ``R^{p x n}``, zero at
a row's start, with ONE decay a head and step:

    S_t = exp(delta_t A_a) S_{t-1} + delta_t x_t B_t^T
    y_t = S_t C_t + D_a x_t,      delta_t > 0 the step size, A_a < 0

``B_t``, ``C_t`` in ``R^n`` are shared by the ``h / g`` heads of a group.
Training never runs that token by token. In a chunk of C positions with
``cs_i`` the running sum of ``delta A`` inside the chunk (its own step
included) and the entering state ``S_in``:

    Y     = ((C B^T) * L) (delta x) + exp(cs) * (C S_in^T) + D x
    L_ij  = exp(cs_i - cs_j) for i >= j, else 0
    S_out = exp(cs_last) S_in + sum_j exp(cs_last - cs_j) delta_j x_j B_j^T

Every exponent is a difference that is at most 0, so nothing leaves float32
however steep the decay (no sub-chunks, unlike a decay a key channel:
``linear_attention.py``). ``C B^T`` is made once a group, not once a head.

:func:`_lanes_step` is that chunk step for one block of lanes (the heads
that share 128 lanes: two of 64 channels) as plain ``jax.numpy``, and
:func:`_group_step` runs it over the heads of one grid step. Both forms run
them and their ``jax.vjp``: ``dense`` under ``lax.scan`` over the chunks,
``flash`` inside two Pallas kernels (``ssd_fwd``: chunks in order, the
states in VMEM scratch, every chunk's entering state written out;
``ssd_bwd``: chunks in reverse order, the states' cotangent in VMEM
scratch). One ``custom_vjp`` over the whole sequence holds the pair
together; its residuals are the operands and the entering states. The
kernels read ``[b, s, h * p]`` as the projection leaves it (a grid step's
heads are a lane-aligned column block): nothing is transposed around them
but the two ``[b, s, h]`` float32 arrays of step sizes and running sums.

:func:`ssm_layer` is the whole Mamba-2 layer between its frozen products:
what the module does element by element before the kernels (the mask,
the causal convolution and SiLU, the step sizes and their running sums,
written straight into the kernels' layouts) and after them (the gate and
the grouped norm) runs as one Pallas pass a direction each, under one
``custom_vjp`` with the kernels: see "the passes around the kernels"
below. :func:`ssd_scan` stays the entry that takes the recurrence's own
operands (the module's ``dense`` path, and the tests).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core import kernels
from .linear_attention import _exact, _taps

# the two kernels' names in a device trace (forward; backward)
SSD_KERNEL_NAMES = ("ssd_fwd", "ssd_bwd")
# the element-wise passes around them (before the kernels: forward,
# backward; after them: forward, backward); none contains a kernel's name
SSM_PASS_NAMES = ("ssm_pre_fwd", "ssm_pre_bwd", "ssm_post_fwd",
                  "ssm_post_bwd")
# positions a chunk where the configuration names none (the published one)
CHUNK = 128
# a short row is one chunk of whole bfloat16 tiles
SUB = 16
# heads a grid step of the kernels works through, at most (a group's heads
# share B and C, so a step never spans two groups)
HEADS_PER_STEP = 16
_LANES = 128

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_NN = (((1,), (0,)), ((), ()))
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, dims, dtype):
    """A product with its operands in the compute dtype and a float32
    result: bfloat16 goes to the MXU in one pass, float32 at full
    precision."""
    if dtype == jnp.float32:
        return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                                   preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               preferred_element_type=jnp.float32)


def heads_per_lane_block(p: int, heads: int) -> int:
    """Heads that share one block of lanes: as many of ``p`` channels as
    fill 128 lanes (two of 64), one where a head has its own."""
    per = _LANES // p if p < _LANES and _LANES % p == 0 else 1
    while heads % per:
        per //= 2
    return max(per, 1)


def heads_per_step(h: int, groups: int, p: int) -> int:
    """Heads a grid step works through: the heads of a group, or the
    largest divisor of them under ``HEADS_PER_STEP`` that is whole lane
    blocks."""
    in_group = h // groups
    per = heads_per_lane_block(p, in_group)
    hb = in_group
    while hb > HEADS_PER_STEP and hb % 2 == 0 and (hb // 2) % per == 0:
        hb //= 2
    return hb


def _column(cols, k: int):
    """Column ``k`` of ``cols`` [C, hb] as [C, 1]: a one-hot select and a
    sum over the lanes (no lane of a narrow array is sliced)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, cols.shape, 1)
    return jnp.sum(jnp.where(lane == k, cols, 0.0), 1, keepdims=True)


def _shared(bm, cm, cs, dtype):
    """What the heads of a grid step share: ``C B^T`` [C, C], and the
    running sums as rows [hb, C]: an exact product with the identity (a
    transpose the MXU makes; [C, hb] has too few lanes to turn)."""
    hb = cs.shape[1]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (hb, hb), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (hb, hb), 1))
    cs_rows = jax.lax.dot_general(eye.astype(jnp.float32), cs, _NT,
                                  precision=_HIGHEST,
                                  preferred_element_type=jnp.float32)
    return _mm(cm, bm, _NT, dtype), cs_rows


def _lanes_step(j, x, dt, cs, cs_rows, cb, bm, cm, d, st, p, dtype):
    """One chunk of the ``per`` heads that share block ``j`` of the step's
    lanes. ``x`` [C, per * p] the block; ``dt``, ``cs`` [C, hb] float32 of
    all the step's heads (step sizes; the chunk's running sum of ``delta
    A``, its own row included) and ``cs_rows`` [hb, C]; ``cb`` [C, C] the
    group's ``C B^T``; ``bm``, ``cm`` [C, n]; ``d`` [1, per * p] the skip a
    lane; ``st`` [per * p, n] the entering states, one head's rows under
    the other's. -> (y [C, per * p] float32, the states the chunk leaves).
    A product whose width is the block's reads or writes every head of it
    at once; the masked product inside the chunk runs once a head, its
    lanes selected after it."""
    f32 = jnp.float32
    c, width = x.shape
    per = width // p
    x = x.astype(f32)
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // p
    row_head = jax.lax.broadcasted_iota(jnp.int32, (width, 1), 0) // p

    def by_lane(cols):
        """``per`` columns [C, 1] -> [C, width], each in its head's lanes."""
        out = cols[0]
        for k in range(1, per):
            out = jnp.where(lane_head == k, cols[k], out)
        return out

    heads = range(j * per, (j + 1) * per)
    dts = [_column(dt, a) for a in heads]
    css = [_column(cs, a) for a in heads]
    dx = x * by_lane(dts)                                    # delta x
    y = None
    for k, a in enumerate(heads):
        decay = jnp.exp(jnp.where(row >= col, css[k] - cs_rows[a:a + 1],
                                  -1e30))
        part = _mm(cb * decay, dx, _NN, dtype)
        y = part if y is None else jnp.where(lane_head == k, part, y)
    y = (y + by_lane([jnp.exp(v) for v in css]) * _mm(cm, st, _NT, dtype)
         + x * d)
    lasts = [v[c - 1:c] for v in css]                        # [1, 1] each
    keep = jnp.exp(lasts[0])
    for k in range(1, per):
        keep = jnp.where(row_head == k, jnp.exp(lasts[k]), keep)
    to_end = by_lane([jnp.exp(last - v) for last, v in zip(lasts, css)])
    return y, st * keep + _mm(dx * to_end, bm, _TN, dtype)


def _group_step(x, dt, cs, bm, cm, d, st, p, dtype):
    """One chunk of the heads of one grid step (all of one group):
    ``x`` [C, hb * p], ``dt``, ``cs`` [C, hb], ``bm``, ``cm`` [C, n], ``d``
    [1, hb * p], ``st`` [blocks, per * p, n] -> (y [C, hb * p] float32,
    the states the chunk leaves). The form ``dense`` scans; the kernels run
    the same two functions block by block on their refs."""
    blocks, width, _ = st.shape
    cb, cs_rows = _shared(bm, cm, cs, dtype)
    ys, sts = [], []
    for j in range(blocks):
        lanes = slice(j * width, (j + 1) * width)
        y, st_new = _lanes_step(j, x[:, lanes], dt, cs, cs_rows, cb, bm, cm,
                                d[:, lanes], st[j], p, dtype)
        ys.append(y)
        sts.append(st_new)
    return jnp.concatenate(ys, 1), jnp.stack(sts)


# ----------------------------------------------------- the jax.numpy form ---
#
# Operands as the kernels take them: x [b, s, h * p]; dt, cs [b, h / hb, s,
# hb] float32; bm, cm [b, s, g * n]; d [1, h * p] float32; the states
# [b, h * p / width, chunks, width, n] float32.

def _by_step(a, chunk, steps):
    """[b, s, steps * w] -> [chunks, b, steps, chunk, w]."""
    b, s, wide = a.shape
    return a.reshape(b, s // chunk, chunk, steps, wide // steps).transpose(
        1, 0, 3, 2, 4)


def _from_steps(a):
    """[chunks, b, steps, chunk, w] -> [b, s, steps * w]."""
    n, b, steps, c, w = a.shape
    return a.transpose(1, 0, 3, 2, 4).reshape(b, n * c, steps * w)


def _small_by_chunk(a, chunk):
    """[b, steps, s, hb] -> [chunks, b, steps, chunk, hb]."""
    b, steps, s, hb = a.shape
    return a.reshape(b, steps, s // chunk, chunk, hb).transpose(2, 0, 1, 3, 4)


def _scan_operands(x, dt, cs, bm, cm, d, chunk, groups):
    steps = dt.shape[1]
    spg = steps // groups

    def shared(a):      # a group's B or C, once for each of its steps
        return jnp.repeat(_by_step(a, chunk, groups), spg, 2)

    return (_by_step(x, chunk, steps), _small_by_chunk(dt, chunk),
            _small_by_chunk(cs, chunk), shared(bm), shared(cm),
            d.reshape(steps, 1, -1))


def _scan_fwd(x, dt, cs, bm, cm, d, chunk, groups, p):
    dtype = x.dtype
    b, s, wide = x.shape
    steps = dt.shape[1]
    n = bm.shape[-1] // groups
    width = heads_per_lane_block(p, dt.shape[-1]) * p
    blocks = wide // steps // width
    xs, dts, css, bms, cms, ds = _scan_operands(x, dt, cs, bm, cm, d, chunk,
                                                groups)
    step = jax.vmap(jax.vmap(
        functools.partial(_group_step, p=p, dtype=dtype),
        in_axes=(0, 0, 0, 0, 0, 0, 0)), in_axes=(0, 0, 0, 0, 0, None, 0))

    def body(st, ins):
        y, st_new = step(*ins, ds, st)
        return st_new, (y, st)

    st0 = jnp.zeros((b, steps, blocks, width, n), jnp.float32)
    _, (y, states) = jax.lax.scan(body, st0, (xs, dts, css, bms, cms))
    # [chunks, b, steps, blocks, width, n] -> [b, steps * blocks, chunks, ..]
    states = states.transpose(1, 2, 3, 0, 4, 5).reshape(
        b, steps * blocks, s // chunk, width, n)
    return _from_steps(y).astype(dtype), states


def _step_grads(x, dt, cs, bm, cm, d, st, dy, dst, p, dtype):
    """``_group_step`` rebuilt and ``(dy, dst)`` pulled back to its inputs
    but the skip, each in float32."""
    f32 = jnp.float32
    args = (x.astype(f32), dt, cs, bm.astype(f32), cm.astype(f32), st)
    _, pull = jax.vjp(
        lambda x, dt, cs, bm, cm, st: _group_step(x, dt, cs, bm, cm, d, st,
                                                  p, dtype), *args)
    return pull((dy.astype(f32), dst))


def _scan_bwd(x, dt, cs, bm, cm, d, states, dy, chunk, groups, p):
    dtype = x.dtype
    b, s, wide = x.shape
    steps = dt.shape[1]
    spg = steps // groups
    xs, dts, css, bms, cms, ds = _scan_operands(x, dt, cs, bm, cm, d, chunk,
                                                groups)
    blocks = states.shape[1] // steps
    sts = states.reshape(b, steps, blocks, s // chunk, *states.shape[3:]
                         ).transpose(3, 0, 1, 2, 4, 5)
    step = jax.vmap(jax.vmap(
        functools.partial(_step_grads, p=p, dtype=dtype),
        in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0)),
        in_axes=(0, 0, 0, 0, 0, None, 0, 0, 0))

    def body(dst, ins):
        *ops, st, dy_c = ins
        dx, ddt, dcs, dbm, dcm, dst0 = step(*ops, ds, st, dy_c, dst)
        return dst0, (dx, ddt, dcs, dbm, dcm)

    _, (dx, ddt, dcs, dbm, dcm) = jax.lax.scan(
        body, jnp.zeros_like(sts[0]),
        (xs, dts, css, bms, cms, sts, _by_step(dy, chunk, steps)),
        reverse=True)

    def small(a):
        return a.transpose(1, 2, 0, 3, 4).reshape(dt.shape)

    def shared(a):  # a group's steps each hold their heads' part of dB, dC
        return _from_steps(
            a.reshape(a.shape[0], b, groups, spg, chunk, -1).sum(3))

    return (_from_steps(dx).astype(dtype), small(ddt), small(dcs),
            shared(dbm).astype(bm.dtype), shared(dcm).astype(cm.dtype))


# ------------------------------------------------------ the Pallas kernels ---

def _ssd_fwd_kernel(x_ref, dt_ref, cs_ref, bm_ref, cm_ref, d_ref, y_ref,
                    states_ref, st_ref, *, p: int):
    """One (row, step of heads, chunk) program; chunks run in order and the
    states stay in ``st_ref`` [blocks, width, n] between them. ``x`` and
    ``y`` blocks are ``[chunk, hb * p]`` columns of ``[b, s, h * p]``."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    dtype = x_ref.dtype
    blocks, width, _ = st_ref.shape
    bm, cm, dt, cs = bm_ref[...], cm_ref[...], dt_ref[...], cs_ref[...]
    cb, cs_rows = _shared(bm, cm, cs, dtype)
    for j in range(blocks):
        lanes = pl.ds(j * width, width)
        st = st_ref[j]
        states_ref[j] = st
        y, st_new = _lanes_step(j, x_ref[:, lanes], dt, cs, cs_rows, cb, bm,
                                cm, d_ref[:, lanes], st, p, dtype)
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        st_ref[j] = st_new


def _ssd_bwd_kernel(x_ref, dt_ref, cs_ref, bm_ref, cm_ref, d_ref, states_ref,
                    dy_ref, dx_ref, ddt_ref, dcs_ref, dbm_ref, dcm_ref,
                    dst_ref, *, p: int):
    """The same grid with the chunks in reverse order; ``dst_ref`` holds the
    cotangent of the states a chunk leaves. The body is the ``jax.vjp`` of
    the forward's: of ``_lanes_step`` block by block, then of what the
    blocks share."""
    import jax.experimental.pallas as pl

    @pl.when(pl.program_id(2) == 0)
    def _():
        dst_ref[...] = jnp.zeros_like(dst_ref)

    f32 = jnp.float32
    dtype = x_ref.dtype
    blocks, width, _ = dst_ref.shape
    bm, cm = bm_ref[...].astype(f32), cm_ref[...].astype(f32)
    dt, cs = dt_ref[...], cs_ref[...]
    (cb, cs_rows), pull_shared = jax.vjp(
        functools.partial(_shared, dtype=dtype), bm, cm, cs)
    shared = (dt, cs, cs_rows, cb, bm, cm)
    acc = tuple(jnp.zeros_like(t) for t in shared)
    for j in range(blocks):
        lanes = pl.ds(j * width, width)
        d = d_ref[:, lanes]
        _, pull = jax.vjp(
            lambda x, dt, cs, cs_rows, cb, bm, cm, st: _lanes_step(
                j, x, dt, cs, cs_rows, cb, bm, cm, d, st, p, dtype),
            x_ref[:, lanes].astype(f32), *shared, states_ref[j])
        dx, *parts, dst0 = pull((dy_ref[:, lanes].astype(f32), dst_ref[j]))
        dx_ref[:, lanes] = dx.astype(dx_ref.dtype)
        dst_ref[j] = dst0
        acc = tuple(a + g for a, g in zip(acc, parts))
    ddt, dcs, dcs_rows, dcb, dbm, dcm = acc
    dbm_s, dcm_s, dcs_s = pull_shared((dcb, dcs_rows))
    ddt_ref[...] = ddt
    dcs_ref[...] = dcs + dcs_s
    dbm_ref[...] = (dbm + dbm_s).astype(dbm_ref.dtype)
    dcm_ref[...] = (dcm + dcm_s).astype(dcm_ref.dtype)


def _pallas_specs(pl, steps, groups, chunk, hb, p, n, blocks, width, chunks,
                  reverse):
    at = (lambda c: chunks - 1 - c) if reverse else (lambda c: c)
    spg = steps // groups
    spec_x = pl.BlockSpec((None, chunk, hb * p), lambda i, j, c: (i, at(c), j))
    spec_small = pl.BlockSpec((None, None, chunk, hb),
                              lambda i, j, c: (i, j, at(c), 0))
    spec_bc = pl.BlockSpec((None, chunk, n),
                           lambda i, j, c: (i, at(c), j // spg))
    spec_d = pl.BlockSpec((1, hb * p), lambda i, j, c: (0, j))
    spec_st = pl.BlockSpec((None, blocks, None, width, n),
                           lambda i, j, c: (i, j, at(c), 0, 0))
    return spec_x, spec_small, spec_bc, spec_d, spec_st


# the Pallas forms are ``jit``s of their own: a model's layers share one
# trace and one lowering of each kernel (``interpret`` is an argument so
# that a process which both interprets and compiles keeps two entries)

@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _pallas_fwd(x, dt, cs, bm, cm, d, chunk, groups, p, interpret):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s, wide = x.shape
    steps, hb = dt.shape[1], dt.shape[3]
    n = bm.shape[-1] // groups
    width = heads_per_lane_block(p, hb) * p
    blocks = hb * p // width
    chunks = s // chunk
    spec_x, spec_small, spec_bc, spec_d, spec_st = _pallas_specs(
        pl, steps, groups, chunk, hb, p, n, blocks, width, chunks, False)
    return pl.pallas_call(
        functools.partial(_ssd_fwd_kernel, p=p),
        grid=(b, steps, chunks),
        in_specs=[spec_x, spec_small, spec_small, spec_bc, spec_bc, spec_d],
        out_specs=[spec_x, spec_st],
        out_shape=[jax.ShapeDtypeStruct((b, s, wide), x.dtype),
                   jax.ShapeDtypeStruct(
                       (b, steps * blocks, chunks, width, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blocks, width, n), jnp.float32)],
        interpret=interpret,
        compiler_params=kernels.tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        name=SSD_KERNEL_NAMES[0],
    )(x, dt, cs, bm, cm, d)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _pallas_bwd(x, dt, cs, bm, cm, d, states, dy, chunk, groups, p,
                interpret):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s, wide = x.shape
    steps, hb = dt.shape[1], dt.shape[3]
    n = bm.shape[-1] // groups
    width = heads_per_lane_block(p, hb) * p
    blocks = hb * p // width
    chunks = s // chunk
    spg = steps // groups
    spec_x, spec_small, spec_bc, spec_d, spec_st = _pallas_specs(
        pl, steps, groups, chunk, hb, p, n, blocks, width, chunks, True)
    at = lambda c: chunks - 1 - c  # noqa: E731
    # a step's part of dB and dC: one block a step, summed over a group's
    # steps afterwards (no sum where a step is the whole group)
    spec_part = pl.BlockSpec((None, chunk, n), lambda i, j, c: (i, at(c), j))
    part = jax.ShapeDtypeStruct((b, s, steps * n),
                                bm.dtype if spg == 1 else jnp.float32)
    dx, ddt, dcs, dbm, dcm = pl.pallas_call(
        functools.partial(_ssd_bwd_kernel, p=p),
        grid=(b, steps, chunks),
        in_specs=[spec_x, spec_small, spec_small, spec_bc, spec_bc, spec_d,
                  spec_st, spec_x],
        out_specs=[spec_x, spec_small, spec_small, spec_part, spec_part],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, jnp.float32),
                   jax.ShapeDtypeStruct(cs.shape, jnp.float32), part, part],
        scratch_shapes=[pltpu.VMEM((blocks, width, n), jnp.float32)],
        interpret=interpret,
        compiler_params=kernels.tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        name=SSD_KERNEL_NAMES[1],
    )(x, dt, cs, bm, cm, d, states, dy)
    if spg > 1:
        dbm, dcm = (a.reshape(b, s, groups, spg, n).sum(3).reshape(bm.shape)
                    .astype(bm.dtype) for a in (dbm, dcm))
    return dx, ddt, dcs, dbm, dcm


# --------------------------------------------------------- the whole row ---

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _ssd_chunks(x, dt, cs, bm, cm, d, chunk: int, groups: int, p: int,
                impl: str):
    return _ssd_chunks_fwd(x, dt, cs, bm, cm, d, chunk, groups, p, impl)[0]


def _ssd_chunks_fwd(x, dt, cs, bm, cm, d, chunk, groups, p, impl):
    if impl == "flash":
        y, states = _pallas_fwd(x, dt, cs, bm, cm, d, chunk, groups, p,
                                kernels.interpret())
    else:
        y, states = _scan_fwd(x, dt, cs, bm, cm, d, chunk, groups, p)
    return y, (x, dt, cs, bm, cm, d, states)


def _ssd_chunks_bwd(chunk, groups, p, impl, res, dy):
    x, *_, d, _ = res
    if impl == "flash":
        grads = _pallas_bwd(*res, dy, chunk, groups, p, kernels.interpret())
    else:
        grads = _scan_bwd(*res, dy, chunk, groups, p)
    # the skip's own gradient, a sum a lane: XLA drops it where D is frozen
    dd = jnp.sum(dy.astype(jnp.float32) * x.astype(jnp.float32), (0, 1))
    return (*grads, dd.reshape(d.shape))


_ssd_chunks.defvjp(_ssd_chunks_fwd, _ssd_chunks_bwd)


def chunk_size(s: int, chunk: int = CHUNK) -> int:
    """Positions a chunk: ``chunk`` where the row has them, else the row
    rounded up to whole tiles of ``SUB`` rows."""
    return chunk if s >= chunk else -(-s // SUB) * SUB


def _kernel_plan(h: int, groups: int, p: int, n: int, impl: str):
    """-> (``flash`` or ``dense``, heads a grid step), or why the kernels
    cannot take the shape."""
    if h % groups:
        raise ValueError(f"{groups} groups do not divide {h} heads")
    impl = "flash" if impl == "flash" else "dense"
    hb = heads_per_step(h, groups, p)
    if impl == "flash" and (n % _LANES or (hb * p) % _LANES):
        raise ValueError(
            f"the SSD kernels take a state size and a step's heads x "
            f"channels on the 128 grid; got n {n}, {hb} heads of {p}")
    return impl, hb


def ssd_scan(x, dt, a, bm, cm, d, impl: str = "dense", chunk: int = CHUNK):
    """``x`` [b, s, h, p]; ``dt`` [b, s, h] float32 step sizes (positive;
    0 at a position that neither writes nor decays); ``a`` [h] negative;
    ``bm``, ``cm`` [b, s, g, n] (head ``i`` reads group ``i // (h / g)``);
    ``d`` [h] the skip -> y [b, s, h, p] in ``x``'s dtype: the recurrence of
    the module's docstring from a zero state, in chunks of ``chunk``
    positions. ``impl`` ``flash`` runs the chunks in the Pallas kernels
    (``n`` and a grid step's ``heads * p`` on the 128 grid), anything else
    as ``jax.numpy`` under a scan."""
    b, s, h, p = x.shape
    groups, n = bm.shape[2], bm.shape[3]
    impl, hb = _kernel_plan(h, groups, p, n, impl)
    chunk = chunk_size(s, chunk)
    f32 = jnp.float32
    dt = dt.astype(f32)
    pad = -s % chunk
    if pad:     # zeros after the row: no decay, no write, read by nobody
        x, bm, cm = (jnp.pad(t, [(0, 0), (0, pad), (0, 0), (0, 0)])
                     for t in (x, bm, cm))
        dt = jnp.pad(dt, [(0, 0), (0, pad), (0, 0)])
    sp = s + pad
    cs = jnp.cumsum((dt * a.astype(f32)).reshape(b, sp // chunk, chunk, h),
                    axis=2).reshape(b, sp, h)

    def by_step(t):     # [b, s, h] -> [b, h / hb, s, hb]
        return t.reshape(b, sp, h // hb, hb).transpose(0, 2, 1, 3)

    y = _ssd_chunks(
        x.reshape(b, sp, h * p), by_step(dt), by_step(cs),
        bm.reshape(b, sp, groups * n), cm.reshape(b, sp, groups * n),
        jnp.repeat(d.astype(f32), p)[None, :], chunk, groups, p, impl)
    y = y.reshape(b, sp, h, p)
    return y[:, :s] if pad else y


# ------------------------------------- the passes around the kernels ---
#
# What a Mamba-2 layer does element by element between its frozen products
# and the kernels, float32 inside: before them the mask, the causal
# depthwise convolution with its bias and SiLU over ``xBC``, the step sizes
# ``softplus(dt + dt_bias)`` and their running sum within a chunk; after
# them the gate ``y * SiLU(z)`` and the RMS norm over each group's
# channels. ``_pre_dense`` / ``_post_dense`` are the module's own
# ``jax.numpy`` form, in the kernels' layout; :func:`ssm_layer` runs the
# same in four ``pallas_call``s (``SSM_PASS_NAMES``) whose blocks are rows
# of the ``in_proj`` product ``[b, s, inner + wide + h]`` as it leaves the
# MXU, read through column blocks, so every array is read once and written
# once a direction, and the backward passes write the product's cotangent
# as one array.

# rows a block of the passes before the kernels, at most (whole chunks);
# rows and columns a step of their loops (a few registers of each array),
# and rows of the halo block a block reads before it
_PRE_ROWS = 128
_ROWS = 16
_PRE_COLS = 512
# rows of the convolution's cotangent a backward step hands the step before
# it (at least taps - 1)
_HALO = 8
# rows and columns (whole groups) a block of the passes after them
_POST_ROWS = 512
_POST_COLS = 2048


class _Plan(NamedTuple):
    """What a pass knows before it sees an array."""
    heads: int
    head_dim: int
    groups: int
    state: int
    chunk: int
    step_heads: int     # heads a grid step of the kernels (``dt``'s layout)
    tile: int           # rows a block before the kernels
    post_tile: int      # rows a block after them
    post_cols: int      # columns (whole groups) a block after them
    eps: float
    interpret: bool

    @property
    def inner(self):
        return self.heads * self.head_dim

    @property
    def bc(self):       # B's columns, and C's
        return self.groups * self.state

    @property
    def wide(self):     # xBC's
        return self.inner + 2 * self.bc


def _divisor(n: int, unit: int, most: int) -> int:
    """The largest multiple of ``unit`` that divides ``n`` and is at most
    ``most`` (``unit`` where none is)."""
    fits = [m for m in range(unit, min(n, most) + 1, unit) if n % m == 0]
    return max(fits, default=unit)


def _silu_grad(c, sig):
    """d SiLU(c) / dc from c and sigmoid(c)."""
    return sig * (1.0 + c * (1.0 - sig))


def _softplus(v):
    return jnp.maximum(v, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(v)))


def _pre_dense(plan: _Plan, zx, keep, conv_w, conv_b, a_log, dt_bias):
    """The module's form before the kernels: ``zx`` the ``in_proj`` product
    [b, s, inner + wide + h], ``keep`` [b, s, 1] float32 or None -> x
    [b, s, inner], B and C [b, s, g * n] in ``zx``'s dtype, and the step
    sizes and their running sums [b, h / hb, s, hb] float32."""
    f32 = jnp.float32
    b, s, _ = zx.shape
    inner, wide, h, hb = plan.inner, plan.wide, plan.heads, plan.step_heads
    xbc = zx[..., inner:inner + wide]
    dt = jax.nn.softplus(zx[..., inner + wide:].astype(f32)
                         + dt_bias.astype(f32))
    if keep is not None:
        xbc, dt = xbc * keep.astype(xbc.dtype), dt * keep
    taps = conv_w.shape[0]
    xp = jnp.pad(xbc.astype(f32), [(0, 0), (taps - 1, 0), (0, 0)])
    c = sum(xp[:, j:j + s] * conv_w[j].astype(f32) for j in range(taps))
    xbc = jax.nn.silu(c + conv_b.astype(f32)).astype(zx.dtype)
    cs = jnp.cumsum((dt * -jnp.exp(a_log.astype(f32))).reshape(
        b, s // plan.chunk, plan.chunk, h), axis=2).reshape(b, s, h)

    def by_step(t):     # [b, s, h] -> [b, h / hb, s, hb]
        return t.reshape(b, s, h // hb, hb).transpose(0, 2, 1, 3)

    return (xbc[..., :inner], xbc[..., inner:inner + plan.bc],
            xbc[..., inner + plan.bc:], by_step(dt), by_step(cs))


def _post_dense(plan: _Plan, y, zx, scale):
    """The module's form after the kernels: ``y`` [b, s, inner], the gate
    ``z`` the product's first ``inner`` columns -> ``RMSNorm_group(y *
    SiLU(z)) * scale`` in ``y``'s dtype."""
    f32 = jnp.float32
    b, s, inner = y.shape
    g = plan.groups
    u = (y.astype(f32) * jax.nn.silu(zx[..., :inner].astype(f32))).reshape(
        b, s, g, inner // g)
    u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True)
                          + plan.eps)
    return (u.reshape(b, s, inner) * scale.astype(f32)).astype(y.dtype)


# ----------------------------------------------------- their Pallas form ---

def _lower_in_chunks(rows: int, chunk: int, transpose: bool = False):
    """[rows, rows] ones where a row sums a column of its own chunk at or
    before it (after it: transposed): a running sum within the chunks as
    one product."""
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
    if transpose:
        r, c = c, r
    return jnp.where((r >= c) & (r // chunk == c // chunk), 1.0, 0.0)


def _pre_kernel(*refs, plan: _Plan, masked: bool, backward: bool):
    """One (row, tile) program over every column, in steps of ``_ROWS``
    rows by ``_PRE_COLS`` columns that stay in registers. Forward: tiles
    in any order, a column's steps in order, each handing its rows to the
    next as the convolution's lead. Backward: tiles and steps last to
    first, each handing the first rows of the convolution's cotangent to
    the step before (``tails_ref`` [_HALO, wide] to the tile before)."""
    import jax.experimental.pallas as pl

    f32 = jnp.float32
    refs = list(refs)
    parts, halos, dt_ref = refs[0:3], refs[3:6], refs[6]
    keep_ref, keep_halo_ref = refs[7:9] if masked else (None, None)
    w_ref, bias_ref, a_ref, dtb_ref = refs[7 + 2 * masked:11 + 2 * masked]
    rest = refs[11 + 2 * masked:]
    tile = dt_ref.shape[0]
    steps = tile // _ROWS
    taps = w_ref.shape[0]
    inner, bc, wide = plan.inner, plan.bc, plan.wide
    # the rows before the row's first tile are zeros, not the halo block
    # (the index map clamps it to the first block there)
    first = pl.program_id(1) == (pl.num_programs(1) - 1 if backward else 0)
    lead_keep = 1.0 - first.astype(f32)
    if masked:
        lead_keep = lead_keep * keep_halo_ref[...]
    if backward:
        cots, (ddt_ref, dcs_ref), out_ref, tails_ref = (
            rest[0:3], rest[3:5], rest[6], rest[7])

        @pl.when(pl.program_id(1) == 0)
        def _():
            tails_ref[...] = jnp.zeros_like(tails_ref)
    else:
        outs, (dt_out, cs_out) = rest[0:3], rest[3:5]

    def column(part: int, j):
        """Columns ``j`` of part ``part`` (x, B or C) through the
        convolution and SiLU, or back through them."""
        width = _divisor(parts[part].shape[1], _LANES, _PRE_COLS)
        at = pl.ds(pl.multiple_of(j * width, _LANES), width)
        at_w = pl.ds(pl.multiple_of((0, inner, inner + bc)[part]
                                    + j * width, _LANES), width)
        src, w, bias = parts[part], w_ref[:, at_w], bias_ref[:, at_w]
        halo = halos[part][:, at].astype(f32) * lead_keep

        def rows(r):
            y = src[pl.ds(r, _ROWS), at].astype(f32)
            return y * keep_ref[pl.ds(r, _ROWS), :] if masked else y

        def conv(lead, y):
            c = _taps(jnp.concatenate([lead, y], 0), w,
                      _ROWS - (taps - 1), _ROWS) + bias
            return c, jax.nn.sigmoid(c)

        if not backward:
            def step(i, lead):
                r = pl.multiple_of(i * _ROWS, _ROWS)
                y = rows(r)
                c, sig = conv(lead, y)
                outs[part][pl.ds(r, _ROWS), at] = (c * sig).astype(
                    outs[part].dtype)
                return y

            jax.lax.fori_loop(0, steps, step, halo, unroll=True)
            return

        def step(k, tail):
            i = steps - 1 - k
            r = pl.multiple_of(i * _ROWS, _ROWS)
            before = rows(pl.multiple_of(jnp.maximum(r - _ROWS, 0), _ROWS))
            c, sig = conv(jnp.where(i == 0, halo, before), rows(r))
            dc = cots[part][pl.ds(r, _ROWS), at].astype(f32) * _silu_grad(
                c, sig)
            dx = _taps(jnp.concatenate([dc, tail], 0), w, 0, _ROWS,
                       flip=True)
            if masked:
                dx = dx * keep_ref[pl.ds(r, _ROWS), :]
            out_ref[0, pl.ds(r, _ROWS), at_w] = dx.astype(out_ref.dtype)
            return dc[:_HALO]

        tails_ref[:, at_w] = jax.lax.fori_loop(0, steps, step,
                                               tails_ref[:, at_w],
                                               unroll=True)

    for part in range(3):
        n = parts[part].shape[1] // _divisor(parts[part].shape[1], _LANES,
                                             _PRE_COLS)

        def body(j, carry, part=part):
            column(part, j)
            return carry

        jax.lax.fori_loop(0, n, body, 0)

    # the step sizes and their running sums, heads in lanes here and in
    # steps of ``hb`` heads in the kernels' layout
    keep = keep_ref[...] if masked else 1.0
    hb = plan.step_heads
    v = dt_ref[...].astype(f32) + dtb_ref[...]
    a = a_ref[...]
    if not backward:
        delta = _softplus(v) * keep
        cs = _exact(_lower_in_chunks(tile, plan.chunk), delta * a, _NN)
        for k in range(plan.heads // hb):
            dt_out[k] = delta[:, k * hb:(k + 1) * hb]
            cs_out[k] = cs[:, k * hb:(k + 1) * hb]
        return
    ddt, dcs = (jnp.concatenate([r[k] for k in range(plan.heads // hb)], 1)
                for r in (ddt_ref, dcs_ref))
    d_delta = ddt + a * _exact(_lower_in_chunks(tile, plan.chunk, True), dcs,
                               _NN)
    out_ref[0, :, pl.ds(wide, plan.heads)] = (
        d_delta * jax.nn.sigmoid(v) * keep).astype(out_ref.dtype)


def _post_kernel(*refs, plan: _Plan, backward: bool):
    """One (row, tile, columns) program: whole groups, no row needs
    another."""
    f32 = jnp.float32
    y_ref, z_ref, scale_ref = refs[:3]
    cols = plan.inner // plan.groups
    for q in range(y_ref.shape[1] // cols):
        at = slice(q * cols, (q + 1) * cols)
        y, z = y_ref[:, at].astype(f32), z_ref[:, at].astype(f32)
        scale = scale_ref[:, at].astype(f32)
        sz = jax.nn.sigmoid(z)
        gate = z * sz
        u = y * gate
        r = jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + plan.eps)
        if not backward:
            refs[3][:, at] = (u * r * scale).astype(refs[3].dtype)
            continue
        du_ref, dy_ref, dz_ref = refs[3:]
        n = u * r
        dn = du_ref[:, at].astype(f32) * scale
        du = r * (dn - n * jnp.mean(dn * n, -1, keepdims=True))
        dy_ref[:, at] = (du * gate).astype(dy_ref.dtype)
        dz_ref[0, :, at] = (du * y * _silu_grad(z, sz)).astype(dz_ref.dtype)


@functools.partial(jax.jit, static_argnums=0)
def _pre_pallas(plan: _Plan, zx, keep, conv_w, conv_b, a_log, dt_bias,
                cots=None, buf=None):
    """Forward: -> x, B, C, dt, cs as the kernels read them. With ``cots``
    (their cotangents) the pull-back, written into the ``xBC`` and ``dt``
    columns of ``buf`` [b, s, inner + wide + h], whose ``z`` columns the
    pass after the kernels wrote (a ``jit`` of its own, as the kernels'
    forms are)."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    f32 = jnp.float32
    backward = cots is not None
    b, s, _ = zx.shape
    tile, inner, bc, wide, h = (plan.tile, plan.inner, plan.bc, plan.wide,
                                plan.heads)
    steps, hb = h // plan.step_heads, plan.step_heads
    n = s // tile
    at = (lambda t: n - 1 - t) if backward else (lambda t: t)
    per = tile // _ROWS

    def rows(width, col=0):
        return pl.BlockSpec((None, tile, width),
                            lambda i, t: (i, at(t), col))

    def halo(width, col):
        return pl.BlockSpec(
            (None, _ROWS, width),
            lambda i, t: (i, jnp.maximum(at(t) * per - 1, 0), col))

    def whole(a):
        return pl.BlockSpec(a.shape, lambda i, t: (0,) * a.ndim)

    by_step = pl.BlockSpec((None, steps, tile, hb),
                           lambda i, t: (i, 0, at(t), 0))
    # the parts' column blocks: x at ``inner``, B and C after it, dt last
    cols = ((inner, 1), (bc, 2 * inner // bc), (bc, 2 * inner // bc + 1))
    operands = [zx] * 7
    in_specs = ([rows(w, c) for w, c in cols] + [halo(w, c) for w, c in cols]
                + [rows(h, (inner + wide) // h)])
    masked = keep is not None
    if masked:
        operands += [keep, keep]
        in_specs += [rows(1), halo(1, 0)]
    params = (conv_w.astype(f32), conv_b.astype(f32).reshape(1, wide),
              -jnp.exp(a_log.astype(f32)).reshape(1, h),
              dt_bias.astype(f32).reshape(1, h))
    operands += list(params)
    in_specs += [whole(a) for a in params]
    if backward:
        dx, dbm, dcm, ddt, dcs = cots
        operands += [dx, dbm, dcm, ddt, dcs, buf]
        in_specs += [rows(inner), rows(bc), rows(bc), by_step, by_step,
                     pl.BlockSpec(memory_space=pl.ANY)]
        # the xBC and dt columns of the product's cotangent, one block a
        # tile (element offsets: ``inner`` is no multiple of their width)
        out_specs = pl.BlockSpec(
            (pl.Element(1), pl.Element(tile), pl.Element(wide + h)),
            lambda i, t: (i, at(t) * tile, inner))
        out_shape = jax.ShapeDtypeStruct(buf.shape, buf.dtype)
        aliases = {len(operands) - 1: 0}
        scratch = [pltpu.VMEM((_HALO, wide), f32)]
    else:
        out_specs = [rows(inner), rows(bc), rows(bc), by_step, by_step]
        small = jax.ShapeDtypeStruct((b, steps, s, hb), f32)
        out_shape = [jax.ShapeDtypeStruct((b, s, w), zx.dtype)
                     for w in (inner, bc, bc)] + [small, small]
        aliases, scratch = {}, []
    out = pl.pallas_call(
        functools.partial(_pre_kernel, plan=plan, masked=masked,
                          backward=backward),
        grid=(b, n), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        input_output_aliases=aliases, interpret=plan.interpret,
        compiler_params=kernels.tpu_compiler_params(
            ("parallel", "arbitrary" if backward else "parallel")),
        name=SSM_PASS_NAMES[1 if backward else 0],
    )(*operands)
    return out if backward else tuple(out)


@functools.partial(jax.jit, static_argnums=0)
def _post_pallas(plan: _Plan, y, zx, scale, du=None):
    """Forward: -> u. With ``du`` the pull-back -> (dy, a new [b, s, inner
    + wide + h] array whose ``z`` columns hold the gate's cotangent; the
    pass before the kernels writes the rest)."""
    import jax.experimental.pallas as pl

    backward = du is not None
    b, s, inner = y.shape
    tile, cols = plan.post_tile, plan.post_cols
    block = pl.BlockSpec((None, tile, cols), lambda i, t, j: (i, t, j))
    operands = [y, zx, scale.reshape(1, inner)]
    in_specs = [block, block, pl.BlockSpec((1, cols), lambda i, t, j: (0, j))]
    if backward:
        operands.append(du)
        in_specs.append(block)
        out_specs = [block, pl.BlockSpec(
            (pl.Element(1), pl.Element(tile), pl.Element(cols)),
            lambda i, t, j: (i, t * tile, j * cols))]
        out_shape = [jax.ShapeDtypeStruct(y.shape, y.dtype),
                     jax.ShapeDtypeStruct(zx.shape, zx.dtype)]
    else:
        out_specs, out_shape = block, jax.ShapeDtypeStruct(y.shape, y.dtype)
    return pl.pallas_call(
        functools.partial(_post_kernel, plan=plan, backward=backward),
        grid=(b, s // tile, inner // cols), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape, interpret=plan.interpret,
        compiler_params=kernels.tpu_compiler_params(
            ("parallel", "parallel", "parallel")),
        name=SSM_PASS_NAMES[3 if backward else 2],
    )(*operands)


# ------------------------------------------------- the layer around them ---

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ssm(plan: _Plan, zx, keep, conv_w, conv_b, a_log, skip, dt_bias,
         scale):
    return _ssm_fwd(plan, zx, keep, conv_w, conv_b, a_log, skip, dt_bias,
                    scale)[0]


def _ssm_fwd(plan, zx, keep, conv_w, conv_b, a_log, skip, dt_bias, scale):
    x, bm, cm, dt, cs = _pre_pallas(plan, zx, keep, conv_w, conv_b, a_log,
                                    dt_bias)
    d = jnp.repeat(skip.astype(jnp.float32), plan.head_dim)[None, :]
    y, ssd_res = _ssd_chunks_fwd(x, dt, cs, bm, cm, d, plan.chunk,
                                 plan.groups, plan.head_dim, "flash")
    u = _post_pallas(plan, y, zx, scale)
    return u, (zx, keep, conv_w, conv_b, a_log, skip, dt_bias, scale, y,
               ssd_res)


def _ssm_bwd(plan, res, du):
    zx, keep, conv_w, conv_b, a_log, skip, dt_bias, scale, y, ssd_res = res
    dy, buf = _post_pallas(plan, y, zx, scale, du)
    dx, ddt, dcs, dbm, dcm, dd = _ssd_chunks_bwd(
        plan.chunk, plan.groups, plan.head_dim, "flash", ssd_res, dy)
    cots = (dx, dbm, dcm, ddt, dcs)
    dzx = _pre_pallas(plan, zx, keep, conv_w, conv_b, a_log, dt_bias, cots,
                      buf)
    d_keep, d_w, d_b, d_a, d_dtb, d_scale = _frozen_grads(
        plan, zx, keep, conv_w, conv_b, a_log, dt_bias, scale, y, cots, du)
    d_skip = dd.reshape(-1, plan.head_dim).sum(1).astype(skip.dtype)
    return dzx, d_keep, d_w, d_b, d_a, d_skip, d_dtb, d_scale


@functools.partial(jax.jit, static_argnums=0)
def _frozen_grads(plan, zx, keep, conv_w, conv_b, a_log, dt_bias, scale, y,
                  cots, du):
    """The mask's and the frozen parameters' cotangents: autodiff of the
    module's form, which XLA drops where nobody asks (a LoRA step)."""
    _, pull = jax.vjp(lambda *a: _pre_dense(plan, zx, *a), keep, conv_w,
                      conv_b, a_log, dt_bias)
    _, pull_post = jax.vjp(lambda sc: _post_dense(plan, y, zx, sc), scale)
    return (*pull(cots), *pull_post(du))


_ssm.defvjp(_ssm_fwd, _ssm_bwd)


def ssm_layer(zxbcdt, attn_mask, conv_w, conv_b, a_log, skip, dt_bias,
              scale, *, heads: int, head_dim: int, groups: int, state: int,
              chunk: int = CHUNK, eps: float = 1e-5, fused: bool = True):
    """A Mamba-2 layer between its frozen products, on the kernels' path:
    ``zxbcdt`` the ``in_proj`` product [b, s, inner + wide + h] as
    ``Mamba2`` lays it out (``z``, then ``xBC`` = x, B, C, then ``dt``),
    ``attn_mask`` [b, s] or None, ``conv_w`` [K, wide] and ``conv_b``
    [wide] or None, ``A_log``, ``D`` and ``dt_bias`` [h], ``scale`` [inner]
    the gated norm's -> what ``out_proj`` reads, [b, s, inner]. One
    ``custom_vjp`` keeps the product, the kernels' operands and entering
    states and their output: no intermediate. ``fused`` False runs XLA's
    form of the same passes around the same kernels, under autodiff (the
    reference of ``chip_smoke.py`` and the tests)."""
    b, s, _ = zxbcdt.shape
    _, hb = _kernel_plan(heads, groups, head_dim, state, "flash")
    inner, bc = heads * head_dim, groups * state
    if 2 * inner % bc or (2 * inner + 2 * bc) % heads:
        raise ValueError(
            f"the passes read B, C and dt as column blocks of the product: "
            f"B's {bc} columns must divide 2 x {inner} and the heads "
            f"{heads} the {2 * inner + 2 * bc} columns before dt")
    chunk = chunk_size(s, chunk)
    keep = None if attn_mask is None else attn_mask.astype(
        jnp.float32)[:, :, None]
    pad = -s % chunk
    if pad:     # zeros after the row: no decay, no write, read by nobody
        keep = jnp.ones((b, s, 1), jnp.float32) if keep is None else keep
        zxbcdt, keep = (jnp.pad(a, [(0, 0), (0, pad), (0, 0)])
                        for a in (zxbcdt, keep))
    sp = s + pad
    plan = _Plan(heads, head_dim, groups, state, chunk, hb,
                 _divisor(sp, chunk, max(_PRE_ROWS, chunk)),
                 _divisor(sp, chunk, max(_POST_ROWS, chunk)),
                 _divisor(inner, inner // groups,
                          max(_POST_COLS, inner // groups)),
                 float(eps), kernels.interpret())
    if conv_b is None:
        conv_b = jnp.zeros((conv_w.shape[1],), jnp.float32)
    if fused:
        u = _ssm(plan, zxbcdt, keep, conv_w, conv_b, a_log, skip, dt_bias,
                 scale)
    else:
        x, bm, cm, dt, cs = _pre_dense(plan, zxbcdt, keep, conv_w, conv_b,
                                       a_log, dt_bias)
        y = _ssd_chunks(x, dt, cs, bm, cm, jnp.repeat(
            skip.astype(jnp.float32), head_dim)[None, :], chunk, groups,
            head_dim, "flash")
        u = _post_dense(plan, y, zxbcdt, scale)
    return u[:, :s] if pad else u


def ssd_recurrence(x, dt, a, bm, cm, d):
    """The same map as :func:`ssd_scan`, token by token in float32: the
    definition the chunked forms are tested against (never the timed
    path)."""
    f32 = jnp.float32
    x, dt, bm, cm = (t.astype(f32) for t in (x, dt, bm, cm))
    b, _, h, p = x.shape
    rep = h // bm.shape[2]

    def step(st, ins):
        x_t, dt_t, b_t, c_t = ins                     # [b, h, .]
        b_t, c_t = jnp.repeat(b_t, rep, 1), jnp.repeat(c_t, rep, 1)
        st = (st * jnp.exp(dt_t * a)[..., None, None]
              + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return st, jnp.sum(st * c_t[..., None, :], -1) + d[:, None] * x_t

    st0 = jnp.zeros((b, h, p, bm.shape[-1]), f32)
    _, y = jax.lax.scan(step, st0, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, bm, cm)))
    return jnp.moveaxis(y, 0, 1)
