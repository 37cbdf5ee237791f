"""Learned sparse attention: the lightning indexer's exact top-k selection
and softmax attention over the selected keys alone (DeepSeek-V3.2-Exp's
sparse attention, here over grouped-query heads).

For a row of ``s`` positions, index heads ``qi`` [b, s, H, D], one index
key a position ``ki`` [b, s, D] and head weights ``w`` [b, s, H]
(float32):

    I[t, u] = sum_j w[t, j] * relu(qi[t, j] . ki[u])        for u <= t

and query ``t`` keeps ``S_t``: the ``min(topk, t + 1)`` positions of the
largest ``I[t, .]``, exactly; among equal scores the earlier position goes
first (a score of -0 is 0). Every query head of the layer attends to
``S_t`` alone: ``o_t = sum_{u in S_t} softmax_u(q_t . k_u * scale) v_u``.

The selection is a bit mask, one bit a (query, key): ``words[b, t, c *
128 + l]`` holds in bit ``j`` the key ``c * 4096 + j * 128 + l``. In this
layout the 128 keys of one bit lie along a word block's lanes, so a
kernel's key block of 128 is one shift of a ``[rows, 128]`` block. At
16,384 positions the mask is 32 MiB a row of the batch.

Two implementations (``impl``):

- ``dense``: jax.numpy, the golden: every index score, ``lax.top_k`` for
  each row's threshold, the ties cut by position.
- ``flash``: Pallas. ``sparse_index`` computes a q block's index scores
  against every causal key into VMEM as int32 keys that sort as the
  scores do, finds each row's ``min(topk, t + 1)``-th largest by a
  bisection on the key's 32 bits (a count of the keys at or above a
  candidate a step), cuts ties by a bisection on the position where a
  row holds more equal scores than it keeps, and writes the row's bits.
  ``sparse_fwd``, ``sparse_dq`` and ``sparse_dkv`` are the flash kernels'
  online softmax over the causal blocks with the bits as the mask, a grid
  step one key-value head with its group of query heads (the group shares
  the selection, so a block's bits are unpacked once for all of them).
  A block is computed whole when any of its scores is selected: with
  2,048 of up to 16,384 keys a query spread over the row, that is every
  causal block, some four times the selected work (the gauge
  ``fed_sparse_computed_over_selected``). Gathering the selected keys
  instead would read each query's 2,048 keys and values at every
  key-value head: 4 MiB a query at the published widths, some 80 ms a
  layer forward at the v5e's bandwidth, against 11 ms of compute for the
  whole causal half at its peak.

No gradient reaches the indexer: the selection is discrete, and its inputs
are constants of the backward pass.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..core import kernels
from ..core.obs import metrics as obs_metrics
from .attention import (NEG_INF, _LN2, _LOG2E, _NT, _across, _dkv_out,
                        _dkv_step, _dq_step, _fit_block, _grouped,
                        _heads_first, _scaled, _softmax_step)

# the kernels' names in a device trace (index and selection, forward, dQ,
# dK/dV); none holds ``flash_``
SPARSE_KERNEL_NAMES = ("sparse_index", "sparse_fwd", "sparse_dq",
                       "sparse_dkv")
LANES, BITS = 128, 32
SPAN = LANES * BITS         # the keys one lane's 32 bits cover
BLOCK = 128                 # a kernel tile's queries and keys
DKV_SPAN = 1024             # the query rows a dK/dV grid step reads
_INT_MIN = -2 ** 31


def word_count(s_pad: int) -> int:
    """Words a query row of the mask has for ``s_pad`` keys."""
    return LANES * -(-s_pad // SPAN)


def padded(s: int) -> int:
    return -(-s // BLOCK) * BLOCK


# ---------------------------------------------------------------- dense ---

def index_scores(qi, ki, w):
    """``I`` [b, s, s] float32 over every pair (the caller masks), the
    heads summed in order as the kernel sums them, one at a time."""
    w = w.astype(jnp.float32)
    out = 0.0
    for j in range(qi.shape[2]):
        dots = jnp.einsum("btd,bud->btu", qi[:, :, j], ki,
                          preferred_element_type=jnp.float32)
        out = out + w[:, :, j, None] * jnp.maximum(dots, 0.0)
    return out


def select_dense(scores, topk: int):
    """bool [b, s, s]: each row's ``min(topk, t + 1)`` largest causal
    scores, the earlier position first among equal ones."""
    b, s, _ = scores.shape
    t = jnp.arange(s)
    causal = t[None, :] <= t[:, None]
    score = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    kk = jnp.minimum(topk, t + 1)
    top = jax.lax.top_k(score, min(topk, s))[0]
    tau = jnp.take_along_axis(
        top, jnp.broadcast_to((kk - 1)[None, :, None], (b, s, 1)), -1)
    above = score > tau
    tie = score == tau
    need = kk[None, :, None] - jnp.sum(above, -1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, -1) <= need))


def pack(keep):
    """bool [b, s, n] -> int32 words [b, s, word_count(n)]."""
    b, s, n = keep.shape
    chunks = -(-n // SPAN)
    keep = jnp.pad(keep, ((0, 0), (0, 0), (0, chunks * SPAN - n)))
    bits = (keep.reshape(b, s, chunks, BITS, LANES).astype(jnp.int32)
            << jnp.arange(BITS, dtype=jnp.int32)[:, None])
    # the bits are disjoint: their sum is their union
    return jnp.sum(bits, axis=3).reshape(b, s, chunks * LANES)


def unpack(words, n: int):
    """int32 words [b, s, W] -> bool [b, s, n]."""
    b, s, w = words.shape
    bits = (words.reshape(b, s, w // LANES, 1, LANES)
            >> jnp.arange(BITS, dtype=jnp.int32)[:, None]) & 1
    return bits.reshape(b, s, (w // LANES) * SPAN)[..., :n] > 0


def _dense_attend(q, k, v, keep, scale):
    h, h_kv = q.shape[2], k.shape[2]
    k, v = (jnp.repeat(a, h // h_kv, axis=2) for a in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    scores = jnp.where(keep[:, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ------------------------------------------------------ index kernel ---

def _sortable(x):
    """float32 -> int32 in the same order (-0 taken as 0)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0.0, 0.0, x),
                                        jnp.int32)
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _index_kernel(qi_ref, ki_ref, w_ref, words_ref, keys_ref, *, topk: int,
                  s_pad: int):
    """One (batch row, q block): the block's index scores against every
    causal key as sortable keys in ``keys_ref`` [BLOCK, s_pad] (INT_MIN
    past a row's own position), each row's threshold and tie cut, and the
    row's bits into ``words_ref`` [BLOCK, W].

    qi_ref [H, BLOCK, D]; ki_ref [s_pad, D]; w_ref [H, BLOCK, 1]."""
    import jax.experimental.pallas as pl

    heads = qi_ref.shape[0]
    i = pl.program_id(1)
    row = i * BLOCK + jax.lax.broadcasted_iota(jnp.int32, (BLOCK, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    n_keys = (i + 1) * BLOCK
    int_min = jnp.int32(_INT_MIN)

    def scores(c, _):
        start = pl.multiple_of(c * BLOCK, BLOCK)
        k_blk = ki_ref[pl.ds(start, BLOCK), :]
        acc = jnp.zeros((BLOCK, BLOCK), jnp.float32)
        for j in range(heads):
            dots = jax.lax.dot_general(qi_ref[j], k_blk, _NT,
                                       preferred_element_type=jnp.float32)
            acc = acc + w_ref[j] * jnp.maximum(dots, 0.0)
        keys_ref[:, pl.ds(start, BLOCK)] = jnp.where(
            start + lane <= row, _sortable(acc), int_min)

    jax.lax.fori_loop(0, n_keys // BLOCK, scores, None)
    kk = jnp.minimum(topk, row + 1).astype(jnp.float32)

    def count(pred):
        """[BLOCK, 1] float32: the row's computed keys where ``pred(keys,
        columns)`` holds (exact: a row has under 2^24 keys)."""
        def body(c, acc):
            start = pl.multiple_of(c * LANES, LANES)
            blk = keys_ref[:, pl.ds(start, LANES)]
            return acc + pred(blk, start + lane).astype(jnp.float32)
        acc = jax.lax.fori_loop(0, n_keys // LANES, body,
                                jnp.zeros((BLOCK, LANES), jnp.float32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def select():
        def bit(j, prefix):
            # the largest key value with kk keys at or above it, built from
            # its top bit down, in the order of the unsigned bits
            cand = prefix | jax.lax.shift_left(jnp.int32(1), 31 - j)
            ok = count(lambda blk, col: blk >= (cand ^ int_min)) >= kk
            return jnp.where(ok, cand, prefix)

        tau = jax.lax.fori_loop(0, BITS, bit,
                                jnp.zeros((BLOCK, 1), jnp.int32)) ^ int_min
        need = kk - count(lambda blk, col: blk > tau)
        cut = count(lambda blk, col: blk >= tau) > kk
        n_bits = max(1, (s_pad - 1).bit_length())

        def last_tie():
            """The largest position with fewer than ``need`` ties before
            it: the row keeps its ties up to and with it."""
            def step(j, prefix):
                cand = prefix | jax.lax.shift_left(jnp.int32(1),
                                                   n_bits - 1 - j)
                ok = count(lambda blk, col: (blk == tau) & (col < cand)
                           ) < need
                return jnp.where(ok, cand, prefix)
            return jax.lax.fori_loop(0, n_bits, step,
                                     jnp.zeros((BLOCK, 1), jnp.int32))

        last = jax.lax.cond(jnp.max(cut.astype(jnp.float32)) > 0, last_tie,
                            lambda: jnp.full((BLOCK, 1), s_pad, jnp.int32))
        return tau, jnp.where(cut, last, s_pad)

    def keep_all():
        return (jnp.full((BLOCK, 1), _INT_MIN, jnp.int32),
                jnp.full((BLOCK, 1), s_pad, jnp.int32))

    tau, last = jax.lax.cond(n_keys <= topk, keep_all, select)
    for c in range(words_ref.shape[1] // LANES):
        word = jnp.zeros((BLOCK, LANES), jnp.int32)
        for j in range(BITS):
            start = c * SPAN + j * LANES
            if start >= s_pad:
                break
            blk = keys_ref[:, start:start + LANES]
            col = start + lane
            keep = (((blk > tau) | ((blk == tau) & (col <= last)))
                    & (col <= row))
            word = word | (keep.astype(jnp.int32) << j)
        words_ref[:, c * LANES:(c + 1) * LANES] = word


def _index_call(qi, ki, w, topk: int):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s_pad, heads, d = qi.shape
    width = word_count(s_pad)
    return pl.pallas_call(
        functools.partial(_index_kernel, topk=topk, s_pad=s_pad),
        grid=(b, s_pad // BLOCK),
        in_specs=[
            pl.BlockSpec((None, heads, BLOCK, d), lambda i, j: (i, 0, j, 0)),
            pl.BlockSpec((None, s_pad, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, heads, BLOCK, 1), lambda i, j: (i, 0, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, BLOCK, width), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, s_pad, width), jnp.int32),
        scratch_shapes=[pltpu.VMEM((BLOCK, s_pad), jnp.int32)],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=SPARSE_KERNEL_NAMES[0],
    )(qi.transpose(0, 2, 1, 3), ki,
      w.astype(jnp.float32).transpose(0, 2, 1)[..., None])


def select_keys(qi, ki, w, topk: int, impl: str = "dense"):
    """The selection of every query of the row: int32 words [b, s_pad,
    word_count(s_pad)], ``s_pad`` the row padded to 128 (a padded key lies
    past every real query and is never kept by one). ``qi`` [b, s, H, D],
    ``ki`` [b, s, D], ``w`` [b, s, H]; no gradient flows through."""
    qi, ki, w = (jax.lax.stop_gradient(a) for a in (qi, ki, w))
    s = qi.shape[1]
    pad = padded(s) - s
    if pad:
        qi = jnp.pad(qi, [(0, 0), (0, pad), (0, 0), (0, 0)])
        ki = jnp.pad(ki, [(0, 0), (0, pad), (0, 0)])
        w = jnp.pad(w, [(0, 0), (0, pad), (0, 0)])
    if impl == "flash":
        return _index_call(qi, ki, w, int(topk))
    return pack(select_dense(index_scores(qi, ki, w), int(topk)))


# -------------------------------------------------- attention kernels ---

def _live(words_ref, rows, start):
    """bool [rows, BLOCK]: the bits of keys ``[start, start + BLOCK)``."""
    import jax.experimental.pallas as pl

    chunk = pl.multiple_of((start // SPAN) * LANES, LANES)
    word = words_ref[rows, pl.ds(chunk, LANES)]
    return ((word >> ((start % SPAN) // LANES)) & 1) != 0


def _fwd_kernel(q_ref, k_ref, v_ref, words_ref, o_ref, lse_ref, qs_ref,
                acc_ref, m_ref, l_ref, *, scale: float):
    """One (batch * key-value head, q block): the group's query heads'
    online softmax over the causal key blocks, each score outside the
    selection masked. q_ref [g, BLOCK, d]; k_ref, v_ref [s, d]; words_ref
    [BLOCK, W]; o_ref [g, BLOCK, d_v]; lse_ref [g, BLOCK, 1]."""
    import jax.experimental.pallas as pl

    heads = q_ref.shape[0]
    d_v = acc_ref.shape[-1]
    for a in range(heads):
        qs_ref[a] = _scaled(q_ref[a], scale * _LOG2E)
        acc_ref[a] = jnp.zeros(acc_ref.shape[1:], jnp.float32)
        m_ref[a] = jnp.full(m_ref.shape[1:], NEG_INF, jnp.float32)
        l_ref[a] = jnp.zeros(l_ref.shape[1:], jnp.float32)

    def fold(c, _):
        start = pl.multiple_of(c * BLOCK, BLOCK)
        keys = pl.ds(start, BLOCK)
        k_blk, v_blk = k_ref[keys, :], v_ref[keys, :]
        live = _live(words_ref, slice(None), start)
        for a in range(heads):
            s_blk = jax.lax.dot_general(qs_ref[a], k_blk, _NT,
                                        preferred_element_type=jnp.float32)
            _softmax_step(jnp.where(live, s_blk, NEG_INF), live, v_blk,
                          m_ref, l_ref, acc_ref, a)

    jax.lax.fori_loop(0, pl.program_id(1) + 1, fold, None)
    for a in range(heads):
        l = jnp.maximum(l_ref[a], 1e-30)
        o_ref[a] = (acc_ref[a] * _across(1.0 / l, d_v)).astype(o_ref.dtype)
        lse_ref[a] = ((m_ref[a] + jnp.log2(l)) * _LN2)[:, :1]


def _dq_kernel(q_ref, k_ref, v_ref, words_ref, do_ref, lse_ref, dd_ref,
               dq_ref, qs_ref, acc_ref, *, scale: float):
    """dQ for one q block of the group's query heads: dS = P (dP - D), dQ
    = scale dS K over the causal key blocks, P 0 outside the selection."""
    import jax.experimental.pallas as pl

    heads = q_ref.shape[0]
    for a in range(heads):
        qs_ref[a] = _scaled(q_ref[a], scale * _LOG2E)
        acc_ref[a] = jnp.zeros(acc_ref.shape[1:], jnp.float32)

    def fold(c, _):
        start = pl.multiple_of(c * BLOCK, BLOCK)
        keys = pl.ds(start, BLOCK)
        k_blk, v_blk = k_ref[keys, :], v_ref[keys, :]
        live = _live(words_ref, slice(None), start)
        for a in range(heads):
            s_blk = jax.lax.dot_general(qs_ref[a], k_blk, _NT,
                                        preferred_element_type=jnp.float32)
            p = jnp.where(live, jnp.exp2(s_blk - lse_ref[a] * _LOG2E), 0.0)
            _dq_step(p, do_ref[a], dd_ref[a], k_blk, v_blk, acc_ref, a)

    jax.lax.fori_loop(0, pl.program_id(1) + 1, fold, None)
    for a in range(heads):
        dq_ref[a] = (acc_ref[a] * scale).astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, words_ref, do_ref, lse_ref, dd_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float, span: int):
    """dK/dV for one key block of one key-value head, a grid step a span
    of ``span`` query rows (the spans before the block's first key are
    skipped and read nothing new): the group's heads in the transposed
    orientation, as the flash kernels have it, into the same float32
    scratch. q_ref, do_ref [g, span, d]; words_ref [span, 128] (the lanes
    of the block's keys); lse_ref, dd_ref [g, 1, span]."""
    import jax.experimental.pallas as pl

    heads = q_ref.shape[0]
    j, c = pl.program_id(1), pl.program_id(2)
    first = j * BLOCK
    k, v = k_ref[:], v_ref[:]

    @pl.when(c == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def fold(r, _):
        at = pl.multiple_of(r * BLOCK, BLOCK)
        rows = pl.ds(at, BLOCK)
        word = words_ref[rows, :]
        live = ((word >> ((first % SPAN) // LANES)) & 1).astype(jnp.float32)
        live_t = jnp.transpose(live) > 0                # [keys, queries]
        for a in range(heads):
            q_blk = _scaled(q_ref[a, rows, :], scale * _LOG2E)
            s_t = jax.lax.dot_general(k, q_blk, _NT,
                                      preferred_element_type=jnp.float32)
            p_t = jnp.where(live_t, jnp.exp2(
                s_t - lse_ref[a, :, rows] * _LOG2E), 0.0)
            _dkv_step(p_t, q_blk, do_ref[a, rows, :], v, dd_ref,
                      (a, slice(None), rows), dk_acc, dv_acc)

    @pl.when((c + 1) * span > first)
    def _():
        jax.lax.fori_loop(jnp.maximum(first - c * span, 0) // BLOCK,
                          span // BLOCK, fold, None)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        _dkv_out(dk_ref, dv_ref, dk_acc, dv_acc)


def _attend_fwd(q, k, v, words, scale: float):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s, h, d = q.shape
    h_kv, dv = k.shape[2], v.shape[-1]
    g, width = h // h_kv, words.shape[-1]
    group = lambda w: pl.BlockSpec(  # noqa: E731
        (None, g, BLOCK, w), lambda i, j: (i, 0, j, 0))
    row = lambda w: pl.BlockSpec(  # noqa: E731
        (None, s, w), lambda i, j: (i, 0, 0))
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(b * h_kv, s // BLOCK),
        in_specs=[group(d), row(d), row(dv),
                  pl.BlockSpec((None, BLOCK, width),
                               lambda i, j: (i // h_kv, j, 0))],
        out_specs=[group(dv), group(1)],
        out_shape=[jax.ShapeDtypeStruct((b * h_kv, g, s, dv), q.dtype),
                   jax.ShapeDtypeStruct((b * h_kv, g, s, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((g, BLOCK, d), q.dtype),
                        pltpu.VMEM((g, BLOCK, dv), jnp.float32),
                        pltpu.VMEM((g, BLOCK, 128), jnp.float32),
                        pltpu.VMEM((g, BLOCK, 128), jnp.float32)],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=SPARSE_KERNEL_NAMES[1],
    )(_grouped(q, h_kv), _heads_first(k), _heads_first(v), words)
    return out.reshape(b, h, s, dv).transpose(0, 2, 1, 3), lse


def _attend_bwd(q, k, v, words, o, lse, g_, scale: float):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s, h, d = q.shape
    h_kv, dv = k.shape[2], v.shape[-1]
    g, width = h // h_kv, words.shape[-1]
    qf, kf, vf = _grouped(q, h_kv), _heads_first(k), _heads_first(v)
    gf = _grouped(g_, h_kv)
    # D_i = sum_d dO_i O_i, one element-wise pass in XLA
    dd = jnp.sum(gf.astype(jnp.float32)
                 * _grouped(o, h_kv).astype(jnp.float32),
                 axis=-1, keepdims=True)
    group = lambda w: pl.BlockSpec(  # noqa: E731
        (None, g, BLOCK, w), lambda i, j: (i, 0, j, 0))
    row = lambda w: pl.BlockSpec(  # noqa: E731
        (None, s, w), lambda i, j: (i, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale),
        grid=(b * h_kv, s // BLOCK),
        in_specs=[group(d), row(d), row(dv),
                  pl.BlockSpec((None, BLOCK, width),
                               lambda i, j: (i // h_kv, j, 0)),
                  group(dv), group(1), group(1)],
        out_specs=group(d),
        out_shape=jax.ShapeDtypeStruct((b * h_kv, g, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, BLOCK, d), q.dtype),
                        pltpu.VMEM((g, BLOCK, d), jnp.float32)],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=SPARSE_KERNEL_NAMES[2],
    )(qf, kf, vf, words, gf, lse, dd)

    span = _fit_block(s, DKV_SPAN)

    def at(j, c):
        """The first span that sees key block ``j``, where ``c`` lies
        before it (its step computes nothing and fetches nothing new)."""
        return jnp.maximum(c, (j * BLOCK) // span)

    q_span = lambda w: pl.BlockSpec(  # noqa: E731
        (None, g, span, w), lambda i, j, c: (i, 0, at(j, c), 0))
    stat = pl.BlockSpec((None, g, 1, span),
                        lambda i, j, c: (i, 0, 0, at(j, c)))
    key = lambda w: pl.BlockSpec(  # noqa: E731
        (None, BLOCK, w), lambda i, j, c: (i, j, 0))
    dk, dv_ = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, span=span),
        grid=(b * h_kv, s // BLOCK, s // span),
        in_specs=[key(d), key(dv), q_span(d),
                  pl.BlockSpec((None, span, LANES),
                               lambda i, j, c: (i // h_kv, at(j, c),
                                                (j * BLOCK) // SPAN)),
                  q_span(dv), stat, stat],
        out_specs=[key(d), key(dv)],
        out_shape=[jax.ShapeDtypeStruct((b * h_kv, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h_kv, s, dv), q.dtype)],
        scratch_shapes=[pltpu.VMEM((BLOCK, d), jnp.float32),
                        pltpu.VMEM((BLOCK, dv), jnp.float32)],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(
            ("parallel", "parallel", "arbitrary")),
        name=SPARSE_KERNEL_NAMES[3],
    )(kf, vf, qf, words, gf, lse.reshape(b * h_kv, g, 1, s),
      dd.reshape(b * h_kv, g, 1, s))
    unflat = lambda a, n: a.reshape(b, n, s, -1).transpose(0, 2, 1, 3)  # noqa
    return unflat(dq, h), unflat(dk, h_kv), unflat(dv_, h_kv)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _attend(q, k, v, words, scale: float):
    return _attend_fwd(q, k, v, words, scale)[0]


def _attend_fwd_rule(q, k, v, words, scale):
    out, lse = _attend_fwd(q, k, v, words, scale)
    return out, (q, k, v, words, out, lse)


def _attend_bwd_rule(scale, res, g):
    q, k, v, words, out, lse = res
    return (*_attend_bwd(q, k, v, words, out, lse, g, scale), None)


_attend.defvjp(_attend_fwd_rule, _attend_bwd_rule)


def selected(s: int, topk: int) -> int:
    """The (query, key) pairs a row's selection keeps: ``min(topk, t +
    1)`` a query."""
    kept = min(topk, s)
    return kept * (kept + 1) // 2 + (s - kept) * kept


def computed_over_selected(s: int, topk: int) -> float:
    """The score positions the attention kernels compute a head (every
    causal block of BLOCK x BLOCK of the padded row) over those the
    selection keeps."""
    n = padded(s) // BLOCK
    return n * (n + 1) // 2 * BLOCK * BLOCK / selected(s, topk)


def sparse_attend(q, k, v, words, topk: int, impl: str = "dense",
                  scale=None):
    """Softmax attention of every query over its selected keys alone. q [b,
    s, h, d], k, v [b, s, h_kv, d] (query head ``i`` reads key-value head
    ``i // (h / h_kv)``), ``words`` from :func:`select_keys` of the same
    row; ``scale`` multiplies the scores (default ``d ** -0.5``). ``topk``
    says what the selection keeps, for the gauge the ``flash`` path sets
    when it is traced."""
    b, s, h, d = q.shape
    if h % k.shape[2] or v.shape[2] != k.shape[2]:
        raise ValueError(f"{h} query heads on {k.shape[2]} key and "
                         f"{v.shape[2]} value heads")
    scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
    if impl != "flash":
        return _dense_attend(q, k, v, unpack(words[:, :s], s), scale)
    obs_metrics.record_sparse_plan(computed_over_selected(s, int(topk)))
    pad = padded(s) - s
    if pad:
        q, k, v = (jnp.pad(a, [(0, 0), (0, pad), (0, 0), (0, 0)])
                   for a in (q, k, v))
    out = _attend(q, k, v, words, scale)
    return out[:, :s] if pad else out
