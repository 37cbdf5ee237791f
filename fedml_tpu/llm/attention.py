"""Attention implementations for the LLM path.

The reference's only long-context machinery is a CUDA flash-attn
monkey-patch (``train/llm/models/attention.py:30``). The TPU-native
counterparts here are first-class:

- ``dense``: plain causal attention — XLA fuses this well for short
  sequences; the numerical golden for the other two.
- ``flash``: Pallas online-softmax kernels for BOTH directions — the
  forward emits O and the per-query logsumexp; the backward recomputes
  probabilities blockwise from (Q, K, LSE) in two kernels (dQ; dK/dV), so
  the [s, s] score matrix never materializes in HBM in either direction
  and training memory is O(s·d + s·block). Key-padding masks are
  supported. This is the fwd+bwd fused flash-attn the reference gets from
  its CUDA monkey-patch (``train/llm/models/attention.py:30-67``), built
  for the MXU. What a score block costs beyond its products is kept
  small (PR 31): one plan (:func:`flash_block_plan`) tells every kernel
  which blocks lie wholly under the diagonal, and those run no compare
  and no select; a call without a key mask (none passed, s on the 128
  grid) carries no mask operand at all, and one with a mask makes ONE
  compare of positions serve for both masks; dK/dV works on the
  transposed scores, so nothing score-sized is transposed; bf16 operands
  go to the MXU as they lie, statistics and accumulators stay float32 in
  VMEM scratch; the exponentials are base 2 (the scale carries log2 e).
  A sliding ``window`` (a query sees its last ``window`` keys, itself
  among them) gives the plan a first live block as well as a last one, so
  the kernels compute the band's blocks alone, under names of their own
  (``flash_win_*``), a grid step one key-value head and its group of query
  heads; a learned ``sink`` (one logit a query head that takes
  softmax mass and carries no value) is where the forward's running
  maximum and sum start, so the stored logsumexp carries it and the
  backward kernels need nothing new.
- ``ring``: ring attention over the ``sp`` mesh axis — sequence shards
  rotate K/V (and the key-padding mask) via ``ppermute`` while
  accumulating online-softmax state, so context length scales with the
  number of chips (capability beyond the reference; SURVEY §5.7 flags
  this as the TPU equivalent to build).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import kernels
from ..core.obs import metrics as obs_metrics

NEG_INF = -1e30
# the three kernels' names in a device trace (forward, dQ, dK/dV)
FLASH_KERNEL_NAMES = ("flash_fwd", "flash_dq", "flash_dkv")
# the same three of a call with a sliding window
WINDOW_KERNEL_NAMES = ("flash_win_fwd", "flash_win_dq", "flash_win_dkv")

# (axis_name, axis_size) for ring attention; set by the sequence-parallel
# wrapper (sharding.py) around the shard_map'd forward.
_RING_AXIS: contextvars.ContextVar[Optional[Tuple[str, int]]] = \
    contextvars.ContextVar("fedml_tpu_ring_axis", default=None)


@contextlib.contextmanager
def ring_axis(name: str, size: int):
    token = _RING_AXIS.set((name, size))
    try:
        yield
    finally:
        _RING_AXIS.reset(token)


def causal_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     impl: str = "dense",
                     attn_mask: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None,
                     window: Optional[int] = None,
                     sink: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Dispatch. q/k: [b, s, h, d_qk], v: [b, s, h, d_v] → [b, s, h, d_v].
    ``scale`` multiplies the scores (default ``d_qk ** -0.5``); dense and
    flash take ``d_qk != d_v`` (latent attention's 192/128), a sliding
    ``window`` (query i sees keys ``i - window < j <= i``) and a ``sink``
    (``[h]`` logits: ``p_ij = exp(s_ij) / (exp(sink_h) + sum_j exp(s_ij))``)."""
    if impl == "ring":
        ax = _RING_AXIS.get()
        if ax is None:
            raise RuntimeError(
                "attention_impl='ring' requires the sequence-parallel "
                "context (fedml_tpu.llm.attention.ring_axis) — wrap the "
                "forward in shard_map over the 'sp' axis")
        if (scale is not None or q.shape[-1] != v.shape[-1]
                or window is not None or sink is not None):
            raise NotImplementedError(
                "ring attention takes one head size and its default scale, "
                "and neither a sliding window nor a sink")
        return ring_causal_attention(q, k, v, axis_name=ax[0],
                                     axis_size=ax[1], attn_mask=attn_mask)
    if impl == "flash":
        return flash_causal_attention(q, k, v, attn_mask=attn_mask,
                                      scale=scale, window=window, sink=sink)
    return dense_causal_attention(q, k, v, attn_mask=attn_mask, scale=scale,
                                  window=window, sink=sink)


def dense_causal_attention(q, k, v, attn_mask=None, scale=None, window=None,
                           sink=None):
    """[b, s, h, d] — reference semantics, scores in f32. ``window``: a
    query sees its last ``window`` keys only; ``sink`` ([h] logits): one
    more column of the row's softmax that carries no value."""
    _, s, _, d = q.shape
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    if window is not None:
        causal = causal & ~jnp.tril(jnp.ones((s, s), bool), -int(window))
    mask = causal[None, None]
    if attn_mask is not None:  # [b, s] key padding
        mask = mask & attn_mask[:, None, None, :].astype(bool)
    scores = jnp.where(mask, scores, NEG_INF)
    if sink is None:
        probs = jax.nn.softmax(scores, axis=-1)
    else:
        column = jnp.broadcast_to(
            sink.astype(jnp.float32)[None, :, None, None],
            scores.shape[:3] + (1,))
        probs = jax.nn.softmax(
            jnp.concatenate([scores, column], -1), axis=-1)[..., :-1]
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def cached_attention(q, k_all, v_all, q_positions):
    """Decode/prefill attention over a position-ordered cached K/V view.

    q: [b, s, h, d] (s = 1 for decode, chunk length for prefill);
    k_all/v_all: [b, T, h, d] — the slot's gathered cache view with the
    current tokens already written at their logical positions;
    q_positions: [b, s] absolute positions of the query rows.

    The live mask is ``key_index <= q_position``: the view is position-
    ordered, every position <= q_pos holds a genuinely written key, and
    everything after is masked to NEG_INF (exact-zero probability). The
    math mirrors :func:`dense_causal_attention` term for term — f32
    scores, NEG_INF masking, softmax over a T-long key axis — so a decode
    step over a ``T == max_seq_len`` view is bit-compatible with the
    full-forward step on the padded ``[1, max_seq_len]`` buffer (masked
    positions contribute exact 0.0 in both).
    """
    _, _, _, d = q.shape
    t = k_all.shape[1]
    scale = 1.0 / math.sqrt(d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k_all.astype(jnp.float32)) * scale
    key_idx = jnp.arange(t, dtype=jnp.int32)
    live = key_idx[None, None, None, :] <= q_positions[:, None, :, None]
    scores = jnp.where(live, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v_all.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------- flash ----
# FlashAttention-2 style: the forward saves only (O, LSE); both backward
# kernels recompute P = exp(QK^T·scale − LSE) blockwise in VMEM, so neither
# direction materializes [s, s] in HBM. Key padding rides a [b, s] mask.

class FlashBlockPlan(NamedTuple):
    """Which [block_q, block_k] score blocks of the causal ``s x s`` square
    a kernel computes, and which of them the diagonal crosses. A block
    wholly under the diagonal (*interior*) needs no causal compare; one
    wholly above it is skipped. With a sliding ``window`` (query i sees
    keys ``i - window < j <= i``) a block wholly below the band is skipped
    too, and an interior block that the band's lower edge crosses (*edge*)
    compares ``i - j < window``. The bounds take a Python int or a traced
    ``program_id`` alike, for any ``block_q``, ``block_k`` that divide s."""
    n_q: int
    n_k: int
    block_q: int
    block_k: int
    window: Optional[int] = None

    def q_major(self, i):
        """``(n_full, n_live)`` for q block ``i`` (forward, dQ): kv blocks
        ``[0, n_full)`` are interior, ``[n_full, n_live)`` hold the
        diagonal, the rest see none of this q block."""
        bq, bk = self.block_q, self.block_k
        # interior: the block's last key <= its first query
        n_full = _least(self.n_k, (i * bq + 1) // bk)
        # live: the block's first key <= its last query
        n_live = _least(self.n_k, ((i + 1) * bq + bk - 1) // bk)
        return n_full, n_live

    def k_major(self, j):
        """``(j0, j_full)`` for kv block ``j`` (dK/dV): q blocks
        ``[j0, j_full)`` hold the diagonal, ``[j_full, n_q)`` are interior,
        those before ``j0`` see none of this kv block."""
        bq, bk = self.block_q, self.block_k
        j0 = (j * bk) // bq
        j_full = _least(self.n_q, ((j + 1) * bk + bq - 2) // bq)
        return j0, j_full

    def q_major_window(self, i):
        """``(n_first, n_edge, n_full, n_live)`` for q block ``i`` under
        the plan's window (none: no block skipped below, no edge): kv blocks before ``n_first`` lie wholly below
        the band and are skipped, ``[n_first, n_edge)`` are edge blocks,
        ``[n_edge, n_full)`` interior, ``[n_full, n_live)`` hold the
        diagonal (and, where the window is narrower than a block, the
        band's lower edge as well)."""
        bq, bk, w = self.block_q, self.block_k, self.window
        n_full, n_live = self.q_major(i)
        if w is None:           # no band: nothing skipped below, no edge
            return 0, 0, n_full, n_live
        # the block of the first query's first key
        n_first = _most(i * bq - w + 1, 0) // bk
        # no edge from the block on whose first key the LAST query's
        # window opens: (i + 1) * bq - 1 - first key < w
        n_edge = _least(n_full, (_most((i + 1) * bq - w, 0) + bk - 1) // bk)
        return n_first, n_edge, n_full, n_live

    def k_major_window(self, j):
        """``(j0, j_full, j_edge, j_last)`` for kv block ``j`` under the
        plan's window (none: ``j_edge = j_last = n_q``): q blocks ``[j0, j_full)`` hold the diagonal,
        ``[j_full, j_edge)`` are interior, ``[j_edge, j_last)`` edge blocks,
        those from ``j_last`` on lie wholly below the band."""
        bq, bk, w = self.block_q, self.block_k, self.window
        j0, j_full = self.k_major(j)
        if w is None:
            return j0, j_full, self.n_q, self.n_q
        # live: the block's first query - this block's last key < w
        j_last = _least(self.n_q, (w + (j + 1) * bk - 2) // bq + 1)
        # no edge while the block's last query - this block's first key < w
        j_edge = _least(j_last, _most((w + j * bk) // bq, j_full))
        return j0, j_full, j_edge, j_last

    def band_rows(self) -> int:
        """The query rows the widest kv block's band spans, in whole q
        blocks: what dK/dV of a window call holds of a query head."""
        widest = max(w[3] - w[0] for w in map(self.k_major_window,
                                              range(self.n_k)))
        return widest * self.block_q

    def band_start(self, j, rows: int):
        """The first of the ``rows`` query rows dK/dV holds for kv block
        ``j``: its first live q block's, moved back where the band would
        run past the last query."""
        # a multiple of block_q that Mosaic can see: the product last
        return _least(self.n_q - rows // self.block_q,
                      (j * self.block_k) // self.block_q) * self.block_q

    def counts(self, k_major: bool = False):
        """(interior, diagonal, skipped) blocks a head, q-major or k-major
        (the same blocks, counted along the other axis). With a window the
        edge blocks count with the diagonal ones: blocks that compare."""
        interior = diagonal = 0
        if k_major:
            for j in range(self.n_k):
                j0, j_full, j_edge, j_last = self.k_major_window(j)
                interior += j_edge - j_full
                diagonal += (j_full - j0) + (j_last - j_edge)
        else:
            for i in range(self.n_q):
                n_first, n_edge, n_full, n_live = self.q_major_window(i)
                interior += n_full - n_edge
                diagonal += (n_live - n_full) + (n_edge - n_first)
        return interior, diagonal, self.n_q * self.n_k - interior - diagonal


def _least(a, b):
    return min(a, b) if isinstance(b, int) else jnp.minimum(a, b)


def _most(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.maximum(a, b)


def flash_block_plan(s: int, block_q: int, block_k: int,
                     window: Optional[int] = None) -> FlashBlockPlan:
    """The plan all three kernels take their loop bounds from (``s`` a
    multiple of both blocks). A window that reaches every earlier key
    (``window >= s``) is no window."""
    if window is not None and window >= s:
        window = None
    return FlashBlockPlan(s // block_q, s // block_k, block_q, block_k,
                          window)


def _split_refs(refs, n_lead: int, key_mask: bool):
    """A kernel's refs: ``n_lead`` leading operands, the key mask only when
    the call has one, then the rest."""
    lead, rest = refs[:n_lead], refs[n_lead:]
    return (*lead, rest[0] if key_mask else None,
            *(rest[1:] if key_mask else rest))


# a @ b.T for the MXU: contract the last dimension of both
_NT = (((1,), (1,)), ((), ()))

# a masked key is given a position after every query, so ONE compare of
# positions is both the causal and the key mask
_NEVER = 2 ** 30

# the kernels work in base 2: the scores carry log2(e) in q's scale, so
# P = exp2(S2 - LSE2) is the exponential unit's own operation and no
# multiply an element; LSE is stored in natural units all the same
_LOG2E = 1.4426950408889634
_LN2 = 0.6931471805599453


# what a block compares: nothing, the diagonal, the window's lower edge, both
_NONE, _CAUSAL, _EDGE, _BOTH = 0, 1, 2, 3


def _live(q_pos, k_pos, keep, compare=_CAUSAL, window=None):
    """Which scores count: ``q_pos >= k_pos`` (``_CAUSAL``) and / or
    ``q_pos - k_pos < window`` (``_EDGE``), one a column and one a row.
    ``keep`` (bool, ``k_pos``'s shape, or None) is the key mask; with one,
    ``compare`` has to include ``_CAUSAL``."""
    if keep is not None:
        k_pos = jnp.where(keep, k_pos, _NEVER)
    if compare == _CAUSAL:
        return q_pos >= k_pos
    inside = q_pos < k_pos + window
    return inside if compare == _EDGE else (q_pos >= k_pos) & inside


def _scaled(q, factor: float):
    """q times ``factor`` in float32, rounded once to q's own dtype: every
    kernel's score product reads the same numbers."""
    return (q.astype(jnp.float32) * factor).astype(q.dtype)


def _across(stat, width: int):
    """A row statistic held lane-replicated as ``[rows, 128]``, at
    ``width`` columns (whole registers again when 128 divides it)."""
    if width % 128 == 0:
        return jnp.tile(stat, (1, width // 128))
    return jnp.broadcast_to(stat[:, :1], (stat.shape[0], width))


def _fold_segments(pl, segments, block: int, fold):
    """``fold(start, compare)`` over the blocks of a ref's long axis, one
    loop a ``(first, last, compare)`` segment, in order. The state lives in
    VMEM scratch; nothing is carried from loop to loop."""
    for first, last, compare in segments:
        def body(i, _, compare=compare):
            fold(pl.multiple_of(i * block, block), compare)
        jax.lax.fori_loop(first, last, body, None)


def _fold_q_major(pl, plan: FlashBlockPlan, fold, key_mask: bool):
    """The forward's and dQ's loops over kv blocks for this q block: edge
    blocks, interior ones (no compare), then the diagonal. With a key mask
    every block compares positions (:func:`_live`), so one loop runs them
    all."""
    n_first, n_edge, n_full, n_live = plan.q_major_window(pl.program_id(1))
    if plan.window is None:
        segments = ([(0, n_live, _CAUSAL)] if key_mask else
                    [(0, n_full, _NONE), (n_full, n_live, _CAUSAL)])
    else:
        segments = ([(n_first, n_live, _BOTH)] if key_mask else
                    [(n_first, n_edge, _EDGE), (n_edge, n_full, _NONE),
                     (n_full, n_live, _BOTH)])
    _fold_segments(pl, segments, plan.block_k, fold)


def _fold_k_major(pl, plan: FlashBlockPlan, fold, key_mask: bool):
    """dK/dV's loops over q blocks for this kv block: the diagonal,
    interior blocks, then edge ones."""
    j0, j_full, j_edge, j_last = plan.k_major_window(pl.program_id(1))
    if plan.window is None:
        segments = ([(j0, plan.n_q, _CAUSAL)] if key_mask else
                    [(j0, j_full, _CAUSAL), (j_full, plan.n_q, _NONE)])
    else:
        segments = ([(j0, j_last, _BOTH)] if key_mask else
                    [(j0, j_full, _BOTH), (j_full, j_edge, _NONE),
                     (j_edge, j_last, _EDGE)])
    _fold_segments(pl, segments, plan.block_q, fold)


def _flash_fwd_kernel(*refs, plan: FlashBlockPlan, scale: float,
                      key_mask: bool, sink: bool = False):
    """One (batch*head, q-block) program: online softmax over KV blocks.

    q_ref: [block_q, d_qk]; k_ref: [s, d_qk]; v_ref: [s, d_v]; mask_ref
    (only with a key mask): [1, s]; sink_ref (only with a sink): [1, 128],
    this head's logit in every lane; o_ref: [block_q, d_v]; lse_ref:
    [block_q, 1]; scratch, float32: acc_ref [block_q, d_v], m_ref and
    l_ref [block_q, 128] (a row's statistic in every lane).
    """
    import jax.experimental.pallas as pl

    q_ref, k_ref, v_ref, mask_ref, *rest = _split_refs(refs, 3, key_mask)
    sink_ref = rest.pop(0) if sink else None
    o_ref, lse_ref, acc_ref, m_ref, l_ref = rest
    block_q, block_k = plan.block_q, plan.block_k
    d_v = acc_ref.shape[1]
    q_pos = pl.program_id(1) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    q = _scaled(q_ref[:], scale * _LOG2E)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    if sink:
        # the sink is a key every row has already seen: score sink, value 0
        m_ref[:] = jnp.broadcast_to(sink_ref[:] * _LOG2E, m_ref.shape)
        l_ref[:] = jnp.ones_like(l_ref)
    else:
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def fold(start, compare):
        """Keys ``[start, start + block_k)`` into the running softmax."""
        keys = pl.ds(start, block_k)
        k_blk, v_blk = k_ref[keys, :], v_ref[keys, :]
        s_blk = jax.lax.dot_general(                    # [bq, bk]
            q, k_blk, _NT, preferred_element_type=jnp.float32)
        if compare:
            k_pos = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            live = _live(q_pos, k_pos,
                         mask_ref[:, keys] > 0 if key_mask else None,
                         compare, plan.window)
            s_blk = jnp.where(live, s_blk, NEG_INF)
        _softmax_step(s_blk, live if key_mask else None, v_blk, m_ref, l_ref,
                      acc_ref, slice(None))

    _fold_q_major(pl, plan, fold, key_mask)
    l = jnp.maximum(l_ref[:], 1e-30)
    o_ref[:] = (acc_ref[:] * _across(1.0 / l, d_v)).astype(o_ref.dtype)
    lse_ref[:] = ((m_ref[:] + jnp.log2(l)) * _LN2)[:, :1]


def _softmax_step(s_blk, live, v_blk, m_ref, l_ref, acc_ref, at):
    """A block's scores (``[rows, block_k]``, masked ones NEG_INF) into the
    running softmax that ``m_ref[at]``, ``l_ref[at]``, ``acc_ref[at]``
    hold. ``live``: the block's live scores where the call has a key mask,
    else None."""
    block_k, d_v = s_blk.shape[1], v_blk.shape[1]
    m = m_ref[at]
    m_new = jnp.maximum(m, jnp.max(s_blk, -1, keepdims=True))
    p = jnp.exp2(s_blk - _across(m_new, block_k))
    if live is not None:
        # gate on `live`, not just the exp: for a row with NO live
        # keys m_new stays NEG_INF, so exp2(s_blk - m_new) = 1 at every
        # masked position and O would silently become an unmasked
        # average of V; gating keeps l = 0 so the row's output is
        # exactly zero and its stored LSE ≈ NEG_INF (flagging the row)
        # instead. Without a key mask every row has seen key 0 by its
        # first block, so m_new is finite and the exp2 of a masked
        # score is already 0. (Under a window a row may meet blocks
        # before its first live key; what they leave in l and acc is
        # multiplied by alpha = exp2(NEG_INF - finite) = 0 at that key,
        # which every row reaches: its own.)
        p = jnp.where(live, p, 0.0)
    alpha = jnp.exp2(m - m_new)
    m_ref[at] = m_new
    l_ref[at] = l_ref[at] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[at] = acc_ref[at] * _across(alpha, d_v) + jnp.dot(
        p.astype(v_blk.dtype), v_blk, preferred_element_type=jnp.float32)


def _flash_dq_kernel(*refs, plan: FlashBlockPlan, scale: float,
                     key_mask: bool):
    """dQ for one q block: dS = P ∘ (dO·Vᵀ − D); dQ = scale · dS·K.
    Scratch acc_ref: [block_q, d_qk] float32."""
    import jax.experimental.pallas as pl

    q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, dd_ref, dq_ref, acc_ref = \
        _split_refs(refs, 3, key_mask)
    block_q, block_k = plan.block_q, plan.block_k
    q_pos = pl.program_id(1) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    q = _scaled(q_ref[:], scale * _LOG2E)
    do = do_ref[:]
    lse2 = lse_ref[:] * _LOG2E            # [block_q, 1]
    dd = dd_ref[:]                        # [block_q, 1]
    acc_ref[:] = jnp.zeros_like(acc_ref)

    def fold(start, compare):
        """Keys ``[start, start + block_k)`` into dQ."""
        keys = pl.ds(start, block_k)
        k_blk, v_blk = k_ref[keys, :], v_ref[keys, :]
        s_blk = jax.lax.dot_general(
            q, k_blk, _NT, preferred_element_type=jnp.float32)
        p = jnp.exp2(s_blk - lse2)
        if compare:
            k_pos = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            live = _live(q_pos, k_pos,
                         mask_ref[:, keys] > 0 if key_mask else None,
                         compare, plan.window)
            p = jnp.where(live, p, 0.0)
        _dq_step(p, do, dd, k_blk, v_blk, acc_ref, slice(None))

    _fold_q_major(pl, plan, fold, key_mask)
    dq_ref[:] = (acc_ref[:] * scale).astype(dq_ref.dtype)


def _dq_step(p, do, dd, k_blk, v_blk, acc_ref, at):
    """A block's probabilities ``p`` (masked ones 0) into the dQ that
    ``acc_ref[at]`` holds: dS = P ∘ (dO·Vᵀ − D), dQ += dS·K."""
    dp = jax.lax.dot_general(
        do, v_blk, _NT, preferred_element_type=jnp.float32)
    ds = p * (dp - dd)
    acc_ref[at] += jnp.dot(ds.astype(k_blk.dtype), k_blk,
                           preferred_element_type=jnp.float32)


def _flash_dkv_kernel(*refs, plan: FlashBlockPlan, scale: float,
                      key_mask: bool):
    """dK/dV for one kv block, in the transposed orientation: the scores
    as ``[block_k, block_q]`` from ``K·Qᵀ``, so that dV = Pᵀ·dO and
    dK = scale · dSᵀ·Q are plain products of what the block already holds
    and nothing score-sized is transposed.

    k_ref: [block_k, d_qk]; v_ref: [block_k, d_v]; q_ref: [s, d_qk];
    mask_ref (only with a key mask): [block_k, 1]; do_ref: [s, d_v];
    lse_ref, dd_ref: [1, s] rows; dk_ref: [block_k, d_qk]; dv_ref:
    [block_k, d_v]; scratch dk_acc, dv_acc: the results' shapes, float32.
    """
    import jax.experimental.pallas as pl

    (k_ref, v_ref, q_ref, mask_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
     dk_acc, dv_acc) = _split_refs(refs, 3, key_mask)
    block_q, block_k = plan.block_q, plan.block_k
    k_pos = pl.program_id(1) * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, 1), 0)
    if key_mask:                          # this kv block's, via BlockSpec
        k_pos = jnp.where(mask_ref[:] > 0, k_pos, _NEVER)
    k, v = k_ref[:], v_ref[:]
    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

    def fold(start, compare):
        """Queries ``[start, start + block_q)`` into dK and dV."""
        rows = pl.ds(start, block_q)
        # the forward's and dQ's q block, to the last bit
        q_blk = _scaled(q_ref[rows, :], scale * _LOG2E)
        do_blk = do_ref[rows, :]
        s_t = jax.lax.dot_general(                      # [bk, bq]
            k, q_blk, _NT, preferred_element_type=jnp.float32)
        p_t = jnp.exp2(s_t - lse_ref[:, rows] * _LOG2E)
        if compare:
            q_pos = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_q), 1)
            p_t = jnp.where(_live(q_pos, k_pos, None, compare, plan.window),
                            p_t, 0.0)
        _dkv_step(p_t, q_blk, do_blk, v, dd_ref, (slice(None), rows), dk_acc,
                  dv_acc)

    _fold_k_major(pl, plan, fold, key_mask)
    _dkv_out(dk_ref, dv_ref, dk_acc, dv_acc)


def _dkv_step(p_t, q_blk, do_blk, v, dd_ref, dd_at, dk_acc, dv_acc):
    """A q block's transposed probabilities ``p_t`` (masked ones 0) into
    dK and dV: dV += Pᵀ·dO, dSᵀ = Pᵀ ∘ (V·dOᵀ − D), dK += dSᵀ·Q; D is
    ``dd_ref[dd_at]``, a ``[1, block_q]`` row."""
    dv_acc[:] += jnp.dot(p_t.astype(do_blk.dtype), do_blk,
                         preferred_element_type=jnp.float32)
    dp_t = jax.lax.dot_general(
        v, do_blk, _NT, preferred_element_type=jnp.float32)
    ds_t = p_t * (dp_t - dd_ref[dd_at])
    dk_acc[:] += jnp.dot(ds_t.astype(q_blk.dtype), q_blk,
                         preferred_element_type=jnp.float32)


def _dkv_out(dk_ref, dv_ref, dk_acc, dv_acc):
    # q carried scale * log2(e); dK wants the scale alone
    dk_ref[:] = (dk_acc[:] * _LN2).astype(dk_ref.dtype)
    dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _window_fwd_kernel(*refs, plan: FlashBlockPlan, scale: float,
                       key_mask: bool, sink: bool):
    """One (batch*key-value head, q-block) program: the online softmax of
    the group's g query heads against the key-value row they share. A kv
    block is loaded once and every head's step on it runs in the same loop
    body, so one head's products overlap another's softmax.

    q_ref: [g, block_q, d_qk]; k_ref, v_ref, mask_ref as
    :func:`_flash_fwd_kernel` has them; sink_ref: [g, 1, 128]; o_ref: [g,
    block_q, d_v]; lse_ref: [g, block_q, 1]; scratch: qs_ref [g, block_q,
    d_qk] (the scaled queries), float32 acc_ref [g, block_q, d_v], m_ref
    and l_ref [g, block_q, 128].
    """
    import jax.experimental.pallas as pl

    q_ref, k_ref, v_ref, mask_ref, *rest = _split_refs(refs, 3, key_mask)
    sink_ref = rest.pop(0) if sink else None
    o_ref, lse_ref, qs_ref, acc_ref, m_ref, l_ref = rest
    heads, block_q, block_k = q_ref.shape[0], plan.block_q, plan.block_k
    d_v = acc_ref.shape[-1]
    q_pos = pl.program_id(1) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    for a in range(heads):
        qs_ref[a] = _scaled(q_ref[a], scale * _LOG2E)
        acc_ref[a] = jnp.zeros(acc_ref.shape[1:], jnp.float32)
        if sink:    # a key every row has already seen: score sink, value 0
            m_ref[a] = jnp.broadcast_to(sink_ref[a] * _LOG2E,
                                        m_ref.shape[1:])
            l_ref[a] = jnp.ones(l_ref.shape[1:], jnp.float32)
        else:
            m_ref[a] = jnp.full(m_ref.shape[1:], NEG_INF, jnp.float32)
            l_ref[a] = jnp.zeros(l_ref.shape[1:], jnp.float32)

    def fold(start, compare):
        """Keys ``[start, start + block_k)`` into every head's softmax."""
        keys = pl.ds(start, block_k)
        k_blk, v_blk = k_ref[keys, :], v_ref[keys, :]
        if compare:
            k_pos = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            live = _live(q_pos, k_pos,
                         mask_ref[:, keys] > 0 if key_mask else None,
                         compare, plan.window)
        for a in range(heads):
            s_blk = jax.lax.dot_general(
                qs_ref[a], k_blk, _NT, preferred_element_type=jnp.float32)
            if compare:
                s_blk = jnp.where(live, s_blk, NEG_INF)
            _softmax_step(s_blk, live if key_mask else None, v_blk, m_ref,
                          l_ref, acc_ref, a)

    _fold_q_major(pl, plan, fold, key_mask)
    for a in range(heads):
        l = jnp.maximum(l_ref[a], 1e-30)
        o_ref[a] = (acc_ref[a] * _across(1.0 / l, d_v)).astype(o_ref.dtype)
        lse_ref[a] = ((m_ref[a] + jnp.log2(l)) * _LN2)[:, :1]


def _window_dq_kernel(*refs, plan: FlashBlockPlan, scale: float,
                      key_mask: bool):
    """dQ for one q block of the group's query heads, every head's step on
    a kv block in the same loop body: q_ref, do_ref, dq_ref [g, block_q,
    .], lse_ref, dd_ref [g, block_q, 1]; scratch qs_ref [g, block_q, d_qk]
    (the scaled queries) and float32 acc_ref [g, block_q, d_qk]."""
    import jax.experimental.pallas as pl

    (q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, dd_ref, dq_ref, qs_ref,
     acc_ref) = _split_refs(refs, 3, key_mask)
    heads, block_q, block_k = q_ref.shape[0], plan.block_q, plan.block_k
    q_pos = pl.program_id(1) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    for a in range(heads):
        qs_ref[a] = _scaled(q_ref[a], scale * _LOG2E)
        acc_ref[a] = jnp.zeros(acc_ref.shape[1:], jnp.float32)

    def fold(start, compare):
        """Keys ``[start, start + block_k)`` into every head's dQ."""
        keys = pl.ds(start, block_k)
        k_blk, v_blk = k_ref[keys, :], v_ref[keys, :]
        if compare:
            k_pos = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            live = _live(q_pos, k_pos,
                         mask_ref[:, keys] > 0 if key_mask else None,
                         compare, plan.window)
        for a in range(heads):
            s_blk = jax.lax.dot_general(
                qs_ref[a], k_blk, _NT, preferred_element_type=jnp.float32)
            p = jnp.exp2(s_blk - lse_ref[a] * _LOG2E)
            if compare:
                p = jnp.where(live, p, 0.0)
            _dq_step(p, do_ref[a], dd_ref[a], k_blk, v_blk, acc_ref, a)

    _fold_q_major(pl, plan, fold, key_mask)
    for a in range(heads):
        dq_ref[a] = (acc_ref[a] * scale).astype(dq_ref.dtype)


def _window_dkv_kernel(*refs, plan: FlashBlockPlan, scale: float,
                       key_mask: bool):
    """dK/dV for one kv block of one key-value head: the group's query
    heads' steps on a q block in the same loop body, into the same float32
    scratch, so the block's gradient is the group's sum. q_ref, do_ref:
    [1, g, rows, .] and lse_ref, dd_ref: [1, g, 1, rows], the band's
    queries from row ``plan.band_start`` on; the rest as
    :func:`_flash_dkv_kernel` has them."""
    import jax.experimental.pallas as pl

    (k_ref, v_ref, q_ref, mask_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
     dk_acc, dv_acc) = _split_refs(refs, 3, key_mask)
    heads, block_q, block_k = q_ref.shape[1], plan.block_q, plan.block_k
    k_pos = pl.program_id(1) * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, 1), 0)
    if key_mask:                          # this kv block's, via BlockSpec
        k_pos = jnp.where(mask_ref[:] > 0, k_pos, _NEVER)
    k, v = k_ref[:], v_ref[:]
    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)
    first = plan.band_start(pl.program_id(1), q_ref.shape[2])

    def fold(start, compare):
        """Queries ``[start, start + block_q)`` of every head into dK and
        dV."""
        rows = pl.ds(pl.multiple_of(start - first, block_q), block_q)
        if compare:
            q_pos = start + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_q), 1)
            live = _live(q_pos, k_pos, None, compare, plan.window)
        for a in range(heads):
            q_blk = _scaled(q_ref[0, a, rows, :], scale * _LOG2E)
            do_blk = do_ref[0, a, rows, :]
            s_t = jax.lax.dot_general(
                k, q_blk, _NT, preferred_element_type=jnp.float32)
            p_t = jnp.exp2(s_t - lse_ref[0, a, :, rows] * _LOG2E)
            if compare:
                p_t = jnp.where(live, p_t, 0.0)
            _dkv_step(p_t, q_blk, do_blk, v, dd_ref, (0, a, slice(None), rows),
                      dk_acc, dv_acc)

    _fold_k_major(pl, plan, fold, key_mask)
    _dkv_out(dk_ref, dv_ref, dk_acc, dv_acc)


def _heads_first(a):
    """[b, s, h, d] -> [b*h, s, d], the kernels' layout."""
    b, s, h, d = a.shape
    return a.transpose(0, 2, 1, 3).reshape(b * h, s, d)



def _row_mask(pl, mask, h: int):
    """(operands, specs) of the key mask for the q-major kernels: a
    ``[1, s]`` row a batch row, lane-dense beside the ``[block_q, block_k]``
    scores whose keys lie along the lanes; nothing without a mask."""
    if mask is None:
        return [], []
    b, s, _ = mask.shape
    return ([mask.reshape(b, 1, s)],
            [pl.BlockSpec((None, 1, s), lambda i, j: (i // h, 0, 0))])


def _flash_fwd(q, k, v, mask, sink, block_q: int, block_k: int, scale: float):
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s, h, d = q.shape
    dv = v.shape[-1]
    key_mask = mask is not None
    plan = flash_block_plan(s, block_q, block_k)
    qf, kf, vf = _heads_first(q), _heads_first(k), _heads_first(v)
    mask_rows, mask_specs = _row_mask(pl, mask, h)
    sink_rows, sink_specs = [], []
    if sink is not None:    # a head's logit, lane-replicated as m and l are
        sink_rows = [jnp.broadcast_to(
            sink.astype(jnp.float32)[:, None, None], (h, 1, 128))]
        sink_specs = [pl.BlockSpec((None, 1, 128), lambda i, j: (i % h, 0, 0))]
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, plan=plan, scale=scale,
                          key_mask=key_mask, sink=sink is not None),
        grid=(b * h, plan.n_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, dv), lambda i, j: (i, 0, 0)),
            *mask_specs, *sink_specs,
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, dv), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=FLASH_KERNEL_NAMES[0],
    )(qf, kf, vf, *mask_rows, *sink_rows)
    return out.reshape(b, h, s, dv).transpose(0, 2, 1, 3), lse


def _flash_bwd(q, k, v, mask, sink, o, lse, g, block_q: int, block_k: int,
               scale: float):
    """-> (dq, dk, dv, dsink); ``dsink`` None without a sink."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s, h, d = q.shape
    dv = v.shape[-1]
    key_mask = mask is not None
    plan = flash_block_plan(s, block_q, block_k)
    qf, kf, vf = _heads_first(q), _heads_first(k), _heads_first(v)
    gf, of = _heads_first(g), _heads_first(o)
    # D_i = Σ_d dO_i ∘ O_i — one cheap elementwise pass in XLA
    dd = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                 axis=-1, keepdims=True)
    mask_rows, mask_specs = _row_mask(pl, mask, h)

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, plan=plan, scale=scale,
                          key_mask=key_mask),
        grid=(b * h, plan.n_q),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, dv), lambda i, j: (i, 0, 0)),
            *mask_specs,
            pl.BlockSpec((None, block_q, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=FLASH_KERNEL_NAMES[1],
    )(qf, kf, vf, *mask_rows, gf, lse, dd)

    dk, dv_ = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, plan=plan, scale=scale,
                          key_mask=key_mask),
        grid=(b * h, plan.n_k),
        in_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
            *([pl.BlockSpec((None, block_k, 1), lambda i, j: (i // h, j, 0))]
              if key_mask else []),
            pl.BlockSpec((None, s, dv), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, s), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, 1, s), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, dv), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s, dv), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=FLASH_KERNEL_NAMES[2],
    )(kf, vf, qf, *([mask] if key_mask else []), gf,
      lse.reshape(b * h, 1, s), dd.reshape(b * h, 1, s))

    dsink = _sink_grad(sink, lse, dd, b, h, s)
    unflat = lambda a: a.reshape(b, h, s, -1).transpose(0, 2, 1, 3)
    return unflat(dq), unflat(dk), unflat(dv_), dsink


def _sink_grad(sink, lse, dd, b: int, h: int, s: int):
    """The sink's column of dS = P (dP - D) with dP = 0 (it has no value):
    -exp(sink - lse) D, summed over rows and the batch; None without a
    sink. ``lse``, ``dd``: any layout that reshapes to ``[b, h, s]``."""
    if sink is None:
        return None
    p_sink = jnp.exp(sink.astype(jnp.float32)[None, :, None, None]
                     - lse.reshape(b, h, s, 1))
    return -jnp.sum(p_sink * dd.reshape(b, h, s, 1),
                    axis=(0, 2, 3)).astype(sink.dtype)


def _grouped(a, h_kv: int):
    """[b, s, h, d] -> [b*h_kv, h/h_kv, s, d]: query head ``i`` is member
    ``i % g`` of key-value head ``i // g``'s group (``jnp.repeat``'s
    order)."""
    b, s, h, d = a.shape
    return _heads_first(a).reshape(b * h_kv, h // h_kv, s, d)


def _window_fwd(q, k, v, mask, sink, block_q: int, block_k: int,
                scale: float, window: int):
    """The window kernels' forward: a grid step is one key-value head's
    group of query heads (k, v at ``h_kv`` heads) and one q block."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s, h, d = q.shape
    h_kv, dv = k.shape[2], v.shape[-1]
    g = h // h_kv
    plan = flash_block_plan(s, block_q, block_k, window)
    mask_rows, mask_specs = _row_mask(pl, mask, h_kv)
    sink_rows, sink_specs = [], []
    if sink is not None:    # a head's logit, lane-replicated as m and l are
        sink_rows = [jnp.broadcast_to(sink.astype(jnp.float32).reshape(
            h_kv, g, 1, 1), (h_kv, g, 1, 128))]
        sink_specs = [pl.BlockSpec((None, g, 1, 128),
                                   lambda i, j: (i % h_kv, 0, 0, 0))]
    group = lambda width: pl.BlockSpec(  # noqa: E731
        (None, g, block_q, width), lambda i, j: (i, 0, j, 0))
    out, lse = pl.pallas_call(
        functools.partial(_window_fwd_kernel, plan=plan, scale=scale,
                          key_mask=mask is not None, sink=sink is not None),
        grid=(b * h_kv, plan.n_q),
        in_specs=[
            group(d),
            pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, dv), lambda i, j: (i, 0, 0)),
            *mask_specs, *sink_specs,
        ],
        out_specs=[group(dv), group(1)],
        out_shape=[
            jax.ShapeDtypeStruct((b * h_kv, g, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h_kv, g, s, 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((g, block_q, d), q.dtype),
                        pltpu.VMEM((g, block_q, dv), jnp.float32),
                        pltpu.VMEM((g, block_q, 128), jnp.float32),
                        pltpu.VMEM((g, block_q, 128), jnp.float32)],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=WINDOW_KERNEL_NAMES[0],
    )(_grouped(q, h_kv), _heads_first(k), _heads_first(v), *mask_rows,
      *sink_rows)
    return out.reshape(b, h, s, dv).transpose(0, 2, 1, 3), lse


def _window_bwd(q, k, v, mask, sink, o, lse, g_, block_q: int, block_k: int,
                scale: float, window: int):
    """-> (dq, dk, dv, dsink), dk and dv at ``h_kv`` heads: the group's
    sum is made in dK/dV's scratch."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    b, s, h, d = q.shape
    h_kv, dv = k.shape[2], v.shape[-1]
    g = h // h_kv
    key_mask = mask is not None
    plan = flash_block_plan(s, block_q, block_k, window)
    qf, kf, vf = _grouped(q, h_kv), _heads_first(k), _heads_first(v)
    gf = _grouped(g_, h_kv)
    # D_i = Σ_d dO_i ∘ O_i — one cheap elementwise pass in XLA
    dd = jnp.sum(gf.astype(jnp.float32)
                 * _grouped(o, h_kv).astype(jnp.float32),
                 axis=-1, keepdims=True)
    mask_rows, mask_specs = _row_mask(pl, mask, h_kv)
    group = lambda width: pl.BlockSpec(  # noqa: E731
        (None, g, block_q, width), lambda i, j: (i, 0, j, 0))

    dq = pl.pallas_call(
        functools.partial(_window_dq_kernel, plan=plan, scale=scale,
                          key_mask=key_mask),
        grid=(b * h_kv, plan.n_q),
        in_specs=[
            group(d),
            pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, dv), lambda i, j: (i, 0, 0)),
            *mask_specs, group(dv), group(1), group(1),
        ],
        out_specs=group(d),
        out_shape=jax.ShapeDtypeStruct((b * h_kv, g, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((g, block_q, d), q.dtype),
                        pltpu.VMEM((g, block_q, d), jnp.float32)],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=WINDOW_KERNEL_NAMES[1],
    )(qf, kf, vf, *mask_rows, gf, lse, dd)

    # the queries that see a kv block: its band's rows, in element offsets
    rows = plan.band_rows()
    band = lambda width: pl.BlockSpec(  # noqa: E731
        tuple(map(pl.Element, (1, g, rows, width))),
        lambda i, j: (i, 0, plan.band_start(j, rows), 0))
    stat = pl.BlockSpec(tuple(map(pl.Element, (1, g, 1, rows))),
                        lambda i, j: (i, 0, 0, plan.band_start(j, rows)))
    dk, dv_ = pl.pallas_call(
        functools.partial(_window_dkv_kernel, plan=plan, scale=scale,
                          key_mask=key_mask),
        grid=(b * h_kv, plan.n_k),
        in_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, dv), lambda i, j: (i, j, 0)),
            band(d),
            *([pl.BlockSpec((None, block_k, 1),
                            lambda i, j: (i // h_kv, j, 0))]
              if key_mask else []),
            band(dv), stat, stat,
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, dv), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h_kv, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h_kv, s, dv), q.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=WINDOW_KERNEL_NAMES[2],
    )(kf, vf, qf, *([mask] if key_mask else []), gf,
      lse.reshape(b * h_kv, g, 1, s), dd.reshape(b * h_kv, g, 1, s))

    dsink = _sink_grad(sink, lse, dd, b, h, s)
    unflat = lambda a, n: a.reshape(b, n, s, -1).transpose(0, 2, 1, 3)  # noqa: E731
    return unflat(dq, h), unflat(dk, h_kv), unflat(dv_, h_kv), dsink


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(q, k, v, mask, sink, block_q: int, block_k: int, scale: float,
           window: Optional[int]):
    return _flash_fwd_rule(q, k, v, mask, sink, block_q, block_k, scale,
                           window)[0]


def _flash_fwd_rule(q, k, v, mask, sink, block_q, block_k, scale, window):
    if window is None:
        out, lse = _flash_fwd(q, k, v, mask, sink, block_q, block_k, scale)
    else:
        out, lse = _window_fwd(q, k, v, mask, sink, block_q, block_k, scale,
                               window)
    return out, (q, k, v, mask, sink, out, lse)


def _flash_bwd_rule(block_q, block_k, scale, window, res, g):
    q, k, v, mask, sink, out, lse = res
    if window is None:
        dq, dk, dv, dsink = _flash_bwd(q, k, v, mask, sink, out, lse, g,
                                       block_q, block_k, scale)
    else:
        dq, dk, dv, dsink = _window_bwd(q, k, v, mask, sink, out, lse, g,
                                        block_q, block_k, scale, window)
    return (dq, dk, dv, None if mask is None else jnp.zeros_like(mask),
            dsink)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_causal_attention(q, k, v, block_q: Optional[int] = None,
                           block_k: Optional[int] = None,
                           attn_mask: Optional[jnp.ndarray] = None,
                           scale: Optional[float] = None,
                           window: Optional[int] = None,
                           sink: Optional[jnp.ndarray] = None):
    """Pallas flash attention, fused fwd+bwd (see module docstring).
    q: [b, s, h, d_qk], k: [b, s, h_kv, d_qk], v: [b, s, h_kv, d_v] ->
    [b, s, h, d_v], ``h_kv`` dividing ``h`` (query head ``i`` reads
    key-value head ``i // (h / h_kv)``, ``jnp.repeat``'s order); one set of
    kernels serves ``d_qk == d_v`` (128) and latent attention's 192/128.
    ``attn_mask``: optional [b, s] key-padding mask (1 = real); ``scale``
    multiplies the scores (default ``d_qk ** -0.5``). ``window``: query i
    sees keys ``i - window < j <= i`` only (one that reaches every earlier
    key is no window: the causal kernels run, under their names);
    ``sink``: ``[h]`` logits, differentiable, a softmax column a head that
    carries no value. Both are static properties of a call: without them
    the kernels are the causal ones to the last instruction.

    Blocks are 512x512 where s allows (``_fit_block``). On the v5e (PR 31,
    kernels alone, bf16, ms a call forward / dQ / dK/dV; the kernels before
    it in brackets): ``[1,4096,64,192/128]`` 3.02 / 4.51 / 5.49 (4.09 /
    4.82 / 6.68), ``[8,1024,32,128]`` 0.93 / 1.10 / 1.38 (1.41 / 1.10 /
    2.04), ``[1,8192,2,128]`` 0.25 / 0.33 / 0.42 (0.38 / 0.33 / 0.53); dQ
    and dK/dV then sit at the MXU's own time for the blocks they compute.
    The causal kernels get grouped keys and values repeated here; a window
    call's kernels take them as they are, a grid step one key-value head
    with its group of query heads: a kv block is loaded once and every
    head's step on it runs in one loop body, and dK/dV sums the group in
    its scratch and reads only its band's queries. Blocks ``WINDOW_BLOCKS``
    (128x128):
    at ``[1,4096,64,192/128]``, 8 key-value heads, window 128 with a sink
    (PR 42) 1.01 / 0.95 / 0.88 against 0.98 / 1.06 / 1.11 at 256x256 and
    1.32 / 1.80 / 2.07 at 512x512; one query head a step on keys repeated
    to 64 heads took 1.71 / 1.61 / 1.98 at 256x256 and 2.24 / 2.17 / 2.96
    at 128x128, and the group a step with its heads one after the other
    1.37 / 1.35 / 1.53: what a block costs beyond its products is its
    head's chain of product, softmax and product, which the group's heads
    now overlap, not the grid step.

    Sequences are padded up to a multiple of 128 so every Pallas block is
    lane/sublane-aligned on real TPU hardware (a non-power-of-two s like
    1000 would otherwise pick a 125-row block). Pallas dynamic slices
    CLAMP out-of-bounds starts, so blocks MUST divide the padded length
    exactly — padding then slicing is the safe shape-independent recipe.
    Padded keys are masked out; padded query rows are sliced away.
    """
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv or v.shape[2] != h_kv:
        raise ValueError(f"{h} query heads on {h_kv} key and {v.shape[2]} "
                         "value heads: one head count must divide the other")
    scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
    s_pad = -(-s // 128) * 128
    if window is not None:
        if window < 1:
            raise ValueError(f"window {window}: a query sees itself at least")
        window = int(window) if window < s else None
    if window is None and h_kv != h:    # the causal kernels take every head
        k, v = (jnp.repeat(a, h // h_kv, axis=2) for a in (k, v))
    want_q, want_k = WINDOW_BLOCKS if window is not None else (512, 512)
    block_q, block_k = block_q or want_q, block_k or want_k
    # the key mask is a static property of the call: without one, and with
    # nothing padded, the kernels carry no key-mask operand or arithmetic
    mask = None
    if attn_mask is not None or s_pad != s:
        mask = (jnp.ones((b, s), jnp.float32) if attn_mask is None
                else attn_mask.astype(jnp.float32))[:, :, None]
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        mask = jnp.pad(mask, [(0, 0), (0, s_pad - s), (0, 0)])
    block_q, block_k = _fit_block(s_pad, block_q), _fit_block(s_pad, block_k)
    interior, diagonal, _ = flash_block_plan(s_pad, block_q, block_k).counts()
    if window is None:
        obs_metrics.record_flash_plan(interior / (interior + diagonal),
                                      mask is not None)
    if window is not None or sink is not None:
        computed = sum(flash_block_plan(s_pad, block_q, block_k,
                                        window).counts()[:2])
        obs_metrics.record_flash_window(
            window or 0, computed / (interior + diagonal), sink is not None,
            h // h_kv if window else 1)
    out = _flash(q, k, v, mask, sink, block_q, block_k, scale, window)
    return out[:, :s] if s_pad != s else out


# a window call's blocks where the caller names none (PERF.md section 6,
# PR 42: the probe on the chip at [1,4096,64,192/128], 8 key-value heads,
# window 128)
WINDOW_BLOCKS = (128, 128)


def _fit_block(s_pad: int, want: int) -> int:
    """Largest 128-multiple block <= ``want`` that divides ``s_pad``
    (itself a 128-multiple) — lane-aligned AND exactly tiling."""
    b = max(128, (min(want, s_pad) // 128) * 128)
    while s_pad % b:
        b -= 128
    return b


# ----------------------------------------------------------------- ring ----

def ring_causal_attention(q, k, v, axis_name: str = "sp",
                          axis_size: int = 1,
                          attn_mask: Optional[jnp.ndarray] = None
                          ) -> jnp.ndarray:
    """Causal attention with the sequence sharded over ``axis_name``.

    Must be traced inside ``shard_map``: q/k/v are the local shards
    [b, s_loc, h, d]; K/V rotate around the ring via ``ppermute`` while each
    device folds the visiting block into its online-softmax accumulator.
    Communication rides ICI; peak memory per device is O(s_loc² + s_loc·d).

    ``attn_mask``: optional [b, s_loc] key-padding shard (1 = real key),
    sharded over ``axis_name`` the same way as k/v. It rotates around the
    ring alongside the K/V block it describes, so every device masks the
    *visiting* block's padded keys (the varlen/unpad story of the
    reference's flash patch, ``train/llm/models/attention.py:68``).
    A query row whose visible keys are all padded yields exactly zero.
    """
    b, s_loc, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    my_idx = jax.lax.axis_index(axis_name)
    q_pos = my_idx * s_loc + jnp.arange(s_loc, dtype=jnp.int32)
    kmask0 = (jnp.ones((b, s_loc), bool) if attn_mask is None
              else attn_mask.astype(bool))

    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    def fold(carry, xs):
        o_acc, m, l, k_cur, v_cur, km_cur = carry
        step = xs
        kv_idx = (my_idx - step) % axis_size
        kv_pos = kv_idx * s_loc + jnp.arange(s_loc, dtype=jnp.int32)
        s_blk = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                           k_cur.astype(jnp.float32)) * scale
        causal = q_pos[:, None] >= kv_pos[None, :]          # [s_loc, s_loc]
        live = causal[None, None] & km_cur[:, None, None, :]  # [b,1,q,k]
        s_blk = jnp.where(live, s_blk, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_blk, -1))
        alpha = jnp.exp(m - m_new)
        # gate on `live` (not just the exp): a row with no live keys has
        # m_new = NEG_INF and exp(NEG_INF - NEG_INF) = 1 everywhere, which
        # would silently average V; gating keeps l = 0 -> output 0
        p = jnp.where(live, jnp.exp(s_blk - m_new[..., None]), 0.0)
        l_new = l * alpha + jnp.sum(p, -1)
        o_new = (o_acc * alpha[..., None] +
                 jnp.einsum("bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32)))
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        km_nxt = jax.lax.ppermute(km_cur, axis_name, perm)
        return (o_new, m_new, l_new, k_nxt, v_nxt, km_nxt), ()

    o0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    # TRAINING-MEMORY CONTRACT: the fold is rematerialized. Plain autodiff
    # through the scan would save each step's [b, h, s_loc, s_loc]
    # probability block as a residual — s_loc²·axis_size memory, erasing
    # ring attention's point at exactly the context lengths it exists for.
    # With remat the backward recomputes the block from the step's carry
    # (K/V shards, O(s_loc·d)), so saved state stays O(axis_size·s_loc·d)
    # and the s_loc² working block lives only transiently per step — the
    # same guarantee the flash kernels give single-chip
    # (test_ring_bwd_residuals_stay_linear_in_s).
    (o, m, l, _, _, _), _ = jax.lax.scan(
        jax.checkpoint(fold), (o0, m0, l0, k, v, kmask0),
        jnp.arange(axis_size))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)
