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
  for the MXU.
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
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..core import kernels

NEG_INF = -1e30
# the three kernels' names in a device trace (forward, dQ, dK/dV)
FLASH_KERNEL_NAMES = ("flash_fwd", "flash_dq", "flash_dkv")

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
                     scale: Optional[float] = None) -> jnp.ndarray:
    """Dispatch. q/k: [b, s, h, d_qk], v: [b, s, h, d_v] → [b, s, h, d_v].
    ``scale`` multiplies the scores (default ``d_qk ** -0.5``); dense and
    flash take ``d_qk != d_v`` (latent attention's 192/128)."""
    if impl == "ring":
        ax = _RING_AXIS.get()
        if ax is None:
            raise RuntimeError(
                "attention_impl='ring' requires the sequence-parallel "
                "context (fedml_tpu.llm.attention.ring_axis) — wrap the "
                "forward in shard_map over the 'sp' axis")
        if scale is not None or q.shape[-1] != v.shape[-1]:
            raise NotImplementedError(
                "ring attention takes one head size and its default scale")
        return ring_causal_attention(q, k, v, axis_name=ax[0],
                                     axis_size=ax[1], attn_mask=attn_mask)
    if impl == "flash":
        return flash_causal_attention(q, k, v, attn_mask=attn_mask,
                                      scale=scale)
    return dense_causal_attention(q, k, v, attn_mask=attn_mask, scale=scale)


def dense_causal_attention(q, k, v, attn_mask=None, scale=None):
    """[b, s, h, d] — reference semantics, scores in f32."""
    _, s, _, d = q.shape
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    mask = causal[None, None]
    if attn_mask is not None:  # [b, s] key padding
        mask = mask & attn_mask[:, None, None, :].astype(bool)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
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

def _flash_fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref, *,
                      block_k: int, seq_len: int, scale: float):
    """One (batch*head, q-block) program: online softmax over KV blocks.

    q_ref: [block_q, d_qk]; k_ref: [s, d_qk]; v_ref: [s, d_v];
    mask_ref: [s, 1]; o_ref: [block_q, d_v]; lse_ref: [block_q, 1].
    """
    import jax.experimental.pallas as pl

    block_q = q_ref.shape[0]
    d = v_ref.shape[1]
    q_blk_idx = pl.program_id(1)
    q_pos = q_blk_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)

    q = q_ref[:].astype(jnp.float32) * scale

    def body(i, carry):
        o_acc, m, l = carry
        k_blk = k_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s_blk = jnp.dot(q, k_blk.T,
                        preferred_element_type=jnp.float32)  # [bq, bk]
        k_pos = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        live = q_pos >= k_pos
        kmask = mask_ref[pl.ds(i * block_k, block_k), 0]
        live = jnp.logical_and(live, (kmask > 0)[None, :])
        s_blk = jnp.where(live, s_blk, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_blk, -1, keepdims=True))
        # gate on `live`, not just the exp: for a row with NO live keys
        # m_new stays NEG_INF, so exp(s_blk - m_new) = exp(0) = 1 at every
        # masked position and O would silently become an unmasked average
        # of V; gating keeps l = 0 so the row's output is exactly zero and
        # its stored LSE ≈ NEG_INF (flagging the row) instead
        p = jnp.where(live, jnp.exp(s_blk - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, -1, keepdims=True)
        o_new = o_acc * alpha + jnp.dot(p, v_blk,
                                        preferred_element_type=jnp.float32)
        return o_new, m_new, l_new

    n_k = pl.cdiv(seq_len, block_k)
    # causal: kv blocks strictly after this q block contribute nothing;
    # the last live block is the one containing this q block's final query
    n_live = jnp.minimum(
        n_k, ((q_blk_idx + 1) * block_q + block_k - 1) // block_k)
    o_acc = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    o_acc, m, l = jax.lax.fori_loop(0, n_live, body, (o_acc, m0, l0))
    o_ref[:] = (o_acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[:] = m + jnp.log(jnp.maximum(l, 1e-30))


def _flash_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, dd_ref,
                     dq_ref, *, block_k: int, seq_len: int, scale: float):
    """dQ for one q block: dS = P ∘ (dO·Vᵀ − D); dQ = scale · dS·K."""
    import jax.experimental.pallas as pl

    block_q, d = q_ref.shape              # d = d_qk; v and dO carry d_v
    q_blk_idx = pl.program_id(1)
    q_pos = q_blk_idx * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    q = q_ref[:].astype(jnp.float32) * scale
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:]                      # [block_q, 1]
    dd = dd_ref[:]                        # [block_q, 1]

    def body(i, dq_acc):
        k_blk = k_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[pl.ds(i * block_k, block_k), :].astype(jnp.float32)
        s_blk = jnp.dot(q, k_blk.T, preferred_element_type=jnp.float32)
        k_pos = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        live = q_pos >= k_pos
        kmask = mask_ref[pl.ds(i * block_k, block_k), 0]
        live = jnp.logical_and(live, (kmask > 0)[None, :])
        p = jnp.where(live, jnp.exp(s_blk - lse), 0.0)
        dp = jnp.dot(do, v_blk.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dd)
        return dq_acc + jnp.dot(ds, k_blk,
                                preferred_element_type=jnp.float32)

    n_k = pl.cdiv(seq_len, block_k)
    n_live = jnp.minimum(
        n_k, ((q_blk_idx + 1) * block_q + block_k - 1) // block_k)
    dq = jax.lax.fori_loop(0, n_live, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(k_ref, v_ref, q_ref, mask_ref, do_ref, lse_ref,
                      dd_ref, dk_ref, dv_ref, *, block_q: int, seq_len: int,
                      scale: float):
    """dK/dV for one kv block: dV = Pᵀ·dO; dK = scale · dSᵀ·Q."""
    import jax.experimental.pallas as pl

    block_k, d = k_ref.shape              # d = d_qk
    d_v = v_ref.shape[1]
    k_blk_idx = pl.program_id(1)
    k_pos = k_blk_idx * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    k = k_ref[:].astype(jnp.float32)
    v = v_ref[:].astype(jnp.float32)
    kmask = (mask_ref[:, 0] > 0)[None, :]  # this kv block's slice via BlockSpec

    def body(j, carry):
        dk_acc, dv_acc = carry
        q_blk = q_ref[pl.ds(j * block_q, block_q), :].astype(
            jnp.float32) * scale
        do_blk = do_ref[pl.ds(j * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(j * block_q, block_q), :]
        dd = dd_ref[pl.ds(j * block_q, block_q), :]
        s_blk = jnp.dot(q_blk, k.T, preferred_element_type=jnp.float32)
        q_pos = j * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        live = jnp.logical_and(q_pos >= k_pos, kmask)
        p = jnp.where(live, jnp.exp(s_blk - lse), 0.0)       # [bq, bk]
        dv_acc = dv_acc + jnp.dot(p.T, do_blk,
                                  preferred_element_type=jnp.float32)
        dp = jnp.dot(do_blk, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dd)
        dk_acc = dk_acc + jnp.dot(ds.T, q_blk,
                                  preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    n_q = pl.cdiv(seq_len, block_q)
    # causal: q blocks strictly before this kv block see none of it
    j0 = (k_blk_idx * block_k) // block_q
    dk, dv = jax.lax.fori_loop(
        j0, n_q, body, (jnp.zeros((block_k, d), jnp.float32),
                        jnp.zeros((block_k, d_v), jnp.float32)))
    # dk absorbs the q-side scale (q was pre-scaled), which equals the
    # symmetric scale on s = scale·q·kᵀ
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _heads_first(a):
    """[b, s, h, d] -> [b*h, s, d], the kernels' layout."""
    b, s, h, d = a.shape
    return a.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _flash_fwd(q, k, v, mask, block_q: int, block_k: int, scale: float):
    import jax.experimental.pallas as pl

    b, s, h, d = q.shape
    dv = v.shape[-1]
    qf, kf, vf = _heads_first(q), _heads_first(k), _heads_first(v)
    grid = (b * h, pl.cdiv(s, block_q))
    out, lse = pl.pallas_call(
        functools.partial(_flash_fwd_kernel, block_k=block_k, seq_len=s,
                          scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, dv), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, 1), lambda i, j, h=h: (i // h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, s, 1), jnp.float32),
        ],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=FLASH_KERNEL_NAMES[0],
    )(qf, kf, vf, mask)
    return out.reshape(b, h, s, dv).transpose(0, 2, 1, 3), lse


def _flash_bwd(q, k, v, mask, o, lse, g, block_q: int, block_k: int,
               scale: float):
    import jax.experimental.pallas as pl

    b, s, h, d = q.shape
    dv = v.shape[-1]
    qf, kf, vf = _heads_first(q), _heads_first(k), _heads_first(v)
    gf, of = _heads_first(g), _heads_first(o)
    # D_i = Σ_d dO_i ∘ O_i — one cheap elementwise pass in XLA
    dd = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                 axis=-1, keepdims=True)

    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, block_k=block_k, seq_len=s,
                          scale=scale),
        grid=(b * h, pl.cdiv(s, block_q)),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, dv), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, 1), lambda i, j, h=h: (i // h, 0, 0)),
            pl.BlockSpec((None, block_q, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=FLASH_KERNEL_NAMES[1],
    )(qf, kf, vf, mask, gf, lse, dd)

    dk, dv_ = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, block_q=block_q, seq_len=s,
                          scale=scale),
        grid=(b * h, pl.cdiv(s, block_k)),
        in_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, dv), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, s, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, block_k, 1), lambda i, j, h=h: (i // h, j, 0)),
            pl.BlockSpec((None, s, dv), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((None, s, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((None, block_k, dv), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, s, dv), q.dtype),
        ],
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=FLASH_KERNEL_NAMES[2],
    )(kf, vf, qf, mask, gf, lse, dd)

    unflat = lambda a: a.reshape(b, h, s, -1).transpose(0, 2, 1, 3)
    return unflat(dq), unflat(dk), unflat(dv_)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash(q, k, v, mask, block_q: int, block_k: int, scale: float):
    return _flash_fwd(q, k, v, mask, block_q, block_k, scale)[0]


def _flash_fwd_rule(q, k, v, mask, block_q, block_k, scale):
    out, lse = _flash_fwd(q, k, v, mask, block_q, block_k, scale)
    return out, (q, k, v, mask, out, lse)


def _flash_bwd_rule(block_q, block_k, scale, res, g):
    q, k, v, mask, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, mask, out, lse, g, block_q, block_k,
                            scale)
    return dq, dk, dv, jnp.zeros_like(mask)


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_causal_attention(q, k, v, block_q: int = 512, block_k: int = 512,
                           attn_mask: Optional[jnp.ndarray] = None,
                           scale: Optional[float] = None):
    """Pallas flash attention, fused fwd+bwd (see module docstring).
    q/k: [b, s, h, d_qk], v: [b, s, h, d_v] -> [b, s, h, d_v]; one set of
    kernels serves ``d_qk == d_v`` (128) and latent attention's 192/128.
    ``attn_mask``: optional [b, s] key-padding mask (1 = real); ``scale``
    multiplies the scores (default ``d_qk ** -0.5``).

    Default blocks are 512x512 — measured on v5e (h=8, d=128): 1.5x
    faster than 128x128 at s=4096 and 2.7x at s=8192 (bigger MXU tiles,
    fewer grid programs); ``_fit_block`` shrinks them automatically for
    shorter sequences.

    Sequences are padded up to a multiple of 128 so every Pallas block is
    lane/sublane-aligned on real TPU hardware (a non-power-of-two s like
    1000 would otherwise pick a 125-row block). Pallas dynamic slices
    CLAMP out-of-bounds starts, so blocks MUST divide the padded length
    exactly — padding then slicing is the safe shape-independent recipe.
    Padded keys are masked out; padded query rows are sliced away.
    """
    b, s, h, d = q.shape
    scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
    s_pad = -(-s // 128) * 128
    if attn_mask is None:
        mask = jnp.ones((b, s, 1), jnp.float32)
    else:
        mask = attn_mask.astype(jnp.float32)[:, :, None]
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        mask = jnp.pad(mask, [(0, 0), (0, s_pad - s), (0, 0)])
    out = _flash(q, k, v, mask, _fit_block(s_pad, block_q),
                 _fit_block(s_pad, block_k), scale)
    return out[:, :s] if s_pad != s else out


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
