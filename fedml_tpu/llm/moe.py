"""The sparse expert layer's machinery: routing over all experts, a dropless
plan for the experts this chip holds, and the grouped product over them.

An expert-parallel rank holds ``experts_held`` of the layer's
``n_routed_experts`` (``first_expert`` on). It routes every token over ALL
experts, keeps the slots that chose one of its own, and computes their part
of the layer's result; what absent experts would add is left out (their
ranks add it in a deployment; nothing here stands in for them).

Dropless with static shapes: the held slots are laid out expert by expert,
each expert's rows starting on a ``tile_m`` boundary, in a buffer sized for
the worst case (every slot of every token held). A row tile so belongs to
one expert, and the grouped product is a tiled matmul whose weight block is
chosen per row tile (``grouped_matmul``, a Pallas kernel; tiles past the
last used one are skipped and cost no HBM traffic). Tokens reach their rows
and results return to their tokens by gathers in both directions of the
autodiff (a slot and its row are a permutation of each other), never by a
scatter-add.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core import kernels

# the grouped products' names in a device trace (forward; activation gradient)
GROUPED_KERNEL_NAMES = ("moe_grouped_fwd", "moe_grouped_dx")
# what ``routed_experts`` reports of one pass through one expert layer; the
# caller sums them over layers and steps
STATS = ("slots_held", "load_max", "dropped", "layer_steps", "expert_steps")


class Plan(NamedTuple):
    """Where each held slot lives in the row buffer."""
    row_token: jnp.ndarray    # [R] int32: the token a row reads (0 if unused)
    row_used: jnp.ndarray     # [R] bool
    slot_row: jnp.ndarray     # [T, k] int32: a held slot's row (0 if not held)
    slot_held: jnp.ndarray    # [T, k] bool
    tile_group: jnp.ndarray   # [R / tile_m] int32: the local expert of a tile
    num_tiles: jnp.ndarray    # [1] int32: tiles in use
    load: jnp.ndarray         # [held] int32: slots per held expert
    dropped: jnp.ndarray      # () int32: held slots without a row (always 0)


def route(scores_in: jnp.ndarray, top_k: int, scaling: float,
          norm_topk: bool = True):
    """``scores_in`` [T, E] router logits -> (gates [T, k] float32, experts
    [T, k] int32): sigmoid scores in float32, plain top-k over all experts,
    the chosen scores normalised to sum 1 and scaled."""
    s = jax.nn.sigmoid(scores_in.astype(jnp.float32))
    vals, idx = jax.lax.top_k(s, top_k)
    if norm_topk:
        vals = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20)
    return vals * scaling, idx.astype(jnp.int32)


def buffer_rows(tokens: int, top_k: int, held: int, tile_m: int) -> int:
    """Rows that hold any routing: every slot held, each expert's tail
    padded to a tile."""
    rows = tokens * min(top_k, held) + held * tile_m
    return -(-rows // tile_m) * tile_m


def plan(experts: jnp.ndarray, first_expert: int, held: int,
         tile_m: int) -> Plan:
    """Lay the slots that chose experts ``first_expert .. first_expert +
    held - 1`` into the row buffer, expert by expert, in token order."""
    t, k = experts.shape
    rows = buffer_rows(t, k, held, tile_m)
    local = experts - first_expert
    is_held = (local >= 0) & (local < held)
    flat = jnp.where(is_held, local, held).reshape(-1)          # [T*k]
    onehot = (flat[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0),
                               jnp.minimum(flat, held - 1)[:, None], 1)[:, 0] - 1
    load = jnp.sum(onehot, 0)                                   # [held]
    padded = -(-load // tile_m) * tile_m
    ends = jnp.cumsum(padded)
    starts = ends - padded
    held_flat = is_held.reshape(-1)
    row = jnp.where(held_flat,
                    starts[jnp.minimum(flat, held - 1)] + rank, rows)
    token = jnp.arange(t * k, dtype=jnp.int32) // k
    row_token = jnp.zeros((rows,), jnp.int32).at[row].set(token, mode="drop")
    row_used = jnp.zeros((rows,), bool).at[row].set(True, mode="drop")
    tile_start = jnp.arange(rows // tile_m, dtype=jnp.int32) * tile_m
    tile_group = jnp.minimum(
        jnp.searchsorted(ends, tile_start, side="right"), held - 1)
    placed = held_flat & (row < rows)
    return Plan(row_token, row_used,
                jnp.where(placed, row, 0).reshape(t, k).astype(jnp.int32),
                placed.reshape(t, k), tile_group.astype(jnp.int32),
                (ends[-1] // tile_m).astype(jnp.int32).reshape(1), load,
                jnp.sum(held_flat & ~placed).astype(jnp.int32))


# ------------------------------------------------------- tokens <-> rows ---

@jax.custom_vjp
def dispatch(x, row_token, slot_row, slot_held):
    """x [T, H] -> rows [R, H]: each row reads its token."""
    return x[row_token]


def _dispatch_fwd(x, row_token, slot_row, slot_held):
    return x[row_token], (slot_row, slot_held)


def _slot_sum(rows, weights, slot_row):
    """[T, H] float32: sum over a token's k slots of weight * its row, one
    slot at a time (a [T, k, H] gather would be k times the activations).
    A zero weight SELECTS zero: the row it points at may never have been
    written (``_gmm`` leaves unused tiles alone)."""
    return sum(jnp.where(weights[:, j, None] != 0,
                         rows[slot_row[:, j]].astype(jnp.float32)
                         * weights[:, j, None], 0.0)
               for j in range(slot_row.shape[1]))


def _dispatch_bwd(res, g):
    slot_row, slot_held = res
    dx = _slot_sum(g, slot_held.astype(jnp.float32), slot_row)
    return dx.astype(g.dtype), None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(rows, gates, row_token, row_used, slot_row, slot_held):
    """rows [R, H], gates [T, k] -> [T, H] float32: each token's gated sum
    over its held slots."""
    return _combine(rows, gates, slot_row, slot_held)


def _combine(rows, gates, slot_row, slot_held):
    return _slot_sum(rows, jnp.where(slot_held, gates, 0.0), slot_row)


def _combine_fwd(rows, gates, row_token, row_used, slot_row, slot_held):
    return (_combine(rows, gates, slot_row, slot_held),
            (rows, gates, row_token, row_used, slot_row, slot_held))


def _combine_bwd(res, g):
    rows, gates, row_token, row_used, slot_row, slot_held = res
    w = jnp.where(slot_held, gates, 0.0).reshape(-1)
    row_w = jnp.zeros(rows.shape[:1], jnp.float32).at[
        jnp.where(slot_held, slot_row, rows.shape[0]).reshape(-1)].set(
        w, mode="drop")
    d_rows = jnp.where(
        row_used[:, None],
        g.astype(rows.dtype)[row_token].astype(jnp.float32) * row_w[:, None],
        0.0)
    d_gates = jnp.stack(
        [jnp.sum(rows[slot_row[:, j]].astype(jnp.float32) * g, -1)
         for j in range(slot_row.shape[1])], -1)
    d_gates = jnp.where(slot_held, d_gates, 0.0)
    return (d_rows.astype(rows.dtype), d_gates.astype(gates.dtype),
            None, None, None, None)


combine.defvjp(_combine_fwd, _combine_bwd)


# --------------------------------------------------------- grouped matmul ---

def _fit(dim: int, want: int = 1024) -> int:
    """The whole of a small dimension, else the largest 128-multiple block
    <= ``want`` that divides it."""
    if dim <= want:
        return dim
    b = (want // 128) * 128
    while b >= 128 and dim % b:
        b -= 128
    return b if b >= 128 else dim


def _gmm_kernel(tile_group_ref, num_tiles_ref, x_ref, w_ref, o_ref, acc_ref,
                *, transpose_rhs: bool, n_k: int):
    import jax.experimental.pallas as pl

    del tile_group_ref
    i, k = pl.program_id(0), pl.program_id(2)

    @pl.when(k == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < num_tiles_ref[0])
    def _():
        dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], dims,
            preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gmm(x, w, tile_group, num_tiles, tile_m: int, transpose_rhs: bool):
    """x [R, C] @ w[group of the row's tile] -> [R, O]; w is [G, C, O], or
    [G, O, C] with ``transpose_rhs``. Tiles >= ``num_tiles`` cost nothing:
    their operands are not fetched and their rows are left UNWRITTEN (the
    skipped steps all point at the last tile's first block), so a caller
    selects the rows it placed and never multiplies an unused row by 0."""
    import jax.experimental.pallas as pl
    import jax.experimental.pallas.tpu as pltpu

    r, c = x.shape
    o = w.shape[1] if transpose_rhs else w.shape[2]
    tc, to = _fit(c), _fit(o)
    n_k = c // tc
    w = w.astype(x.dtype)

    def live(i, nt):
        return i < nt[0]

    def x_map(i, j, k, tg, nt):
        on = live(i, nt)
        return (jnp.where(on, i, jnp.maximum(nt[0] - 1, 0)),
                jnp.where(on, k, 0))

    def w_map(i, j, k, tg, nt):
        on = live(i, nt)
        g = tg[jnp.where(on, i, jnp.maximum(nt[0] - 1, 0))]
        kk, jj = jnp.where(on, k, 0), jnp.where(on, j, 0)
        return (g, jj, kk) if transpose_rhs else (g, kk, jj)

    def o_map(i, j, k, tg, nt):
        on = live(i, nt)
        return jnp.where(on, i, r // tile_m - 1), jnp.where(on, j, 0)

    w_block = (None, to, tc) if transpose_rhs else (None, tc, to)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs, n_k=n_k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(r // tile_m, o // to, n_k),
            in_specs=[pl.BlockSpec((tile_m, tc), x_map),
                      pl.BlockSpec(w_block, w_map)],
            out_specs=pl.BlockSpec((tile_m, to), o_map),
            scratch_shapes=[pltpu.VMEM((tile_m, to), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((r, o), x.dtype),
        interpret=kernels.interpret(),
        compiler_params=kernels.tpu_compiler_params(),
        name=GROUPED_KERNEL_NAMES[1 if transpose_rhs else 0],
    )(tile_group, num_tiles, x, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def grouped_matmul(x, w, tile_group, num_tiles, tile_m: int):
    """Rows [R, C] through FROZEN expert kernels ``w`` [G, C, O], the kernel
    of each ``tile_m`` rows named by ``tile_group``. Differentiable in ``x``
    alone: the experts train no weight here, so no weight gradient is taken
    (the caller stops the gradient at ``w``)."""
    return _gmm(x, w, tile_group, num_tiles, tile_m, False)


def _grouped_fwd(x, w, tile_group, num_tiles, tile_m):
    return (_gmm(x, w, tile_group, num_tiles, tile_m, False),
            (w, tile_group, num_tiles))


def _grouped_bwd(tile_m, res, g):
    w, tile_group, num_tiles = res
    return (_gmm(g, w, tile_group, num_tiles, tile_m, True),
            jnp.zeros_like(w), None, None)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def tile_rows(slots: int) -> int:
    """Rows a tile: 256 at training sizes (an expert's few hundred tokens
    read its kernels once), a power of two down to 16 for small inputs."""
    t = 16
    while t < 256 and t * 16 <= slots:
        t *= 2
    return t


def routed_experts(x, gates, experts, w_gate, w_up, w_down, first_expert: int):
    """The held experts' part of the layer: x [T, H], the routing of every
    token (``gates``, ``experts`` [T, k]) and this rank's frozen SwiGLU
    kernels ``w_gate`` / ``w_up`` [G, H, I], ``w_down`` [G, I, H] ->
    ([T, H] float32, stats). Rematerialised: the backward pass rebuilds the
    row buffers from ``x`` and the plan instead of keeping three
    worst-case-sized buffers a layer alive."""
    held = w_gate.shape[0]
    tile_m = tile_rows(experts.size)
    p = plan(experts, first_expert, held, tile_m)
    w_gate, w_up, w_down = (jax.lax.stop_gradient(w)
                            for w in (w_gate, w_up, w_down))

    @jax.checkpoint
    def rows_through_experts(x, gates):
        xs = dispatch(x, p.row_token, p.slot_row, p.slot_held)
        mm = functools.partial(grouped_matmul, tile_group=p.tile_group,
                               num_tiles=p.num_tiles, tile_m=tile_m)
        h = jax.nn.silu(mm(xs, w_gate)) * mm(xs, w_up)
        return combine(mm(h, w_down), gates, p.row_token, p.row_used,
                       p.slot_row, p.slot_held)

    stats = {"slots_held": jnp.sum(p.load).astype(jnp.float32),
             "load_max": jnp.max(p.load).astype(jnp.float32),
             "dropped": p.dropped.astype(jnp.float32),
             "layer_steps": jnp.float32(1), "expert_steps": jnp.float32(held)}
    return rows_through_experts(x, gates), stats
