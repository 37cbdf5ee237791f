"""The sparse expert layer's machinery: routing over all experts, a dropless
plan for the experts this chip holds, and the grouped product over them.

An expert-parallel rank holds ``experts_held`` of the layer's
``n_routed_experts`` (``first_expert`` on). It routes every token over ALL
experts, keeps the slots that chose one of its own, and computes their part
of the layer's result; what absent experts would add is left out (their
ranks add it in a deployment; nothing here stands in for them).

Dropless with static shapes: the held slots are laid out expert by expert,
each expert's rows starting on a ``tile_m`` boundary (``plan``: a slot's
row does not depend on the buffer's size). A row tile so belongs to one
expert, and the grouped product is a tiled matmul whose weight block is
chosen per row tile (``grouped_matmul``, a Pallas kernel; tiles past the
last used one are skipped and cost no HBM traffic). Tokens reach their rows
and results return to their tokens by gathers in both directions of the
autodiff (a slot and its row are a permutation of each other), never by a
scatter-add.

The row buffers follow the rows in use. ``compact_rows`` is twice the held
slots a uniform router would send plus every expert's tail tile, a
function of shapes alone; ``buffer_rows`` is the worst case (every slot of
every token held). Where the first is the smaller, ``routed_experts`` runs
the pass under ``jax.lax.cond(rows in use <= compact_rows)``: the same
rows in the same order through the same products at either size, so the
result is the same to the last bit, and a routing that overflows the
compact buffer takes the worst-case one and drops nothing.

A train step runs six grouped products a layer where the rows fit the
compact size: three forward, and three ``dx`` in the backward pass, which
works from the gate and up products the forward pass kept (two ``[compact_rows,
width]`` arrays a layer and step stay alive between the passes). At the
worst-case size nothing of that size is kept: the backward pass rebuilds
the two products there and runs the same pull-back (2 + 3).

An expert is a SwiGLU (``silu(x W_gate) * (x W_up)) W_down``, three
kernels) or, where the caller hands no ``w_gate`` (a static branch), the
non-gated ``relu(x W_up)^2 W_down`` of two: two grouped products forward
and two ``dx`` backward, from the one up product the forward pass kept.
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
STATS = ("slots_held", "load_max", "dropped", "layer_steps", "expert_steps",
         "compact_steps", "kept_steps")


class Plan(NamedTuple):
    """Where each held slot lives, whatever the row buffer's size."""
    slot_row: jnp.ndarray     # [T, k] int32: a held slot's row (0 if not held)
    slot_held: jnp.ndarray    # [T, k] bool
    ends: jnp.ndarray         # [held] int32: where each expert's tiles end
    num_tiles: jnp.ndarray    # [1] int32: tiles in use
    load: jnp.ndarray         # [held] int32: slots per held expert


class Rows(NamedTuple):
    """A plan laid into a buffer of R rows."""
    row_token: jnp.ndarray    # [R] int32: the token a row reads (0 if unused)
    row_used: jnp.ndarray     # [R] bool
    tile_group: jnp.ndarray   # [R / tile_m] int32: the local expert of a tile


def route(scores_in: jnp.ndarray, top_k: int, scaling: float,
          norm_topk: bool = True, bias=None, n_group: int = 0,
          topk_group: int = 0, scoring: str = "sigmoid"):
    """``scores_in`` [T, E] router logits -> (gates [T, k] float32, experts
    [T, k] int32): sigmoid scores in float32 (``scoring`` ``softmax``: a
    softmax over all E logits, a static branch), top-k over all experts,
    the chosen scores normalised to sum 1 and scaled.

    With ``bias`` [E] (a score-correction bias) or ``n_group > 1`` the
    choice is DeepSeek-V3's ``noaux_tc``: experts are chosen by ``s + b``,
    the ``n_group`` consecutive groups are scored by the sum of their two
    best ``s + b``, the best ``topk_group`` groups stay and the top-k is
    taken among their experts; the gates come from ``s`` without ``b``.
    Without either (a static branch) this is plain top-k on ``s``."""
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError(f"router scoring {scoring!r}: sigmoid or softmax")
    s = (jax.nn.softmax(scores_in.astype(jnp.float32), -1)
         if scoring == "softmax" else
         jax.nn.sigmoid(scores_in.astype(jnp.float32)))
    if bias is None and n_group <= 1:
        vals, idx = jax.lax.top_k(s, top_k)
    else:
        choice = s if bias is None else s + bias.astype(jnp.float32)
        if n_group > 1:
            t, e = choice.shape
            groups = choice.reshape(t, n_group, e // n_group)
            group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], -1)
            _, kept = jax.lax.top_k(group_score, topk_group)     # [T, g]
            stays = jnp.any(kept[:, :, None] == jnp.arange(n_group), 1)
            choice = jnp.where(stays[:, :, None], groups,
                               -jnp.inf).reshape(t, e)
        _, idx = jax.lax.top_k(choice, top_k)
        vals = jnp.take_along_axis(s, idx, -1)
    if norm_topk:
        vals = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20)
    return vals * scaling, idx.astype(jnp.int32)


def _ceil_to(n, tile_m: int):
    return -(-n // tile_m) * tile_m


def buffer_rows(tokens: int, top_k: int, held: int, tile_m: int) -> int:
    """Rows that hold any routing: every slot held, each expert's tail
    padded to a tile."""
    return _ceil_to(tokens * min(top_k, held) + held * tile_m, tile_m)


def compact_rows(tokens: int, top_k: int, held: int, n_experts: int,
                 tile_m: int) -> int:
    """Rows that hold twice the slots a uniform router sends to ``held`` of
    ``n_experts``, each expert's tail padded to a tile."""
    return _ceil_to(-(-2 * tokens * top_k * held // n_experts), tile_m) \
        + held * tile_m


def plan(experts: jnp.ndarray, first_expert: int, held: int,
         tile_m: int) -> Plan:
    """Give the slots that chose experts ``first_expert .. first_expert +
    held - 1`` their rows, expert by expert, in token order, each expert's
    rows starting on a tile; ``ends[-1]`` is the rows in use."""
    t, k = experts.shape
    local = experts - first_expert
    is_held = (local >= 0) & (local < held)
    flat = jnp.where(is_held, local, held).reshape(-1)          # [T*k]
    onehot = (flat[:, None] == jnp.arange(held)[None, :]).astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, 0),
                               jnp.minimum(flat, held - 1)[:, None], 1)[:, 0] - 1
    load = jnp.sum(onehot, 0)                                   # [held]
    padded = _ceil_to(load, tile_m)
    ends = jnp.cumsum(padded)
    row = (ends - padded)[jnp.minimum(flat, held - 1)] + rank
    return Plan(jnp.where(is_held, row.reshape(t, k), 0).astype(jnp.int32),
                is_held, ends.astype(jnp.int32),
                (ends[-1] // tile_m).astype(jnp.int32).reshape(1), load)


def place(p: Plan, rows: int, tile_m: int) -> Rows:
    """Lay the plan into a buffer of ``rows`` rows (a held slot whose row
    lies past the buffer gets none: the caller sizes it by ``ends[-1]``)."""
    row = jnp.where(p.slot_held, p.slot_row, rows).reshape(-1)
    token = jnp.arange(row.size, dtype=jnp.int32) // p.slot_row.shape[1]
    row_token = jnp.zeros((rows,), jnp.int32).at[row].set(token, mode="drop")
    row_used = jnp.zeros((rows,), bool).at[row].set(True, mode="drop")
    tile_start = jnp.arange(rows // tile_m, dtype=jnp.int32) * tile_m
    tile_group = jnp.minimum(
        jnp.searchsorted(p.ends, tile_start, side="right"),
        p.ends.shape[0] - 1)
    return Rows(row_token, row_used, tile_group.astype(jnp.int32))


# ------------------------------------------------------- tokens <-> rows ---

def _slot_sum(rows, weights, slot_row):
    """[T, H] float32: sum over a token's k slots of weight * its row, one
    slot at a time (a [T, k, H] gather would be k times the activations).
    A zero weight SELECTS zero: the row it points at may never have been
    written (``_gmm`` leaves unused tiles alone)."""
    return sum(jnp.where(weights[:, j, None] != 0,
                         rows[slot_row[:, j]].astype(jnp.float32)
                         * weights[:, j, None], 0.0)
               for j in range(slot_row.shape[1]))


def _combine(rows, gates, p: Plan):
    """rows [R, H], gates [T, k] -> [T, H] float32: each token's gated sum
    over its held slots."""
    return _slot_sum(rows, jnp.where(p.slot_held, gates, 0.0), p.slot_row)


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


def tile_rows(slots: int) -> int:
    """Rows a tile: 256 at training sizes (an expert's few hundred tokens
    read its kernels once), a power of two down to 16 for small inputs."""
    t = 16
    while t < 256 and t * 16 <= slots:
        t *= 2
    return t


# --------------------------------------------------------------- the pass ---

def _mm(r: Rows, p: Plan, tile_m: int, transpose_rhs: bool = False):
    """The grouped product over the plan's tiles: forward, or with
    ``transpose_rhs`` a cotangent pulled back to the product's rows."""
    return lambda x, w: _gmm(x, w, r.tile_group, p.num_tiles, tile_m,
                             transpose_rhs)


def _gate_up(rows: int, tile_m: int, x, w_gate, w_up, p: Plan):
    """The plan laid into ``rows`` rows, and the gate and up products of
    the rows' tokens, ``a = xs W_gate`` (None for a non-gated expert) and
    ``b = xs W_up`` [rows, width]."""
    r = place(p, rows, tile_m)
    xs = x[r.row_token]
    mm = _mm(r, p, tile_m)
    return r, None if w_gate is None else mm(xs, w_gate), mm(xs, w_up)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _pass_at(rows: int, tile_m: int, x, gates, w_gate, w_up, w_down, p: Plan):
    """One pass of the held experts over a buffer of ``rows`` rows -> the
    [T, H] float32 result and the gate and up products it came from. Jitted,
    as ``_pull_back`` and ``_rebuilt_pull_back`` are, so that a model's
    expert layers of one shape are traced and lowered once a size and
    direction, not once a layer."""
    r, a, b = _gate_up(rows, tile_m, x, w_gate, w_up, p)
    act = jnp.square(jax.nn.relu(b)) if a is None else jax.nn.silu(a) * b
    y = _mm(r, p, tile_m)(act, w_down)
    return _combine(y, gates, p), a, b


@functools.partial(jax.jit, static_argnums=(0, 1))
def _pull_back(rows: int, tile_m: int, a, b, g, gates, w_gate, w_up, w_down,
               p: Plan):
    """``g`` [T, H], the cotangent of the pass's result, pulled back to
    ``x`` and ``gates`` from the gate and up products ``a``, ``b`` [rows,
    width] alone: three ``dx`` products and no forward one. ``u``, the
    UNWEIGHTED cotangent rows through ``W_down^T``, serves both gradients:
    a slot's gate gets ``<h[row], u[row]>``, which is ``<y[row], g[token]>``
    re-associated through ``W_down`` (so ``y`` is not rebuilt), and ``h``
    gets ``u`` times the row's gate. Elementwise work and sums in float32,
    the products' operands in the compute dtype; a row's numbers stay in
    its row until ``_slot_sum`` and the gates' gather select the held
    slots' rows, so an unwritten row reaches no sum. A non-gated expert
    (``a`` None) has ``h = relu(b)^2``: ``d_gates = <h, u>``, ``d_b = 2
    relu(b) d_h``, two ``dx`` products."""
    f32, dtype = jnp.float32, b.dtype
    r = place(p, rows, tile_m)
    mm_t = _mm(r, p, tile_m, transpose_rhs=True)
    g_rows = jnp.where(r.row_used[:, None], g.astype(dtype)[r.row_token], 0)
    u = mm_t(g_rows, w_down).astype(f32)
    if a is None:
        return _pull_back_relu2(rows, b, u, gates, w_up, p, mm_t)
    a, b = a.astype(f32), b.astype(f32)
    sig = jax.nn.sigmoid(a)
    d_gates = jnp.where(p.slot_held,
                        jnp.sum(a * sig * b * u, -1)[p.slot_row], 0.0)
    d_h = u * _row_weights(rows, gates, p)[:, None]
    d_a = d_h * b * sig * (1 + a * (1 - sig))
    d_b = d_h * a * sig
    d_xs = mm_t(d_a.astype(dtype), w_gate) + mm_t(d_b.astype(dtype), w_up)
    dx = _slot_sum(d_xs, p.slot_held.astype(f32), p.slot_row)
    return dx.astype(dtype), d_gates.astype(gates.dtype)


def _row_weights(rows: int, gates, p: Plan):
    """[rows] float32: each held slot's gate at its row, 0 elsewhere."""
    return jnp.zeros((rows,), jnp.float32).at[
        jnp.where(p.slot_held, p.slot_row, rows).reshape(-1)].set(
        gates.astype(jnp.float32).reshape(-1), mode="drop")


def _pull_back_relu2(rows: int, b, u, gates, w_up, p: Plan, mm_t):
    """``_pull_back``'s second half for the non-gated expert ``relu(b)^2``:
    ``b`` the kept up product and ``u`` [rows, width] float32 the
    unweighted cotangent rows through ``W_down^T``."""
    f32, dtype = jnp.float32, b.dtype
    rb = jax.nn.relu(b.astype(f32))
    d_gates = jnp.where(p.slot_held, jnp.sum(rb * rb * u, -1)[p.slot_row],
                        0.0)
    d_b = u * _row_weights(rows, gates, p)[:, None] * (2.0 * rb)
    dx = _slot_sum(mm_t(d_b.astype(dtype), w_up), p.slot_held.astype(f32),
                   p.slot_row)
    return dx.astype(dtype), d_gates.astype(gates.dtype)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _rebuilt_pull_back(rows: int, tile_m: int, x, g, gates, w_gate, w_up,
                       w_down, p: Plan):
    """The same pull-back behind a rebuild of the gate and up products: what
    a pass that kept nothing runs."""
    _, a, b = _gate_up(rows, tile_m, x, w_gate, w_up, p)
    return _pull_back(rows, tile_m, a, b, g, gates, w_gate, w_up, w_down, p)


def _fits(p: Plan, compact: int):
    return p.ends[-1] <= compact


def _sized(p: Plan, compact: int, full: int, small, worst):
    """``small()`` where the rows in use fit the compact size, ``worst()``
    where they do not: a conditional on a scalar of the input, of which one
    branch runs; ``small()`` alone, and no conditional, where the worst case
    is no larger than the compact size."""
    if compact >= full:
        return small()
    return jax.lax.cond(_fits(p, compact), small, worst)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def expert_pass(compact: int, full: int, tile_m: int, x, gates, w_gate, w_up,
                w_down, p: Plan):
    """The pass at the size the plan needs, ``min(compact, full)`` rows or
    ``full``. Differentiable in ``x`` and ``gates`` alone: the experts'
    kernels are frozen and get no cotangent.

    Under differentiation a pass at the smaller size keeps its gate and up
    products (two ``[rows, width]`` arrays in the compute dtype) beside
    ``x``, ``gates`` and the plan, and the backward pass pulls the
    cotangent back from them (``_pull_back``: no forward product runs
    again). A pass at the worst-case size keeps nothing of its own size
    (the conditional's branches must agree in shape, so it hands on blanks
    of the compact shape); its backward pass makes the same choice of size
    again, rebuilds the two products there and runs the same pull-back."""
    args = (x, gates, w_gate, w_up, w_down, p)
    return _sized(p, compact, full,
                  lambda: _pass_at(min(compact, full), tile_m, *args)[0],
                  lambda: _pass_at(full, tile_m, *args)[0])


def _expert_pass_fwd(compact, full, tile_m, x, gates, w_gate, w_up, w_down, p):
    args = (x, gates, w_gate, w_up, w_down, p)
    rows = min(compact, full)

    def worst():
        blank = jnp.zeros((rows, w_up.shape[2]), x.dtype)
        return (_pass_at(full, tile_m, *args)[0],
                None if w_gate is None else blank, blank)

    y, a, b = _sized(p, compact, full,
                     lambda: _pass_at(rows, tile_m, *args), worst)
    return y, args + (a, b)


def _expert_pass_bwd(compact, full, tile_m, res, g):
    x, gates, w_gate, w_up, w_down, p, a, b = res
    rest = (gates, w_gate, w_up, w_down, p)

    def worst():
        # as jax.checkpoint does: the rebuilt products must not be merged
        # with the forward ones, which would keep those alive
        x_again, g_again = jax.lax.optimization_barrier((x, g))
        return _rebuilt_pull_back(full, tile_m, x_again, g_again, *rest)

    dx, d_gates = _sized(
        p, compact, full,
        lambda: _pull_back(min(compact, full), tile_m, a, b, g, *rest), worst)
    return dx, d_gates, None, None, None, None


expert_pass.defvjp(_expert_pass_fwd, _expert_pass_bwd)


def routed_experts(x, gates, experts, w_gate, w_up, w_down,
                   first_expert: int, n_experts: int):
    """The held experts' part of the layer: x [T, H], the routing of every
    token (``gates``, ``experts`` [T, k]) over ``n_experts`` and this
    rank's frozen SwiGLU kernels ``w_gate`` / ``w_up`` [G, H, I], ``w_down``
    [G, I, H] (``w_gate`` None: non-gated ``relu^2`` experts of ``w_up`` and
    ``w_down`` alone) -> ([T, H] float32, stats). The row buffers have
    ``compact_rows`` rows where this routing fits them and ``buffer_rows``
    where it does not; ``kept_steps`` is 1 where a backward pass will work
    from the forward's products (at the compact size, and where the worst
    case is no larger and ``compact_steps`` so reads 0)."""
    held = w_up.shape[0]
    t, k = experts.shape
    tile_m = tile_rows(experts.size)
    full = buffer_rows(t, k, held, tile_m)
    compact = compact_rows(t, k, held, n_experts, tile_m)
    p = plan(experts, first_expert, held, tile_m)
    routed = expert_pass(compact, full, tile_m, x, gates, w_gate, w_up,
                         w_down, p)
    kept = _fits(p, compact)          # always, where compact >= full
    fits = kept & (compact < full)
    rows = jnp.where(fits, compact, full)
    dropped = jnp.sum(p.slot_held & (p.slot_row >= rows))
    stats = {"slots_held": jnp.sum(p.load).astype(jnp.float32),
             "load_max": jnp.max(p.load).astype(jnp.float32),
             "dropped": dropped.astype(jnp.float32),
             "layer_steps": jnp.float32(1), "expert_steps": jnp.float32(held),
             "compact_steps": fits.astype(jnp.float32),
             "kept_steps": kept.astype(jnp.float32)}
    return routed, stats
