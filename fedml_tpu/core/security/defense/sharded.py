"""Sharded robust aggregation — defenses that never materialize the full
update matrix on one device.

The engine's robust mode emits the round's raw client updates as a
[K, D] matrix. For CNN-sized models a single device holds it easily, but
for the LLM path D is billions — so the defense itself must run SPMD. The
trick: every defense in :mod:`.robust_agg` factors into

  1. per-coordinate statistics (median/trimmed-mean/sign votes) — trivially
     parallel over a feature-sharded matrix,
  2. a [K, K] pairwise-distance Gram (krum/bulyan/wbc/3σ) or per-row norms
     (norm-clip/outlier/RFA) — computed as a ``psum`` of per-shard partial
     sums (K² and K are tiny; D is what's sharded), followed by [K]-sized
     selection weights applied locally, or
  3. an iteration whose [D]-sized iterate stays feature-sharded and only
     exchanges [K] distance fragments per step (RFA's Weiszfeld loop,
     cclip's clipped mean, wbc's 2-means).

Cross-round defense state (FoolsGold's similarity history, cclip momentum,
SLSGD's previous global, cross-round's per-client previous updates) is a
DEVICE-RESIDENT, feature-sharded pytree (:func:`defense_state_init` /
:func:`defense_state_spec`) so stateful defenses fuse too: the engine
threads it through the fused multi-round ``lax.scan`` like ``client_states``
and checkpoints it for crash-resume.

``defend_matrix_sharded`` jits one ``shard_map`` over the mesh's device
axis with the matrix feature-sharded [K, D/n]; only [K, K]/[K] statistics
are replicated. Parity with the host path is asserted in tests.

Coverage: every defense in ``DEFENSE_TYPES`` has a sharded kernel. Two
caveats, both documented where they bite: ``weak_dp``/``crfl`` fold the
shard index into their noise key (like stochastic attacks, the stream
depends on the mesh layout — valid DP noise, but not bit-identical to the
single-host kernel), and ``soteria`` must see one full row at a time for
its per-client quantile (a scanned [D]-sized ``all_gather`` per row — peak
memory stays O(D), never O(K·D)).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import robust_agg

PyTree = Any

# canonical kernel name per accepted alias (mirrors FedMLDefender._dispatch)
_ALIASES = {
    "median": "coordinate_median",
    "geometric_median": "rfa",
    "robust_learning_rate": "rlr",
}

# every built-in defense now has a feature-sharded kernel. Grouped by how
# they factor over the shard (see module docstring); three_sigma keeps the
# distance-to-coordinate-median + median/MAD scores of the host kernel (a
# weaker mean/std variant would let byzantine rows widen the band).
_SHARDED = (
    # selection / per-coordinate statistics (exact)
    "krum", "multi_krum", "bulyan", "coordinate_median", "median",
    "trimmed_mean", "mean", "three_sigma", "rfa", "geometric_median",
    "norm_clip", "outlier_detection", "residual_reweight",
    "robust_learning_rate", "rlr", "wbc", "soteria",
    # stateful (device-resident cross-round state, see defense_state_init)
    "foolsgold", "cclip", "slsgd", "cross_round",
    # stochastic (per-shard noise streams, mesh-layout-dependent)
    "weak_dp", "crfl",
)

# defenses that carry cross-round device state
_STATEFUL = ("foolsgold", "cclip", "slsgd", "cross_round")


def _canon(defense_type: str) -> str:
    return _ALIASES.get(defense_type, defense_type)


def supports_sharded(defense_type: str) -> bool:
    return defense_type in _SHARDED


def sharded_defense_names() -> str:
    """Stable, human-readable list of the sharded-capable defenses (the
    one the error/log messages print)."""
    return ", ".join(sorted(set(_SHARDED)))


def is_stateful(defense_type: str) -> bool:
    return _canon(defense_type) in _STATEFUL


@dataclass(frozen=True)
class DefenseHP:
    """Hashable hyper-parameter bundle for the sharded kernels (frozen so
    the jitted-builder ``lru_cache`` can key on it). Defaults equal the
    host kernels' defaults in :mod:`.robust_agg` — drift here would
    silently break host/sharded parity."""

    byzantine_count: int = 0
    multi_k: int = 1
    trim_fraction: float = 0.1
    norm_bound: float = 5.0
    tau: float = 10.0
    stddev: float = 0.002
    alpha: float = 1.0
    rfa_iters: int = 8
    rfa_tol: float = 0.0
    cclip_iters: int = 3
    wbc_iters: int = 8
    soteria_frac: float = 0.5
    cr_threshold: float = -0.5
    z_threshold: float = 2.5
    resid_lam: float = 2.0
    rlr_threshold: int = 2

    @classmethod
    def from_defender(cls, dfd) -> "DefenseHP":
        from ....utils.confval import get_float
        return cls(
            byzantine_count=int(dfd.byzantine_count),
            multi_k=int(dfd.krum_param_m),
            trim_fraction=float(dfd.trim_fraction),
            norm_bound=float(dfd.norm_bound),
            tau=float(dfd.cclip_tau),
            stddev=float(dfd.dp_stddev),
            alpha=float(dfd.alpha),
            rfa_iters=int(getattr(dfd, "rfa_iters", 8)),
            rfa_tol=float(getattr(dfd, "rfa_tol", 0.0)),
            soteria_frac=get_float(dfd.args, "soteria_frac", 0.5),
            cr_threshold=get_float(dfd.args, "cross_round_threshold", -0.5),
        )


# ---------------------------------------------------------------------------
# cross-round defense state
# ---------------------------------------------------------------------------

def defense_state_init(defense_type: str, n_total: int,
                       d_pad: int) -> Dict[str, jnp.ndarray]:
    """Zero-initialized cross-round state for a stateful defense, GLOBAL
    (unsharded) shapes — the caller places leaves per
    :func:`defense_state_spec`. ``d_pad`` is the feature dim padded to a
    multiple of the device count; ``n_total`` the total client population
    (per-client-keyed state indexes by true client id). Empty dict for
    stateless defenses. Zeros reproduce the host kernels' cold start:
    FoolsGold/cross_round accumulate from nothing, cclip's momentum starts
    at the origin, SLSGD's ``has`` flag skips the prev-global mix."""
    d = _canon(defense_type)
    if d == "foolsgold":
        return {"history": jnp.zeros((n_total, d_pad), jnp.float32)}
    if d == "cclip":
        return {"momentum": jnp.zeros((d_pad,), jnp.float32)}
    if d == "slsgd":
        return {"prev": jnp.zeros((d_pad,), jnp.float32),
                "has": jnp.zeros((), jnp.float32)}
    if d == "cross_round":
        return {"prev": jnp.zeros((n_total, d_pad), jnp.float32),
                "has": jnp.zeros((n_total,), jnp.float32)}
    return {}


def defense_state_spec(defense_type: str, axis: str) -> Dict[str, P]:
    """PartitionSpec per state leaf: [*, D]-shaped leaves are
    feature-sharded over ``axis`` (the history matrices are the BIG state —
    N·D for FoolsGold — and must never gather), [K]/[N]/scalar leaves are
    replicated."""
    d = _canon(defense_type)
    if d == "foolsgold":
        return {"history": P(None, axis)}
    if d == "cclip":
        return {"momentum": P(axis)}
    if d == "slsgd":
        return {"prev": P(axis), "has": P()}
    if d == "cross_round":
        return {"prev": P(None, axis), "has": P()}
    return {}


# ---------------------------------------------------------------------------
# attack injection (on-device, per shard)
# ---------------------------------------------------------------------------

def _apply_attack_shard(attack_type: str, mat_s, byz_mask, key, scale,
                        axis: str):
    """Model-poisoning injection on a FEATURE shard of the update matrix —
    the on-device counterpart of FedMLAttacker.poison_updates. Row-wise
    transforms (flip/zero/replacement) are shard-exact; stochastic attacks
    fold the shard index into the key so noise differs per shard (the
    stream therefore depends on the mesh layout, unlike the host path —
    fine for attacks, which model an adversary, not a reproducible rng)."""
    from ..attack import (byzantine_flip, byzantine_random, byzantine_zero,
                          gaussian_noise, lazy_worker, model_replacement)
    key = jax.random.fold_in(key, jax.lax.axis_index(axis))
    if attack_type == "byzantine_random":
        return byzantine_random(mat_s, byz_mask, key, scale)
    if attack_type == "byzantine_zero":
        return byzantine_zero(mat_s, byz_mask)
    if attack_type == "byzantine_flip":
        return byzantine_flip(mat_s, byz_mask, scale)
    if attack_type == "model_replacement":
        boost = scale if scale != 1.0 else float(mat_s.shape[0])
        return model_replacement(mat_s, byz_mask, boost)
    if attack_type == "gaussian_noise":
        return gaussian_noise(mat_s, key, scale)
    if attack_type == "lazy_worker":
        return lazy_worker(mat_s, byz_mask, key)
    return mat_s


# ---------------------------------------------------------------------------
# per-shard kernel helpers (pure SPMD bodies, run INSIDE a shard_map)
# ---------------------------------------------------------------------------

# Partial-pour row masking (buffered-async defended pours): the pour
# program's [K] buffer shape is compiled once, so a partial pour (fewer
# than K arrivals — drained event heap, pour-timeout valve) pads with
# zero rows and hands the kernels a [K] validity mask. Masking semantics
# per kernel family, all reducing to the unmasked code at mask=None
# (the sync paths never pass a mask — their behavior is bit-identical):
#
# * weight-folded kernels (mean, norm_clip, rfa, cclip, soteria, rlr)
#   are mask-exact already: padded rows carry weight 0 (and sign(0) = 0
#   for rlr's votes), so they vanish from every weighted reduction.
# * coordinate sorts (median, trimmed_mean, slsgd) sort padded rows to
#   +inf and index the valid prefix dynamically (_masked_median /
#   _masked_sorted_window_mean).
# * robust statistics (three_sigma, outlier_detection,
#   residual_reweight) take their median/MAD over valid rows only.
# * Gram selections (krum, multi_krum, bulyan, wbc) add +1e30 to any
#   pair involving a padded row: every valid row's score gains the SAME
#   inflated tail, so the relative order among valid rows is preserved
#   and padded rows are never selected (while a selection size larger
#   than the valid count degrades toward the zero rows — a conservative,
#   smaller step — documented rather than hidden).
# * stateful scatters (foolsgold, cross_round) must not write padded
#   rows into per-client history; callers pad ``ids`` with ids DISJOINT
#   from the valid rows so the masked writes are exact no-ops.

def _masked_median(x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Median over rows with ``mask > 0`` (axis 0; works for [K] vectors
    and [K, D] matrices). Invalid rows sort to +inf; the two middle
    elements of the valid prefix are indexed dynamically."""
    key = mask if x.ndim == 1 else mask[:, None]
    big = jnp.where(key > 0, x, jnp.inf)
    s = jnp.sort(big, axis=0)
    n = jnp.maximum(jnp.sum(mask).astype(jnp.int32), 1)
    return 0.5 * (s[(n - 1) // 2] + s[n // 2])


def _masked_sorted_window_mean(mat_s: jnp.ndarray, mask: jnp.ndarray,
                               b) -> jnp.ndarray:
    """Per-coordinate mean of the sorted valid rows with ``b`` trimmed
    from each side (the masked trimmed-mean / SLSGD core). ``b`` may be
    traced; it is clamped to the valid count."""
    k = mat_s.shape[0]
    big = jnp.where(mask[:, None] > 0, mat_s, jnp.inf)
    s = jnp.sort(big, axis=0)
    n = jnp.maximum(jnp.sum(mask).astype(jnp.int32), 1)
    b = jnp.clip(jnp.asarray(b, jnp.int32), 0, (n - 1) // 2)
    idx = jnp.arange(k)[:, None]
    keep = ((idx >= b) & (idx < n - b)).astype(mat_s.dtype)
    s = jnp.where(jnp.isfinite(s), s, 0.0)
    return (jnp.sum(s * keep, axis=0)
            / jnp.maximum(jnp.sum(keep, axis=0), 1.0))


def _mask_dists(dists: jnp.ndarray,
                mask: Optional[jnp.ndarray]) -> jnp.ndarray:
    """+1e30 on every pair involving an invalid row: valid rows' score
    tails inflate identically (order preserved), invalid rows score off
    the chart and are never selected."""
    if mask is None:
        return dists
    valid = mask[:, None] * mask[None, :]
    return dists + (1.0 - valid) * 1e30


def _psum_dists(mat_s: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Replicated [K, K] squared-distance Gram from per-shard partials."""
    return jax.lax.psum(robust_agg.pairwise_sq_dists(mat_s), axis)


def _psum_row_norms(mat_s: jnp.ndarray, axis: str) -> jnp.ndarray:
    """Replicated [K] euclidean row norms from per-shard squared sums."""
    return jnp.sqrt(jax.lax.psum(jnp.sum(mat_s * mat_s, axis=1), axis))


def _selection_weights(defense_type: str, dists: jnp.ndarray,
                       weights: jnp.ndarray, byzantine_count: int,
                       multi_k: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[K] aggregation weights from the replicated [K, K] distance matrix,
    plus the [K] selection mask (the defense's per-client verdict)."""
    k = dists.shape[0]
    if defense_type in ("krum", "multi_krum"):
        m = 1 if defense_type == "krum" else multi_k
        closest = max(k - byzantine_count - 2, 1)
        sorted_d = jnp.sort(dists, axis=1)
        scores = jnp.sum(sorted_d[:, 1:closest + 1], axis=1)
        order = jnp.argsort(scores)
        sel = jnp.zeros(k).at[order[:m]].set(1.0)
        return sel * weights, sel
    return weights, jnp.ones(k, weights.dtype)  # mean


def _bulyan_shard(mat_s, weights, axis, hp: DefenseHP, mask=None):
    """Bulyan (El Mhamdi et al.) on a feature shard: iterated Multi-Krum
    selection from the psum'd [K, K] Gram (theta = K - 2f rows), then the
    per-coordinate nearest-to-median trimmed mean — purely local once the
    replicated selection is known. Mirrors robust_agg.bulyan row for row.
    Under a partial-pour ``mask``, padded rows are never preferred; a
    theta larger than the valid count pulls the trimmed mean toward the
    zero padding (a conservative, smaller step — see the mask notes)."""
    k = mat_s.shape[0]
    f = hp.byzantine_count
    theta = max(k - 2 * f, 1)
    scores = robust_agg.krum_scores_from_dists(
        _mask_dists(_psum_dists(mat_s, axis), mask), f)
    _, sel = jax.lax.top_k(-scores, theta)
    chosen = mat_s[sel]
    beta = max(theta - 2 * f, 1)
    med = jnp.median(chosen, axis=0)
    dist_to_med = jnp.abs(chosen - med[None])
    _, nearest = jax.lax.top_k(-dist_to_med.T, beta)  # [D/n, beta]
    vals = jnp.take_along_axis(chosen.T, nearest, axis=1)
    return jnp.mean(vals, axis=1), jnp.zeros(k).at[sel].set(1.0)


def _rfa_shard(mat_s, weights, axis, hp: DefenseHP, eps: float = 1e-8):
    """RFA / geometric median (Pillutla et al.): smoothed Weiszfeld as a
    ``lax.while_loop`` whose [D]-sized estimate stays feature-sharded —
    each iteration exchanges only the [K] squared-distance fragments
    (psum of per-shard partial sums); the estimate never gathers.
    Mask-exact under partial pours: padded rows carry weight 0.

    ``rfa_tol > 0`` adds a convergence early exit: stop once the
    iterate's global movement (psum'd across shards, so every shard
    agrees on the verdict) drops below the tolerance. Parity story vs
    the host kernel (:func:`robust_agg.geometric_median`): at the
    default ``rfa_tol: 0`` both run the exact fixed trip count and are
    bit-parity-tested; with a tolerance both kernels share the SAME
    movement rule, but the sharded psum associates float sums
    differently than the host's flat reduction, so near the exit
    boundary the two may differ by one iteration — parity then holds to
    the tolerance, not to the bit (documented, regression-tested)."""
    w = weights / jnp.maximum(jnp.sum(weights), 1e-12)
    v0 = jnp.einsum("k,kd->d", w, mat_s)

    def iterate(v):
        part = jnp.sum((mat_s - v[None]) ** 2, axis=1)
        dist = jnp.sqrt(jax.lax.psum(part, axis) + eps)
        beta = w / jnp.maximum(dist, eps)
        beta = beta / jnp.maximum(jnp.sum(beta), 1e-12)
        return jnp.einsum("k,kd->d", beta, mat_s)

    if hp.rfa_tol <= 0.0:  # fixed trip count: the bit-parity default
        def step(carry):
            i, v = carry
            return i + 1, iterate(v)

        _, v = jax.lax.while_loop(lambda c: c[0] < hp.rfa_iters, step,
                                  (jnp.int32(0), v0))
        return v

    def step_tol(carry):
        i, v, _ = carry
        new = iterate(v)
        moved = jnp.sqrt(jax.lax.psum(jnp.sum((new - v) ** 2), axis))
        return i + 1, new, moved

    def cond_tol(carry):
        i, _, moved = carry
        return (i < hp.rfa_iters) & (moved > hp.rfa_tol)

    _, v, _ = jax.lax.while_loop(
        cond_tol, step_tol, (jnp.int32(0), v0, jnp.float32(jnp.inf)))
    return v


def _three_sigma_shard(mat_s, weights, axis, mask=None):
    """host parity: score_i = ||u_i - coord_median||; keep within
    median(score) + 3 * 1.4826 * MAD(score). Masked: the median/MAD
    statistics run over valid rows only (zero padding would drag the
    coordinate median and shrink the band)."""
    if mask is None:
        med = jnp.median(mat_s, axis=0)
    else:
        med = _masked_median(mat_s, mask)
    part = jnp.sum((mat_s - med[None]) ** 2, axis=1)
    scores = jnp.sqrt(jax.lax.psum(part, axis))
    if mask is None:
        mu = jnp.median(scores)
        sd = 1.4826 * jnp.median(jnp.abs(scores - mu)) + 1e-12
        keep = (scores <= mu + 3.0 * sd).astype(weights.dtype)
    else:
        mu = _masked_median(scores, mask)
        sd = 1.4826 * _masked_median(jnp.abs(scores - mu), mask) + 1e-12
        keep = ((scores <= mu + 3.0 * sd)
                & (mask > 0)).astype(weights.dtype)
    return robust_agg.weighted_mean(mat_s, weights * keep), keep


def _norm_clip_shard(mat_s, weights, axis, hp: DefenseHP):
    norms = _psum_row_norms(mat_s, axis)
    scale = jnp.minimum(1.0, hp.norm_bound / jnp.maximum(norms, 1e-12))
    return robust_agg.weighted_mean(mat_s * scale[:, None], weights)


def _outlier_shard(mat_s, weights, axis, hp: DefenseHP, mask=None):
    norms = _psum_row_norms(mat_s, axis)
    if mask is None:
        mu = jnp.median(norms)
        sd = 1.4826 * jnp.median(jnp.abs(norms - mu)) + 1e-12
        keep = (jnp.abs(norms - mu)
                <= hp.z_threshold * sd).astype(mat_s.dtype)
    else:
        mu = _masked_median(norms, mask)
        sd = 1.4826 * _masked_median(jnp.abs(norms - mu), mask) + 1e-12
        keep = ((jnp.abs(norms - mu) <= hp.z_threshold * sd)
                & (mask > 0)).astype(mat_s.dtype)
    return robust_agg.weighted_mean(mat_s, weights * keep), keep


def _residual_shard(mat_s, weights, axis, hp: DefenseHP, mask=None):
    if mask is None:
        med = jnp.median(mat_s, axis=0)
    else:
        med = _masked_median(mat_s, mask)
    part = jnp.sum((mat_s - med[None]) ** 2, axis=1)
    resid = jnp.sqrt(jax.lax.psum(part, axis))
    if mask is None:
        mad = jnp.median(jnp.abs(resid - jnp.median(resid))) + 1e-12
    else:
        mad = _masked_median(jnp.abs(resid - _masked_median(resid, mask)),
                             mask) + 1e-12
    conf = jnp.clip(hp.resid_lam * mad / jnp.maximum(resid, 1e-12), 0.0, 1.0)
    if mask is not None:
        conf = conf * mask
    return robust_agg.weighted_mean(mat_s, weights * conf), conf


def _rlr_shard(mat_s, weights, axis, hp: DefenseHP):
    """Sign votes and the learning-rate flip are per-coordinate — fully
    local on the shard; nothing to reduce."""
    sign_sum = jnp.abs(jnp.sum(jnp.sign(mat_s), axis=0))
    lr_sign = jnp.where(sign_sum >= hp.rlr_threshold, 1.0, -1.0)
    return robust_agg.weighted_mean(mat_s, weights) * lr_sign


def _wbc_shard(mat_s, weights, axis, hp: DefenseHP, mask=None):
    """2-means over the rows with feature-sharded [2, D/n] centroids;
    assignments come from psum'd squared distances each iteration, the
    centroid update is a local per-coordinate mean. Masked: padded rows
    join neither the centroid seeding (their pairs score -1) nor the
    centroid means nor the majority vote."""
    k = mat_s.shape[0]
    valid = jnp.ones(k, mat_s.dtype) if mask is None else mask
    dists = _psum_dists(mat_s, axis)
    if mask is not None:
        dists = jnp.where(valid[:, None] * valid[None, :] > 0, dists, -1.0)
    flat_idx = jnp.argmax(dists)
    c = jnp.stack([mat_s[flat_idx // k], mat_s[flat_idx % k]])

    def assign_to(c):
        d0 = jax.lax.psum(jnp.sum((mat_s - c[0]) ** 2, axis=1), axis)
        d1 = jax.lax.psum(jnp.sum((mat_s - c[1]) ** 2, axis=1), axis)
        return jnp.argmin(jnp.stack([d0, d1]), axis=0)

    def body(_, c):
        one = ((assign_to(c) == 1).astype(mat_s.dtype) * valid)[:, None]
        zero = ((valid - one[:, 0]))[:, None]
        n1 = jnp.maximum(jnp.sum(one), 1.0)
        n0 = jnp.maximum(jnp.sum(zero), 1.0)
        return jnp.stack([jnp.sum(mat_s * zero, axis=0) / n0,
                          jnp.sum(mat_s * one, axis=0) / n1])

    c = jax.lax.fori_loop(0, hp.wbc_iters, body, c)
    assign = assign_to(c)
    majority = (jnp.sum(assign * valid)
                > jnp.sum(valid) / 2).astype(jnp.int32)
    keep = (assign == majority).astype(mat_s.dtype) * valid
    return robust_agg.weighted_mean(mat_s, weights * keep), keep


def _soteria_shard(mat_s, weights, axis, hp: DefenseHP, true_d: int):
    """Per-row magnitude quantile needs the WHOLE row: scan the K rows,
    all_gather one [D] row at a time (peak memory O(D), never O(K·D)),
    take the quantile over the TRUE feature dim (padding zeros would skew
    it), then prune locally on the shard."""
    def cut_for(i):
        row = jax.lax.all_gather(mat_s[i], axis, tiled=True)[:true_d]
        return jnp.quantile(jnp.abs(row), hp.soteria_frac)

    cuts = jax.lax.map(cut_for, jnp.arange(mat_s.shape[0]))
    pruned = jnp.where(jnp.abs(mat_s) >= cuts[:, None], mat_s, 0.0)
    return robust_agg.weighted_mean(pruned, weights)


def _weak_dp_shard(mat_s, weights, axis, hp: DefenseHP, key):
    """Weighted mean + gaussian noise generated per shard (shard index
    folded into the key, like stochastic attacks): valid DP noise of the
    configured stddev, but the stream depends on the mesh layout — not
    bit-identical to the single-host kernel."""
    agg = robust_agg.weighted_mean(mat_s, weights)
    key = jax.random.fold_in(key, jax.lax.axis_index(axis))
    return agg + hp.stddev * jax.random.normal(key, agg.shape)


def _crfl_shard(mat_s, weights, axis, hp: DefenseHP, key):
    """CRFL post-aggregation clip (global norm via psum) + per-shard
    smoothing noise (same mesh-layout caveat as weak_dp)."""
    agg = robust_agg.weighted_mean(mat_s, weights)
    norm = jnp.sqrt(jax.lax.psum(jnp.sum(agg * agg), axis))
    clipped = agg * jnp.minimum(1.0, hp.norm_bound
                                / jnp.maximum(norm, 1e-12))
    key = jax.random.fold_in(key, jax.lax.axis_index(axis))
    return clipped + hp.stddev * jax.random.normal(key, clipped.shape)


def _foolsgold_weights_shard(hist_rows, axis, eps: float = 1e-5):
    """robust_agg.foolsgold_weights on feature-sharded history rows: row
    norms and the [K, K] cosine Gram come from psum'd per-shard partials;
    the pardoning/logit rescale is [K]-sized and replicated. Any drift
    from the host kernel would silently break sharded/host parity."""
    k = hist_rows.shape[0]
    sq = jax.lax.psum(jnp.sum(hist_rows * hist_rows, axis=1), axis)
    normed = hist_rows / jnp.maximum(jnp.sqrt(sq), eps)[:, None]
    cs = jax.lax.psum(normed @ normed.T, axis) - jnp.eye(k)
    maxcs = jnp.max(cs, axis=1)
    pard = jnp.where(maxcs[None, :] > maxcs[:, None],
                     cs * maxcs[:, None] / jnp.maximum(maxcs[None, :], eps),
                     cs)
    wv = jnp.clip(1.0 - jnp.max(pard, axis=1), 0.0, 1.0)
    wv = wv / jnp.maximum(jnp.max(wv), eps)
    wv = jnp.clip(wv, eps, 1.0 - eps)
    logit = jnp.log(wv / (1.0 - wv)) + 0.5
    return jnp.clip(logit, 0.0, 1.0)


def _cclip_shard(mat_s, weights, axis, hp: DefenseHP, state):
    """Centered clipping with the momentum vector as feature-sharded
    cross-round state; per-iteration diff norms psum across shards."""
    v = state["momentum"]
    w = weights / jnp.maximum(jnp.sum(weights), 1e-12)

    def body(_, v):
        diff = mat_s - v[None]
        norms = jnp.sqrt(jax.lax.psum(jnp.sum(diff * diff, axis=1), axis))
        scale = jnp.minimum(1.0, hp.tau / jnp.maximum(norms, 1e-12))
        return v + jnp.einsum("k,kd->d", w, diff * scale[:, None])

    v = jax.lax.fori_loop(0, hp.cclip_iters, body, v)
    return v, {"momentum": v}


def _slsgd_shard(mat_s, weights, axis, hp: DefenseHP, state, mask=None):
    """SLSGD trimmed mean (per-coordinate, local) mixed with the previous
    global — a feature-sharded state leaf; round 0 (has == 0) skips the
    mix exactly like the host kernel's ``prev_global is None``. Masked:
    the trim window covers the sorted VALID rows only."""
    k = mat_s.shape[0]
    if mask is None:
        b = min(max(hp.byzantine_count, 1), (k - 1) // 2)
        s = jnp.sort(mat_s, axis=0)
        agg = jnp.mean(s[b:k - b] if b > 0 else s, axis=0)
    else:
        agg = _masked_sorted_window_mean(mat_s, mask,
                                         max(hp.byzantine_count, 1))
    mixed = jnp.where(state["has"] > 0,
                      (1.0 - hp.alpha) * state["prev"] + hp.alpha * agg, agg)
    return mixed, {"prev": mixed, "has": jnp.float32(1)}


def _cross_round_shard(mat_s, weights, axis, hp: DefenseHP, state, ids,
                       mask=None):
    """Cross-round consistency: per-client previous updates live in a
    feature-sharded [N, D/n] state matrix keyed by TRUE client id; cosines
    come from psum'd per-shard dot/norm fragments. Clients without history
    pass through, as on the host path. Masked rows neither write their
    (zero) row into the state nor mark history as present — callers pad
    ``ids`` disjoint from the valid rows, so the guarded writes are
    no-ops."""
    prev = state["prev"][ids]
    has = state["has"][ids]
    dot = jax.lax.psum(jnp.sum(mat_s * prev, axis=1), axis)
    n_cur = _psum_row_norms(mat_s, axis)
    n_prev = _psum_row_norms(prev, axis)
    cos = dot / (n_cur * n_prev + 1e-12)
    keep = jnp.where(has > 0,
                     (cos >= hp.cr_threshold).astype(mat_s.dtype), 1.0)
    if mask is None:
        new_state = {"prev": state["prev"].at[ids].set(mat_s),
                     "has": state["has"].at[ids].set(1.0)}
    else:
        keep = keep * mask
        new_state = {
            "prev": state["prev"].at[ids].set(
                jnp.where(mask[:, None] > 0, mat_s, prev)),
            "has": state["has"].at[ids].set(jnp.maximum(mask, has)),
        }
    return robust_agg.weighted_mean(mat_s, weights * keep), new_state, keep


def _foolsgold_shard(mat_s, weights, axis, state, ids, mask=None):
    """FoolsGold with the accumulated history as feature-sharded [N, D/n]
    state: add this round's (post-attack) rows into the clients' history
    FIRST — the host kernel scores similarities on the updated history —
    then down-weight mutually-similar clients. Masked rows add nothing to
    history (ids are padded disjoint, see the mask notes)."""
    add = mat_s if mask is None else mask[:, None] * mat_s
    hist_rows = state["history"][ids] + add
    new_state = {"history": state["history"].at[ids].set(hist_rows)}
    wv = _foolsgold_weights_shard(hist_rows, axis)
    return robust_agg.weighted_mean(mat_s, weights * wv), new_state, wv


# ---------------------------------------------------------------------------
# the unified per-shard kernel
# ---------------------------------------------------------------------------

def defend_shard_stateful(
    mat_s: jnp.ndarray,
    weights: jnp.ndarray,
    axis: str,
    defense_type: str,
    hp: Optional[DefenseHP] = None,
    state: Optional[Dict[str, jnp.ndarray]] = None,
    ids: Optional[jnp.ndarray] = None,
    key: Optional[jax.Array] = None,
    true_d: Optional[int] = None,
    row_mask: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray], jnp.ndarray]:
    """The per-shard defense kernel: [K, D/n] feature shard + replicated
    [K] weights (+ optional cross-round ``state``, sampled client ``ids``,
    noise ``key``) -> (defended aggregate shard [D/n], new state,
    [K] verdict). Pure SPMD body meant to run INSIDE an existing
    ``shard_map`` over ``axis`` — this is the ONE implementation shared by
    :func:`defend_matrix_sharded` (host-dispatch path) and the engine's
    fused robust round program; any drift between the two would silently
    break their client-for-client parity.

    The **verdict** is the defense's per-client effective inclusion in
    [0, 1] (1 = fully kept, 0 = excluded): the krum/bulyan selection mask,
    three_sigma/outlier/wbc/cross_round keep flags, residual confidences,
    foolsgold weights. Coordinate-wise and norm-shaping defenses (median,
    trimmed_mean, rfa, norm_clip, soteria, weak_dp, crfl, cclip, slsgd)
    have no per-client exclusion notion and report all-ones. It is
    replicated and [K]-sized — free to emit — and feeds the selection
    subsystem's reputation scores with zero extra dispatches.

    ``row_mask`` (optional [K], 1 = real row) marks partial-pour padding
    (buffered-async defended pours); ``None`` — every sync path — runs
    the exact unmasked code, bit-identical to before. See the mask notes
    above the helpers for the per-family semantics."""
    hp = hp or DefenseHP()
    state = state if state is not None else {}
    ones = jnp.ones(mat_s.shape[0], jnp.float32)
    mask = row_mask
    d = _canon(defense_type)
    if d == "mean":
        return robust_agg.weighted_mean(mat_s, weights), state, ones
    if d == "coordinate_median":
        if mask is None:
            return (robust_agg.coordinate_median(mat_s, weights)[0], state,
                    ones)
        return _masked_median(mat_s, mask), state, ones
    if d == "trimmed_mean":
        if mask is None:
            return (robust_agg.trimmed_mean(mat_s, weights,
                                            hp.trim_fraction)[0], state,
                    ones)
        n = jnp.sum(mask).astype(jnp.float32)
        b = jnp.floor(n * jnp.float32(hp.trim_fraction) + 1e-6)
        return _masked_sorted_window_mean(mat_s, mask, b), state, ones
    if d == "three_sigma":
        vec, keep = _three_sigma_shard(mat_s, weights, axis, mask=mask)
        return vec, state, keep
    if d == "bulyan":
        vec, sel = _bulyan_shard(mat_s, weights, axis, hp, mask=mask)
        return vec, state, sel
    if d == "rfa":
        return _rfa_shard(mat_s, weights, axis, hp), state, ones
    if d == "norm_clip":
        return _norm_clip_shard(mat_s, weights, axis, hp), state, ones
    if d == "outlier_detection":
        vec, keep = _outlier_shard(mat_s, weights, axis, hp, mask=mask)
        return vec, state, keep
    if d == "residual_reweight":
        vec, conf = _residual_shard(mat_s, weights, axis, hp, mask=mask)
        return vec, state, conf
    if d == "rlr":
        return _rlr_shard(mat_s, weights, axis, hp), state, ones
    if d == "wbc":
        vec, keep = _wbc_shard(mat_s, weights, axis, hp, mask=mask)
        return vec, state, keep
    if d == "soteria":
        if true_d is None:
            raise ValueError("soteria's per-row quantile needs true_d "
                             "(the unpadded feature dim)")
        return (_soteria_shard(mat_s, weights, axis, hp, int(true_d)),
                state, ones)
    if d == "weak_dp":
        return _weak_dp_shard(mat_s, weights, axis, hp, key), state, ones
    if d == "crfl":
        return _crfl_shard(mat_s, weights, axis, hp, key), state, ones
    if d == "foolsgold":
        vec, new_state, wv = _foolsgold_shard(mat_s, weights, axis, state,
                                              ids, mask=mask)
        return vec, new_state, wv
    if d == "cclip":
        vec, new_state = _cclip_shard(mat_s, weights, axis, hp, state)
        return vec, new_state, ones
    if d == "slsgd":
        vec, new_state = _slsgd_shard(mat_s, weights, axis, hp, state,
                                      mask=mask)
        return vec, new_state, ones
    if d == "cross_round":
        vec, new_state, keep = _cross_round_shard(mat_s, weights, axis, hp,
                                                  state, ids, mask=mask)
        return vec, new_state, keep
    # krum / multi_krum: selection weights from the psum'd (masked) Gram
    dists = _mask_dists(_psum_dists(mat_s, axis), mask)
    sel_w, sel = _selection_weights(d, dists, weights,
                                    hp.byzantine_count, hp.multi_k)
    return robust_agg.weighted_mean(mat_s, sel_w), state, sel


def defend_shard(mat_s: jnp.ndarray, weights: jnp.ndarray, axis: str,
                 defense_type: str, byzantine_count: int = 0,
                 multi_k: int = 1,
                 trim_fraction: float = 0.1) -> jnp.ndarray:
    """Back-compat stateless entry point (PR 2 signature): builds a
    :class:`DefenseHP` and drops the (empty) state. Stateful defenses must
    go through :func:`defend_shard_stateful`."""
    if is_stateful(defense_type):
        raise ValueError(f"{defense_type!r} carries cross-round state; "
                         "call defend_shard_stateful with a state pytree")
    hp = DefenseHP(byzantine_count=byzantine_count, multi_k=multi_k,
                   trim_fraction=trim_fraction)
    vec, _, _ = defend_shard_stateful(mat_s, weights, axis, defense_type,
                                      hp)
    return vec


# ---------------------------------------------------------------------------
# host-dispatch entry point (one shard_map over the mesh)
# ---------------------------------------------------------------------------

@lru_cache(maxsize=64)
def _build_sharded_fn(mesh: Mesh, axis: str, defense_type: str,
                      hp: DefenseHP, has_state: bool, true_d: int,
                      return_matrix: bool,
                      attack_type: Optional[str] = None,
                      attack_scale: float = 1.0,
                      has_mask: bool = False):
    """One compiled kernel per (mesh, defense, params); jit re-traces only
    on new shapes — without this cache every round would recompile. NOTE:
    inputs are NOT donated here — the cached kernel is shared by engines
    and tests, and donating would delete callers' arrays behind their
    backs; the fused engine path (which owns its buffers) donates."""
    state_spec = defense_state_spec(defense_type, axis) if has_state else {}

    def body(mat_s, weights, byz_mask, akey, dkey, state, ids, row_mask):
        # mat_s: [K, D/n] local shard
        if attack_type is not None:
            mat_s = _apply_attack_shard(attack_type, mat_s, byz_mask, akey,
                                        attack_scale, axis)
        vec, new_state, verdict = defend_shard_stateful(
            mat_s, weights, axis, defense_type, hp, state=state, ids=ids,
            key=dkey, true_d=true_d,
            row_mask=row_mask if has_mask else None)
        out = (vec, new_state, verdict)
        return out + (mat_s,) if return_matrix else out

    out_specs = (P(axis), state_spec, P())
    if return_matrix:
        out_specs = out_specs + (P(None, axis),)
    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None, axis), P(), P(), P(), P(), state_spec, P(), P()),
        out_specs=out_specs,
        check_vma=False,
    ))


def defend_matrix_sharded(
    mesh: Mesh,
    axis: str,
    mat: jnp.ndarray,
    weights: jnp.ndarray,
    defense_type: str,
    byzantine_count: int = 0,
    multi_k: int = 1,
    trim_fraction: float = 0.1,
    attack_type: Optional[str] = None,
    attack_scale: float = 1.0,
    byz_mask: Optional[jnp.ndarray] = None,
    attack_key: Optional[jax.Array] = None,
    hp: Optional[DefenseHP] = None,
    state: Optional[Dict[str, jnp.ndarray]] = None,
    ids: Optional[jnp.ndarray] = None,
    defense_key: Optional[jax.Array] = None,
    return_matrix: bool = False,
    return_verdict: bool = False,
    row_mask: Optional[jnp.ndarray] = None,
):
    """[K, D] (feature-sharded over ``axis``) -> defended aggregate [D]
    (feature-sharded). The caller owns placement; this never gathers D
    (except soteria's documented one-row-at-a-time scan). When
    ``attack_type`` is set, model poisoning is injected ON DEVICE on the
    sharded matrix before the defense (the adversarial-evaluation
    pipeline without any host round-trip).

    Returns ``vec`` for stateless defenses; ``(vec, new_state)`` for
    stateful ones (pass the previous round's ``state`` and the sampled
    client ``ids``, or both default to a cold start over ``K`` clients);
    with ``return_matrix=True`` the post-attack sharded matrix is appended
    (the contribution assessor's input — it must see what the defense
    saw); with ``return_verdict=True`` the [K] per-client verdict (see
    :func:`defend_shard_stateful`) is appended LAST — the selection
    subsystem's reputation input; ``row_mask`` marks partial-pour padding
    rows (see :func:`defend_shard_stateful`)."""
    if not supports_sharded(defense_type):
        raise ValueError(
            f"defense_type {defense_type!r} has no sharded kernel; host "
            f"fallback required. Sharded defenses: "
            f"{sharded_defense_names()}")

    if hp is None:
        hp = DefenseHP(byzantine_count=byzantine_count, multi_k=multi_k,
                       trim_fraction=float(trim_fraction))
    n = mesh.shape[axis]
    d = mat.shape[1]
    pad = (-d) % n
    stateful = is_stateful(defense_type)
    fn = _build_sharded_fn(mesh, axis, defense_type, hp, stateful, d,
                           bool(return_matrix),
                           attack_type, float(attack_scale),
                           has_mask=row_mask is not None)
    if pad:
        mat = jnp.pad(mat, ((0, 0), (0, pad)))
    mat = jax.device_put(mat, NamedSharding(mesh, P(None, axis)))
    k = mat.shape[0]
    if byz_mask is None:
        byz_mask = jnp.zeros(k, jnp.float32)
    if attack_key is None:
        attack_key = jax.random.PRNGKey(0)
    if defense_key is None:
        defense_key = jax.random.PRNGKey(0)
    if ids is None:
        ids = jnp.arange(k, dtype=jnp.int32)
    if stateful and state is None:
        # cold start must cover the LARGEST client id, not just K rows —
        # jax clamps out-of-range gather/scatter indices, which would
        # silently merge every too-large id into the last history row
        n_total = max(k, int(jnp.max(jnp.asarray(ids))) + 1)
        state = jax.tree_util.tree_map(
            lambda z, s: jax.device_put(z, NamedSharding(mesh, s)),
            defense_state_init(defense_type, n_total, d + pad),
            defense_state_spec(defense_type, axis))
    if row_mask is None:
        row_mask = jnp.ones(k, jnp.float32)
    out = fn(mat, jnp.asarray(weights, jnp.float32),
             jnp.asarray(byz_mask, jnp.float32), attack_key, defense_key,
             state if stateful else {}, jnp.asarray(ids, jnp.int32),
             jnp.asarray(row_mask, jnp.float32))
    vec, new_state, verdict = out[0], out[1], out[2]
    result = (vec[:d],)
    if stateful:
        result = result + (new_state,)
    if return_matrix:
        result = result + (out[3],)
    if return_verdict:
        result = result + (verdict,)
    return result[0] if len(result) == 1 else result
