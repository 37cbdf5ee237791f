"""Plain float32 reference of one expert-parallel rank of Keye-VL-2.0's
language model (``model_type`` ``KeyeVL2``) under LoRA.

Token embedding, pre-norm decoder layers, a final RMSNorm and an untied
head. Every layer is learned sparse attention then the expert block. With
``x`` the layer's input, 32 query heads and 4 key-value heads of 128:

    h   = RMSNorm(x)                          (eps ``rms_norm_eps``)
    q   = h W_q, k = h W_k, v = h W_v         as heads of ``head_dim``
    q, k: RMSNorm over each head's 128 dims (the family's q/k norm), then
          rotary (half-split convention) over the whole head at
          ``rope_theta`` (``mrope_section`` gives a text token one position
          in each of its three sections: one plain rotary)
    the indexer, on h with no gradient (``sa_config``):
    qi  = rot(h W_iq)      16 index heads of 64, rotary on the first 32
    ki  = rot(LayerNorm(h W_ik))   one index key of 64, the same rotary;
          the LayerNorm has a weight and a bias
    w   = h W_iw * 16^-1/2 * 64^-1/2
    I_tu = sum_j w_tj relu(qi_tj . ki_u)      for u <= t
    S_t = the min(topk, t + 1) positions of the largest I_t., exactly: the
          threshold is the kk-th value ``lax.top_k`` gives, every score above
          it is kept and, of those equal to it, the earliest (-0 is 0)
    o_ta = sum_{u in S_t} softmax_u(q_ta . k_u,g(a) / sqrt(128)) v_u,g(a)
          with g(a) = a // 8;    x <- x + concat_a(o) W_o

    h2  = RMSNorm(x)
    p   = softmax(h2 W_r) over ALL published experts, float32; the
          ``num_experts_per_tok`` largest; gates p_e / sum of the chosen
          (``norm_topk_prob``);
    x  <- x + sum over e chosen and held here of g_e SwiGLU_e(h2)
          (width ``moe_intermediate_size``); no shared expert.

This rank holds experts ``first_expert .. first_expert + num_experts - 1``
of ``published.num_experts``; what the absent ones would add is left out.
LoRA on ``q k v o`` of every layer: the frozen product plus ``(x @ a) @ b *
(alpha / rank)``; the norms, the indexer, the router and the experts are
frozen. The loss is the mean next-token cross-entropy over the sliced
vocabulary.

Departures, as the configuration file states them: no vision tower (the
traffic is text); the index q and k unrotated by a Hadamard transform and
not rounded to float8 (an orthogonal rotation leaves the scores as they
are; float8 is an inference-time choice).

Imports nothing of ``fedml_tpu``; no kernel, no cache, no batching. The
frozen tree is bfloat16, in the layout the driver hands to the system as
is; each layer is upcast where it is used and recomputed in the backward
pass but for its selection, which is kept as bits. Queries run in blocks of
``reference_q_block`` rows, the heads of one key-value head together; each
quarter of the rows' blocks against the keys up to that quarter's end (a
later key is never selected); the experts run one at a time over
the tokens that chose them (over every token where an expert drew more
than ``reference_expert_rows``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(cfg):
    sa = cfg["sa_config"]
    return {"h": cfg["hidden_size"], "nh": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "ih": sa["indexer_num_heads"], "id": sa["indexer_head_dim"],
            "width": cfg["moe_intermediate_size"],
            "held": cfg["num_experts"],
            "experts": cfg["published"]["num_experts"]}


def _attn_shapes(cfg):
    d = _dims(cfg)
    return {"q": (d["h"], d["nh"], d["d"]), "k": (d["h"], d["kv"], d["d"]),
            "v": (d["h"], d["kv"], d["d"]), "o": (d["nh"] * d["d"], d["h"])}


def init_frozen(key, cfg):
    """The frozen base from the seed: normal with ``initializer_range``
    rounded to bfloat16, RMSNorm scales at 1, the indexer's LayerNorm
    weight ``1 + 0.1 N`` and bias ``0.1 N`` (drawn, so that both are seen).
    The router's column for expert e has its std scaled by ``0.8 + 0.4
    u_e`` (``u`` a seeded permutation of ``0 .. 1``). The embedding rows
    have std 1, and the two projections that write into the residual
    stream (attention's ``o``, the experts' ``down``) std
    ``initializer_range / sqrt(2 * published layers)``, GPT-2's scaled
    init: with every weight at ``initializer_range`` the near-uniform
    softmax of random q and k adds the row's mean to every token, the
    shared part outgrows the token's own by layer 1, and every token then
    picks the same experts, so this rank's load is whatever the seed
    makes it. Call under one ``jax.jit``."""
    std = cfg.get("initializer_range", 0.02)
    d = _dims(cfg)
    n = [0]

    def fresh():
        n[0] += 1
        return jax.random.fold_in(key, n[0])

    def normal(shape, scale=1.0):
        w = jax.random.normal(fresh(), shape, jnp.float32) * std * scale
        return w.astype(jnp.bfloat16)

    def ones(m):
        return {"scale": jnp.ones((m,), jnp.bfloat16)}

    out = (2 * cfg["published"]["num_hidden_layers"]) ** -0.5
    p = {"embed": {"embedding": normal((cfg["vocab_size"], d["h"]),
                                       1.0 / std)}}
    for layer in range(cfg["num_hidden_layers"]):
        attn = {k: {"kernel": normal(s, out if k == "o" else 1.0)}
                for k, s in _attn_shapes(cfg).items()}
        attn.update(
            q_norm=ones(d["d"]), k_norm=ones(d["d"]),
            index_q={"kernel": normal((d["h"], d["ih"], d["id"]))},
            index_k={"kernel": normal((d["h"], d["id"]))},
            index_norm={
                "scale": (1.0 + 0.1 * jax.random.normal(
                    fresh(), (d["id"],))).astype(jnp.bfloat16),
                "bias": (0.1 * jax.random.normal(
                    fresh(), (d["id"],))).astype(jnp.bfloat16)},
            index_w={"kernel": normal((d["h"], d["ih"]))})
        u = jax.random.permutation(
            fresh(), jnp.arange(d["experts"], dtype=jnp.float32)
        ) / max(d["experts"] - 1, 1)
        p[f"layer_{layer}"] = {
            "attn": attn, "ln_attn": ones(d["h"]), "ln_mlp": ones(d["h"]),
            "moe": {
                "router": {"kernel": normal((d["h"], d["experts"]),
                                            0.8 + 0.4 * u[None, :])},
                "experts_gate": normal((d["held"], d["h"], d["width"])),
                "experts_up": normal((d["held"], d["h"], d["width"])),
                "experts_down": normal((d["held"], d["width"], d["h"]),
                                       out)}}
    p["ln_f"] = ones(d["h"])
    p["lm_head"] = {"kernel": normal((d["h"], cfg["vocab_size"]))}
    return p


def init_trainable(key, cfg):
    """Adapters in the middle of a fine-tune (``a`` normal with std 1/rank,
    ``b`` normal with std ``lora_b_std``: at ``b = 0`` every ``a`` has a
    zero gradient), float32."""
    rank = cfg["lora_rank"]
    p, n = {}, 0
    for layer in range(cfg["num_hidden_layers"]):
        pairs = {}
        for name, shape in _attn_shapes(cfg).items():
            n += 1
            ka, kb = jax.random.split(jax.random.fold_in(key, n))
            pairs[name] = {
                "lora_a": jax.random.normal(ka, (shape[0], rank),
                                            jnp.float32) / rank,
                "lora_b": jax.random.normal(
                    kb, (rank, math.prod(shape[1:])), jnp.float32)
                * cfg["lora_b_std"]}
        p[f"layer_{layer}"] = {"attn": pairs}
    return p


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps)
            * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32))


def _rope(x, theta):
    """x [b, s, heads, d]; positions 0..s-1; half-split rotation of all d."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def route(logits, cfg):
    """-> (gates [T, k], experts [T, k]): softmax over every published
    expert's logit, top-k, the chosen renormalised (``fault_sigmoid_router``:
    sigmoid scores, the fault the calibration plants)."""
    p = (jax.nn.sigmoid(logits) if cfg.get("fault_sigmoid_router")
         else jax.nn.softmax(logits, -1))
    vals, idx = jax.lax.top_k(p, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        vals = vals / jnp.sum(vals, -1, keepdims=True)
    return vals, idx


def make_model(cfg):
    d = _dims(cfg)
    scale = cfg["lora_alpha"] / cfg["lora_rank"]
    eps = cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    first = cfg.get("first_expert", 0)
    q_block = cfg.get("reference_q_block", 512)
    expert_rows = cfg.get("reference_expert_rows", 512)
    group = d["nh"] // d["kv"]

    def segments(s):
        """-> (rows of a query block, ``[(first block, end block, keys)]``
        of each quarter of the rows): a query block needs the keys up to its
        last row alone, so a quarter's rows take the keys up to the
        quarter's end (one part where the quarters are not whole blocks and
        whole bytes of bits)."""
        n = -(-s // min(q_block, s))
        qb = s // n
        parts = 4 if n % 4 == 0 and s % 32 == 0 else 1
        step = n // parts
        return qb, [(j * step, (j + 1) * step, (j + 1) * step * qb)
                    for j in range(parts)]

    def blocks(a, qb, lo, hi):
        """Rows ``lo * qb .. hi * qb`` of ``a`` [b, s, ...] as ``hi - lo``
        blocks of ``qb``: [blocks, b, qb, ...]."""
        return jnp.moveaxis(a[:, lo * qb:hi * qb].reshape(
            (a.shape[0], hi - lo, qb) + a.shape[2:]), 1, 0)

    def mm(x, w, quant):
        """``x @ w``; the control routes it through its lower precision."""
        f = lambda a, b: jnp.dot(a, b, precision=HIGHEST)  # noqa: E731
        return f(x, w) if quant is None else quant(f)(x, w)

    def ein(spec, a, b, quant):
        f = lambda x, y: jnp.einsum(  # noqa: E731
            spec, x, y, precision=HIGHEST)
        return f(a, b) if quant is None else quant(f)(a, b)

    def proj(x, base, lora, quant):
        w = base["kernel"].astype(jnp.float32)
        y = mm(x, w.reshape(w.shape[0], -1), quant)
        if lora is None:
            return y
        return y + mm(mm(x, lora["lora_a"], quant), lora["lora_b"],
                      quant) * scale

    def select(h, bp, quant):
        """bool [b, s, s]: ``S_t`` of every query, from the indexer."""
        b, s, _ = h.shape
        topk = min(int(cfg["sa_config"]["topk"]), s)
        rot = d["id"] // 2
        qi = proj(h, bp["index_q"], None, quant).reshape(b, s, d["ih"],
                                                          d["id"])
        ki = _layer_norm(proj(h, bp["index_k"], None, quant),
                         bp["index_norm"], eps)[:, :, None, :]
        w = proj(h, bp["index_w"], None, quant) * (d["ih"] * d["id"]) ** -0.5
        qi, ki = (jnp.concatenate([_rope(a[..., :rot], theta), a[..., rot:]],
                                  -1) for a in (qi, ki))
        ki = ki[:, :, 0]
        qb, quarters = segments(s)
        parts = []
        for lo, hi, keys in quarters:
            cols = jnp.arange(keys)

            def block(inp, keys=keys, cols=cols):
                qi_b, w_b, rows = inp        # [b, qb, H, D], [b, qb, H], [qb]
                dots = ein("bthd,bud->bhtu", qi_b, ki[:, :keys], quant)
                if not cfg.get("fault_no_relu"):
                    dots = jax.nn.relu(dots)
                score = jnp.einsum("bhtu,bth->btu", dots, w_b,
                                   precision=HIGHEST)
                valid = cols[None, None, :] <= rows[None, :, None]
                score = jnp.where(valid, jnp.where(score == 0, 0.0, score),
                                  -jnp.inf)
                kk = jnp.minimum(topk, rows + 1)[None, :, None]
                top = jax.lax.top_k(score, min(topk, keys))[0]
                tau = jnp.take_along_axis(
                    top, jnp.broadcast_to(kk - 1, top.shape[:2] + (1,)), -1)
                above = score > tau
                tie = score == tau
                need = kk - jnp.sum(above, -1, keepdims=True)
                keep = above | (tie & (jnp.cumsum(tie, -1) <= need))
                return jnp.pad(keep, ((0, 0), (0, 0), (0, s - keys)))

            parts.append(jax.lax.map(block, (
                blocks(qi, qb, lo, hi), blocks(w, qb, lo, hi),
                jnp.arange(lo * qb, hi * qb).reshape(hi - lo, qb))))
        keep = jnp.concatenate(parts, 0)
        return jax.lax.stop_gradient(
            jnp.moveaxis(keep, 0, 1).reshape(b, s, s))

    def attention(x, bp, lp, quant):
        b, s, _ = x.shape
        q = proj(x, bp["q"], lp["q"], quant).reshape(b, s, d["nh"], d["d"])
        k = proj(x, bp["k"], lp["k"], quant).reshape(b, s, d["kv"], d["d"])
        v = proj(x, bp["v"], lp["v"], quant).reshape(b, s, d["kv"], d["d"])
        q = _rope(_rms(q, bp["q_norm"]["scale"], eps), theta)
        k = _rope(_rms(k, bp["k_norm"]["scale"], eps), theta)
        # kept for the backward pass as bits, eight keys to a byte
        keep = checkpoint_name(jnp.packbits(
            select(jax.lax.stop_gradient(x), bp, quant), -1), "selection")

        @jax.checkpoint
        def heads(inp):
            # [b, qb, g, d], [b, keys, d] twice, [b, qb, keys]
            q_g, k_g, v_g, keep_b = inp
            scores = ein("bqhd,bkd->bhqk", q_g, k_g, quant) / math.sqrt(d["d"])
            scores = jnp.where(keep_b[:, None], scores, -jnp.inf)
            probs = jax.nn.softmax(scores, -1)
            return ein("bhqk,bkd->bqhd", probs, v_g, quant)

        qb, quarters = segments(s)
        parts = []
        for lo, hi, keys in quarters:
            k_s, v_s = (jnp.moveaxis(a[:, :keys], 2, 0) for a in (k, v))

            def rows(inp, keys=keys, k_s=k_s, v_s=v_s):
                q_b, bits = inp            # [b, qb, nh, d], [b, qb, s / 8]
                keep_b = jnp.unpackbits(bits[..., :keys // 8], -1,
                                        count=keys).astype(bool)
                q_b = jnp.moveaxis(
                    q_b.reshape(b, qb, d["kv"], group, d["d"]), 2, 0)
                out = jax.lax.map(lambda g: heads(g + (keep_b,)),
                                  (q_b, k_s, v_s))
                return jnp.moveaxis(out, 0, 2).reshape(b, qb,
                                                       d["nh"] * d["d"])

            parts.append(jax.lax.map(rows, (blocks(q, qb, lo, hi),
                                            blocks(keep, qb, lo, hi))))
        out = jnp.concatenate(parts, 0)
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, d["nh"] * d["d"])
        return proj(out, bp["o"], lp["o"], quant)

    def experts(x, bp, quant):
        """The held experts' gated part, one expert at a time. An expert
        that at most ``expert_rows`` tokens chose runs over those tokens
        alone (they are gathered first; a token that did not choose it has
        weight 0 and adds nothing); one that drew more runs over every
        token. Either way every token that chose it is computed."""
        b, s, h = x.shape
        flat = x.reshape(b * s, h)
        logits = jnp.dot(flat, bp["router"]["kernel"].astype(jnp.float32),
                         precision=HIGHEST)
        gates, idx = route(logits, cfg)
        rows_e = min(expert_rows, b * s)

        def one(acc, inp):
            e, w_gate, w_up, w_down = inp
            chose = jnp.any(idx == first + e, -1)
            gate_e = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)

            @jax.checkpoint     # keeps no float32 copy of the weights
            def expert(xe):
                act = (jax.nn.silu(mm(xe, w_gate.astype(jnp.float32), quant))
                       * mm(xe, w_up.astype(jnp.float32), quant))
                return mm(act, w_down.astype(jnp.float32), quant)

            def its_tokens():
                take = jnp.argsort(~chose)[:rows_e]      # they come first
                return acc.at[take].add(expert(flat[take])
                                        * gate_e[take][:, None])

            def every_token():
                blocks = (flat.reshape(-1, rows_e, h),
                          gate_e.reshape(-1, rows_e))
                y = jax.lax.map(jax.checkpoint(
                    lambda blk: expert(blk[0]) * blk[1][:, None]), blocks)
                return acc + y.reshape(flat.shape)

            return jax.lax.cond(jnp.sum(chose) > rows_e, every_token,
                                its_tokens), None

        routed, _ = jax.lax.scan(
            one, jnp.zeros_like(flat),
            (jnp.arange(d["held"]), bp["experts_gate"], bp["experts_up"],
             bp["experts_down"]))
        return routed.reshape(b, s, h)

    def make_layer(quant):
        def run(x, bp, lp):
            x = x + attention(_rms(x, bp["ln_attn"]["scale"], eps),
                              bp["attn"], lp["attn"], quant)
            return x + experts(_rms(x, bp["ln_mlp"]["scale"], eps),
                               bp["moe"], quant)
        # the selection is kept from the forward pass: the backward pass
        # rebuilds the rest of the layer, not the top-k
        return jax.checkpoint(
            run, policy=jax.checkpoint_policies.save_only_these_names(
                "selection"))

    def forward(lora, base, tokens, quant):
        x = base["embed"]["embedding"][tokens].astype(jnp.float32)
        run = make_layer(quant)
        for i in range(cfg["num_hidden_layers"]):
            x = run(x, base[f"layer_{i}"], lora[f"layer_{i}"])
        x = _rms(x, base["ln_f"]["scale"], eps)
        return mm(x, base["lm_head"]["kernel"].astype(jnp.float32), quant)

    def block_loss_sum(lora, base, tokens, labels, weights, quant):
        logp = jax.nn.log_softmax(forward(lora, base, tokens, quant), -1)
        per_tok = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        return jnp.sum(per_tok * weights)

    def grad_fn(trainable, frozen, batch, quant):
        """Gradient of the mean loss over the batch's real positions,
        summed row by row."""
        x = batch["x"].astype(jnp.int32)
        y = batch["y"].astype(jnp.int32)
        w = ((y >= 0).astype(jnp.float32)
             * batch["mask"].astype(jnp.float32)[:, None])
        y = jnp.maximum(y, 0)

        def one(carry, blk):
            acc, loss_sum = carry
            ls, g = jax.value_and_grad(block_loss_sum)(
                trainable, frozen, blk[0][None], blk[1][None], blk[2][None],
                quant)
            acc = jax.tree_util.tree_map(jnp.add, acc, g)
            return (acc, loss_sum + ls), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, trainable)
        (acc, loss_sum), _ = jax.lax.scan(
            one, (zero, jnp.zeros((), jnp.float32)), (x, y, w))
        count = jnp.sum(w)
        denom = jnp.maximum(count, 1.0)
        return (jax.tree_util.tree_map(lambda g: g / denom, acc), loss_sum,
                count)

    def selections(lora, base, tokens, quant=None):
        """Each layer's ``S`` [b, s, s] on the way through ``forward``."""
        x = base["embed"]["embedding"][tokens].astype(jnp.float32)
        run, keeps = make_layer(quant), []
        for i in range(cfg["num_hidden_layers"]):
            bp = base[f"layer_{i}"]
            keeps.append(select(_rms(x, bp["ln_attn"]["scale"], eps),
                                bp["attn"], quant))
            x = run(x, bp, lora[f"layer_{i}"])
        return keeps

    grad_fn.forward = forward   # (lora, base, tokens, quant) -> logits
    grad_fn.selections = selections
    return grad_fn
