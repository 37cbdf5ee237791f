"""Plain float32 reference of one expert-parallel rank of A.X-K1 under LoRA.

The model as its ``config.json`` states it and as the DeepSeek-V3 modelling
code reads the same keys (``assumed``: ``axk1`` reads them alike): token
embedding, pre-norm decoder layers, a final RMSNorm and an untied head.

Attention (every layer) is multi-head latent attention: ``c_q =
RMSNorm(x W_qa)``, ``q = c_q W_qb`` -> heads of ``nope + rope`` dims;
``[c_kv | k_r] = x W_kva``, ``[k_nope | v] = RMSNorm(c_kv) W_kvb``; rotary
(half-split convention, YaRN-blended frequencies) on ``q_rope`` and on the
one ``k_r`` that all heads share; causal softmax of ``q k^T * scale`` with
``scale = (nope + rope)^-0.5 * m^2``, ``m = 0.1 * mscale_all_dim *
ln(factor) + 1``; ``softmax(.) v`` through ``W_o``.

The first ``first_k_dense_replace`` layers have a dense SwiGLU; the others
``shared(x) + sum over e in top-k(s), e held here, of g_e E_e(x)`` with
``s = sigmoid(x W_r)`` over ALL published experts, ``g = scaling * s_sel /
(sum s_sel + 1e-20)``. This rank holds experts ``first_expert ..
first_expert + n_routed_experts - 1`` of ``published.n_routed_experts``;
what the absent experts would add is left out, here as in the system.

LoRA on q_a q_b kv_a kv_b o, the dense gate up down and the shared expert's:
the frozen product plus ``(x @ a) @ b * (alpha / rank)``. Only ``a`` and
``b`` train. The loss is the mean next-token cross-entropy over the sliced
vocabulary.

Imports nothing of ``fedml_tpu``. The frozen tree is bfloat16, as the
checkpoint is published, in the layout the driver hands to the system as
is; each layer is upcast to float32 where it is used and recomputed in the
backward pass (``jax.checkpoint``), attention runs over heads in groups and
the experts one at a time over the tokens that chose them, so that nothing
of the whole base's size is ever held in float32 beside the 7 GB bfloat16
base.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(cfg):
    return {"h": cfg["hidden_size"], "nh": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "rq": cfg["q_lora_rank"],
            "rkv": cfg["kv_lora_rank"], "dense": cfg["intermediate_size"],
            "width": cfg["moe_intermediate_size"],
            "held": cfg["n_routed_experts"],
            "experts": cfg["published"]["n_routed_experts"]}


def _attn_shapes(d):
    return {"q_a": (d["h"], d["rq"]),
            "q_b": (d["rq"], d["nh"], d["nope"] + d["rope"]),
            "kv_a": (d["h"], d["rkv"] + d["rope"]),
            "kv_b": (d["rkv"], d["nh"], d["nope"] + d["dv"]),
            "o": (d["nh"] * d["dv"], d["h"])}


def _ffn_shapes(h, width):
    return {"gate": (h, width), "up": (h, width), "down": (width, h)}


def _is_sparse(cfg, layer):
    return layer >= cfg["first_k_dense_replace"]


def init_frozen(key, cfg):
    """The frozen base from the seed in bfloat16: normal with
    ``initializer_range`` rounded to bfloat16, norms at 1. The router's
    column for expert e has its std scaled by ``0.8 + 0.4 * u_e`` (``u`` a
    seeded permutation of ``0 .. 1``): experts with wider logits win the
    top-k more often, so loads are uneven (about 0.3 to 1.7 of the mean)
    and no expert is left without tokens. Call under one ``jax.jit``."""
    std = cfg.get("initializer_range", 0.02)
    d = _dims(cfg)
    n = [0]

    def normal(shape, scale=1.0):
        n[0] += 1
        w = jax.random.normal(jax.random.fold_in(key, n[0]), shape,
                              jnp.float32) * std * scale
        return w.astype(jnp.bfloat16)

    def ones(m):
        return {"scale": jnp.ones((m,), jnp.bfloat16)}

    def kernels(shapes):
        return {k: {"kernel": normal(s)} for k, s in shapes.items()}

    p = {"embed": {"embedding": normal((cfg["vocab_size"], d["h"]))}}
    for layer in range(cfg["num_hidden_layers"]):
        attn = kernels(_attn_shapes(d))
        attn["q_norm"], attn["kv_norm"] = ones(d["rq"]), ones(d["rkv"])
        lp = {"attn": attn, "ln_attn": ones(d["h"]), "ln_mlp": ones(d["h"])}
        if _is_sparse(cfg, layer):
            n[0] += 1
            u = jax.random.permutation(
                jax.random.fold_in(key, n[0]),
                jnp.arange(d["experts"], dtype=jnp.float32)
            ) / max(d["experts"] - 1, 1)
            lp["moe"] = {
                "router": {"kernel": normal((d["h"], d["experts"]),
                                            0.8 + 0.4 * u[None, :])},
                "shared": kernels(_ffn_shapes(
                    d["h"], d["width"] * cfg["n_shared_experts"])),
                "experts_gate": normal((d["held"], d["h"], d["width"])),
                "experts_up": normal((d["held"], d["h"], d["width"])),
                "experts_down": normal((d["held"], d["width"], d["h"]))}
        else:
            lp["mlp"] = kernels(_ffn_shapes(d["h"], d["dense"]))
        p[f"layer_{layer}"] = lp
    p["ln_f"] = ones(d["h"])
    p["lm_head"] = {"kernel": normal((d["h"], cfg["vocab_size"]))}
    return p


def init_trainable(key, cfg):
    """Adapters in the middle of a fine-tune (``a`` normal with std 1/rank,
    ``b`` normal with std ``lora_b_std``: at ``b = 0`` every ``a`` has a
    zero gradient), float32."""
    rank, d = cfg["lora_rank"], _dims(cfg)
    n = [0]

    def pairs(shapes):
        out = {}
        for name, shape in shapes.items():
            n[0] += 1
            ka, kb = jax.random.split(jax.random.fold_in(key, n[0]))
            out[name] = {
                "lora_a": jax.random.normal(ka, (shape[0], rank),
                                            jnp.float32) / rank,
                "lora_b": jax.random.normal(
                    kb, (rank, math.prod(shape[1:])), jnp.float32)
                * cfg["lora_b_std"]}
        return out

    p = {}
    for layer in range(cfg["num_hidden_layers"]):
        lp = {"attn": pairs(_attn_shapes(d))}
        if _is_sparse(cfg, layer):
            lp["moe"] = {"shared": pairs(_ffn_shapes(
                d["h"], d["width"] * cfg["n_shared_experts"]))}
        else:
            lp["mlp"] = pairs(_ffn_shapes(d["h"], d["dense"]))
        p[f"layer_{layer}"] = lp
    return p


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def yarn_frequencies(dim, theta, scaling):
    """``dim / 2`` rotary frequencies, YaRN-blended: a dimension that turns
    more than ``beta_fast`` times within the original context keeps its
    frequency, one under ``beta_slow`` turns gets it divided by ``factor``,
    a linear ramp between."""
    half = dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if not scaling:
        return freq
    orig = scaling["original_max_position_embeddings"]

    def dim_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim_of(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return freq / scaling["factor"] * ramp + freq * (1.0 - ramp)


def _rope(x, freq):
    """x [b, s, heads, d]; positions 0..s-1; half-split rotation."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make_model(cfg):
    d = _dims(cfg)
    scale = cfg["lora_alpha"] / cfg["lora_rank"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    scaling = cfg.get("rope_scaling")
    top_k = cfg["num_experts_per_tok"]
    first = cfg.get("first_expert", 0)
    heads_per_group = cfg.get("reference_heads_per_group", 8)
    rows_per_block = cfg.get("reference_rows_per_block", 1)
    expert_rows = cfg.get("reference_expert_rows", 1024)
    m = 1.0
    if scaling and scaling.get("factor", 1) > 1:
        m = 0.1 * scaling.get("mscale_all_dim", 0) * math.log(
            scaling["factor"]) + 1.0
    softmax_scale = (d["nope"] + d["rope"]) ** -0.5 * m * m

    def mm(x, w, quant):
        """``x @ w``; the control routes it through its lower precision."""
        f = lambda a, b: jnp.dot(a, b, precision=HIGHEST)  # noqa: E731
        return f(x, w) if quant is None else quant(f)(x, w)

    def proj(x, base, lora, quant):
        w = base["kernel"].astype(jnp.float32)
        w = w.reshape(w.shape[0], -1)
        return mm(x, w, quant) + mm(mm(x, lora["lora_a"], quant),
                                    lora["lora_b"], quant) * scale

    def swiglu(x, base, lora, quant):
        act = (jax.nn.silu(proj(x, base["gate"], lora["gate"], quant))
               * proj(x, base["up"], lora["up"], quant))
        return proj(act, base["down"], lora["down"], quant)

    def attention(x, bp, lp, quant):
        b, s, _ = x.shape
        nh, nope, rope, dv = d["nh"], d["nope"], d["rope"], d["dv"]
        c_q = _rms(proj(x, bp["q_a"], lp["q_a"], quant),
                   bp["q_norm"]["scale"], eps)
        kv_a = proj(x, bp["kv_a"], lp["kv_a"], quant)
        c_kv = _rms(kv_a[..., :d["rkv"]], bp["kv_norm"]["scale"], eps)
        freq = yarn_frequencies(rope, theta, scaling)
        k_r = _rope(kv_a[..., d["rkv"]:][:, :, None, :], freq)
        q = proj(c_q, bp["q_b"], lp["q_b"], quant).reshape(
            b, s, nh, nope + rope)
        kv = proj(c_kv, bp["kv_b"], lp["kv_b"], quant).reshape(
            b, s, nh, nope + dv)
        q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], freq)], -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (b, s, nh, rope))], -1)
        v = kv[..., nope:]
        pos = jnp.arange(s)
        live = pos[:, None] >= pos[None, :]

        @jax.checkpoint
        def heads(qkv):
            q, k, v = qkv                       # [b, s, g, .]
            qk = lambda a, c: jnp.einsum(  # noqa: E731
                "bqhd,bkhd->bhqk", a, c, precision=HIGHEST)
            scores = (qk(q, k) if quant is None else quant(qk)(q, k)
                      ) * softmax_scale
            probs = jax.nn.softmax(
                jnp.where(live[None, None], scores, -1e30), axis=-1)
            pv = lambda a, c: jnp.einsum(  # noqa: E731
                "bhqk,bkhd->bqhd", a, c, precision=HIGHEST)
            return pv(probs, v) if quant is None else quant(pv)(probs, v)

        g = heads_per_group if nh % heads_per_group == 0 else nh
        split = lambda a: jnp.moveaxis(  # noqa: E731
            a.reshape(b, s, nh // g, g, a.shape[-1]), 2, 0)
        out = jax.lax.map(heads, (split(q), split(k), split(v)))
        out = jnp.moveaxis(out, 0, 2).reshape(b, s, nh * dv)
        return proj(out, bp["o"], lp["o"], quant)

    def experts(x, bp, lp, quant):
        """shared(x) + the held experts' gated part, one expert at a time
        over the tokens that chose it: the ``expert_rows`` tokens with the
        largest gate for the expert are gathered (a token that did not
        choose it has gate 0 and adds nothing), so an expert costs its
        share and not all tokens. Should an expert ever draw more tokens
        than ``expert_rows``, the result is made NaN: the run then reads
        not correct instead of leaving a token out in silence."""
        b, s, h = x.shape
        flat = x.reshape(b * s, h)
        logits = jnp.dot(flat, bp["router"]["kernel"].astype(jnp.float32),
                         precision=HIGHEST)
        scores = jax.nn.sigmoid(logits)
        vals, idx = jax.lax.top_k(scores, top_k)
        if cfg.get("norm_topk_prob", True):
            vals = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20)
        gates = vals * cfg["routed_scaling_factor"]

        rows_e = min(expert_rows, b * s)

        def one(acc, inp):
            e, w_gate, w_up, w_down = inp
            chose = jnp.any(idx == first + e, -1)
            gate_e = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)
            take = jnp.argsort(~chose)[:rows_e]      # its tokens come first
            xe = flat[take]
            act = (jax.nn.silu(mm(xe, w_gate.astype(jnp.float32), quant))
                   * mm(xe, w_up.astype(jnp.float32), quant))
            y = mm(act, w_down.astype(jnp.float32), quant)
            y = jnp.where(jnp.sum(chose) > rows_e, jnp.nan, y)
            return acc.at[take].add(y * gate_e[take][:, None]), None

        routed, _ = jax.lax.scan(
            one, jnp.zeros_like(flat),
            (jnp.arange(d["held"]), bp["experts_gate"], bp["experts_up"],
             bp["experts_down"]))
        return (swiglu(x, bp["shared"], lp["shared"], quant)
                + routed.reshape(b, s, h))

    def make_layer(sparse, quant):
        @jax.checkpoint
        def layer(x, bp, lp):
            x = x + attention(_rms(x, bp["ln_attn"]["scale"], eps),
                              bp["attn"], lp["attn"], quant)
            hn = _rms(x, bp["ln_mlp"]["scale"], eps)
            if sparse:
                return x + experts(hn, bp["moe"], lp["moe"], quant)
            return x + swiglu(hn, bp["mlp"], lp["mlp"], quant)
        return layer

    def forward(lora, base, tokens, quant):
        x = base["embed"]["embedding"][tokens].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = make_layer(_is_sparse(cfg, i), quant)(
                x, base[f"layer_{i}"], lora[f"layer_{i}"])
        x = _rms(x, base["ln_f"]["scale"], eps)
        return mm(x, base["lm_head"]["kernel"].astype(jnp.float32), quant)

    def block_loss_sum(lora, base, tokens, labels, weights, quant):
        logp = jax.nn.log_softmax(forward(lora, base, tokens, quant), -1)
        per_tok = -jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        return jnp.sum(per_tok * weights)

    def grad_fn(trainable, frozen, batch, quant):
        """Gradient of the mean loss over the batch's real positions,
        summed block of rows by block of rows."""
        x = batch["x"].astype(jnp.int32)
        y = batch["y"].astype(jnp.int32)
        w = ((y >= 0).astype(jnp.float32)
             * batch["mask"].astype(jnp.float32)[:, None])
        y = jnp.maximum(y, 0)
        rows = x.shape[0]
        rpb = rows_per_block if rows % rows_per_block == 0 else 1
        blocks = tuple(a.reshape((rows // rpb, rpb) + a.shape[1:])
                       for a in (x, y, w))

        def one(carry, blk):
            acc, loss_sum = carry
            ls, g = jax.value_and_grad(block_loss_sum)(
                trainable, frozen, blk[0], blk[1], blk[2], quant)
            return (jax.tree_util.tree_map(jnp.add, acc, g), loss_sum + ls), None

        zero = jax.tree_util.tree_map(jnp.zeros_like, trainable)
        (acc, loss_sum), _ = jax.lax.scan(
            one, (zero, jnp.zeros((), jnp.float32)), blocks)
        count = jnp.sum(w)
        denom = jnp.maximum(count, 1.0)
        return (jax.tree_util.tree_map(lambda g: g / denom, acc), loss_sum,
                count)

    grad_fn.forward = forward   # (lora, base, tokens, quant) -> logits
    return grad_fn
