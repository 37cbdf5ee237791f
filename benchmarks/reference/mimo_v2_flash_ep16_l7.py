"""Plain float32 reference of one expert-parallel rank of MiMo-V2-Flash
(``model_type`` ``mimo_v2_flash``) under LoRA.

Token embedding, pre-norm decoder layers, a final RMSNorm and an untied
head. Layer ``l`` is of kind ``window`` where ``hybrid_layer_pattern[l]`` is
1 and ``full`` where it is 0; it has a dense SwiGLU where
``moe_layer_freq[l]`` is 0 and the expert block where it is 1. With ``x``
the layer's input, 64 query heads, ``n_kv`` key-value heads (``full``:
``num_key_value_heads``; ``window``: ``swa_num_key_value_heads``):

    h   = RMSNorm(x)                       (eps ``layernorm_epsilon``)
    q   = h W_q   as heads of ``head_dim`` (192)
    k   = h W_k   as n_kv heads of ``head_dim``
    v   = h W_v   as n_kv heads of ``v_head_dim`` (128); no biases
    q, k: rotary (half-split convention) on the first ``int(head_dim *
          partial_rotary_factor)`` = 64 dims of every head, base
          ``rope_theta`` (full) or ``swa_rope_theta`` (window); the other
          dims pass unrotated
    v   <- ``attention_value_scale`` * v
    s_ij = q_i . k_j / sqrt(head_dim)      for j <= i, and in a window
          layer only for i - j < ``sliding_window``; query head a reads
          key-value head a // (64 / n_kv)
    p_ij = exp(s_ij) / (exp(b_a) + sum_j' exp(s_ij'))   where the kind has
          a sink (``add_swa_attention_sink_bias`` /
          ``add_full_attention_sink_bias``): one learned logit b_a a query
          head, a column that takes mass and carries no value; the plain
          softmax where it has none
    o_i = sum_j p_ij v_j ;   x <- x + concat_a(o) W_o

    h2  = RMSNorm(x)
    dense:   x <- x + SwiGLU(h2)           (width ``intermediate_size``)
    experts: sigma = sigmoid(h2 W_r) over ALL published experts, float32;
             the ``num_experts_per_tok`` experts with the largest sigma +
             bias (``topk_method`` ``noaux_tc`` with ``n_group`` =
             ``topk_group`` = 1: no group limit); gates sigma_e / (sum of
             the chosen sigma + 1e-20) (``norm_topk_prob``) times
             ``routed_scaling_factor`` (null: 1);
             x <- x + sum over e chosen and held here of g_e SwiGLU_e(h2)
             (width ``moe_intermediate_size``); NO shared expert.

This rank holds experts ``first_expert .. first_expert + n_routed_experts
- 1`` of ``published.n_routed_experts``; what the absent ones would add is
left out. LoRA on ``q k v o`` of every layer and the dense layer's ``gate
up down``: the frozen product plus ``(x @ a) @ b * (alpha / rank)``; the
sinks, routers, biases and routed experts are frozen. The loss is the mean
next-token cross-entropy over the sliced vocabulary.

Imports nothing of ``fedml_tpu``; no kernel, no cache, no batching. The
frozen tree is bfloat16 (sinks and the routers' biases float32), in the
layout the driver hands to the system as is; each layer is upcast where it
is used and recomputed in the backward pass, heads attend in groups, the
experts run one at a time over the tokens that chose them (over every
token where an expert drew more than ``reference_expert_rows``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(cfg):
    return {"h": cfg["hidden_size"], "nh": cfg["num_attention_heads"],
            "d_qk": cfg["head_dim"], "d_v": cfg["v_head_dim"],
            "rot": int(cfg["head_dim"] * cfg["partial_rotary_factor"]),
            "dense": cfg["intermediate_size"],
            "width": cfg["moe_intermediate_size"],
            "held": cfg["n_routed_experts"],
            "experts": cfg["published"]["n_routed_experts"]}


def _is_window(cfg, layer):
    return bool(cfg["hybrid_layer_pattern"][layer])


def _is_sparse(cfg, layer):
    return bool(cfg["moe_layer_freq"][layer])


def _kv_heads(cfg, layer):
    return cfg["swa_num_key_value_heads" if _is_window(cfg, layer)
               else "num_key_value_heads"]


def _has_sink(cfg, layer):
    return bool(cfg["add_swa_attention_sink_bias" if _is_window(cfg, layer)
                    else "add_full_attention_sink_bias"])


def _attn_shapes(cfg, layer):
    d, kv = _dims(cfg), _kv_heads(cfg, layer)
    return {"q": (d["h"], d["nh"], d["d_qk"]), "k": (d["h"], kv, d["d_qk"]),
            "v": (d["h"], kv, d["d_v"]), "o": (d["nh"] * d["d_v"], d["h"])}


def _ffn_shapes(h, width):
    return {"gate": (h, width), "up": (h, width), "down": (width, h)}


def init_frozen(key, cfg):
    """The frozen base from the seed: normal with ``initializer_range``
    rounded to bfloat16, norms at 1. A layer's sink logits are ``ln(
    sliding_window) + U(-1, 1)`` a query head, float32 (at the published
    sizes a window row's 128 scores have a standard deviation near 1.6 and
    sum, exponentiated, to a few hundred: the sink then takes a tenth to a
    half of the row's mass); they are drawn for every layer, so that a
    configuration without the flag has the same other weights. The
    router's column for expert e has its std scaled by ``0.8 + 0.4 u_e``
    (``u`` a seeded permutation of ``0 .. 1``), its bias is
    ``router_bias_range`` times U(-1, 1). Call under one ``jax.jit``."""
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

    def kernels(shapes):
        return {k: {"kernel": normal(s)} for k, s in shapes.items()}

    p = {"embed": {"embedding": normal((cfg["vocab_size"], d["h"]))}}
    for layer in range(cfg["num_hidden_layers"]):
        attn = kernels(_attn_shapes(cfg, layer))
        sink = math.log(cfg["sliding_window"]) + jax.random.uniform(
            fresh(), (d["nh"],), jnp.float32, -1.0, 1.0)
        if _has_sink(cfg, layer):
            attn["sink"] = sink
        lp = {"attn": attn, "ln_attn": ones(d["h"]), "ln_mlp": ones(d["h"])}
        if _is_sparse(cfg, layer):
            u = jax.random.permutation(
                fresh(), jnp.arange(d["experts"], dtype=jnp.float32)
            ) / max(d["experts"] - 1, 1)
            lp["moe"] = {
                "router": {"kernel": normal((d["h"], d["experts"]),
                                            0.8 + 0.4 * u[None, :])},
                "router_bias": cfg["router_bias_range"] * jax.random.uniform(
                    fresh(), (d["experts"],), jnp.float32, -1.0, 1.0),
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
        lp = {"attn": pairs(_attn_shapes(cfg, layer))}
        if not _is_sparse(cfg, layer):
            lp["mlp"] = pairs(_ffn_shapes(d["h"], d["dense"]))
        p[f"layer_{layer}"] = lp
    return p


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, freq):
    """x [b, s, heads, d]; positions 0..s-1; half-split rotation of all d."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def route(scores_in, bias, cfg):
    """-> (gates [T, k], experts [T, k]): the choice by sigma + bias over
    all experts (no group limit), the gates from sigma alone."""
    if cfg.get("n_group", 1) > 1:
        raise NotImplementedError("a group limit is not this model's")
    sigma = jax.nn.sigmoid(scores_in)
    choice = sigma + bias if cfg.get("topk_method") == "noaux_tc" else sigma
    idx = jax.lax.top_k(choice, cfg["num_experts_per_tok"])[1]
    vals = jnp.take_along_axis(sigma, idx, -1)
    if cfg.get("norm_topk_prob", True):
        vals = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20)
    return vals * (cfg.get("routed_scaling_factor") or 1.0), idx


def make_model(cfg):
    d = _dims(cfg)
    scale = cfg["lora_alpha"] / cfg["lora_rank"]
    eps = cfg["layernorm_epsilon"]
    first = cfg.get("first_expert", 0)
    heads_per_group = cfg.get("reference_heads_per_group", 8)
    rows_per_block = cfg.get("reference_rows_per_block", 1)
    expert_rows = cfg.get("reference_expert_rows", 512)
    softmax_scale = d["d_qk"] ** -0.5
    value_scale = float(cfg.get("attention_value_scale") or 1.0)

    def mm(x, w, quant):
        """``x @ w``; the control routes it through its lower precision."""
        f = lambda a, b: jnp.dot(a, b, precision=HIGHEST)  # noqa: E731
        return f(x, w) if quant is None else quant(f)(x, w)

    def proj(x, base, lora, quant):
        w = base["kernel"].astype(jnp.float32)
        w = w.reshape(w.shape[0], -1)
        y = mm(x, w, quant)
        if lora is None:
            return y
        return y + mm(mm(x, lora["lora_a"], quant), lora["lora_b"],
                      quant) * scale

    def swiglu(x, base, lora, quant):
        act = (jax.nn.silu(proj(x, base["gate"], lora["gate"], quant))
               * proj(x, base["up"], lora["up"], quant))
        return proj(act, base["down"], lora["down"], quant)

    def attention(x, bp, lp, quant, layer):
        b, s, _ = x.shape
        nh, kv = d["nh"], _kv_heads(cfg, layer)
        window = _is_window(cfg, layer)
        theta = float(cfg["swa_rope_theta" if window else "rope_theta"])
        rot = d["rot"]
        freq = theta ** (-jnp.arange(0, rot // 2, dtype=jnp.float32)
                         / (rot // 2))
        q = proj(x, bp["q"], lp["q"], quant).reshape(b, s, nh, d["d_qk"])
        k = proj(x, bp["k"], lp["k"], quant).reshape(b, s, kv, d["d_qk"])
        v = proj(x, bp["v"], lp["v"], quant).reshape(b, s, kv, d["d_v"])
        q, k = (jnp.concatenate([_rope(a[..., :rot], freq), a[..., rot:]], -1)
                for a in (q, k))
        v = v * value_scale
        # query head a reads key-value head a // (nh / kv)
        k, v = (jnp.repeat(a, nh // kv, axis=2) for a in (k, v))
        pos = jnp.arange(s)
        live = pos[:, None] >= pos[None, :]
        if window:
            live &= pos[:, None] - pos[None, :] < cfg["sliding_window"]
        grp = heads_per_group if nh % heads_per_group == 0 else nh
        sink = bp.get("sink")
        sinks = (jnp.full((nh,), -jnp.inf, jnp.float32) if sink is None
                 else sink.astype(jnp.float32)).reshape(nh // grp, grp)

        @jax.checkpoint
        def heads(inp):
            q, k, v, b_a = inp                  # [b, s, g, .], [g]
            qk = lambda a, c: jnp.einsum(  # noqa: E731
                "bqhd,bkhd->bhqk", a, c, precision=HIGHEST)
            scores = (qk(q, k) if quant is None else quant(qk)(q, k)
                      ) * softmax_scale
            scores = jnp.where(live[None, None], scores, -jnp.inf)
            b_a = b_a[None, :, None, None]
            top = jnp.maximum(jnp.max(scores, -1, keepdims=True), b_a)
            e = jnp.exp(scores - top)
            probs = e / (jnp.exp(b_a - top) + jnp.sum(e, -1, keepdims=True))
            pv = lambda a, c: jnp.einsum(  # noqa: E731
                "bhqk,bkhd->bqhd", a, c, precision=HIGHEST)
            return pv(probs, v) if quant is None else quant(pv)(probs, v)

        split = lambda a: jnp.moveaxis(  # noqa: E731
            a.reshape(b, s, nh // grp, grp, a.shape[-1]), 2, 0)
        out = jax.lax.map(heads, (split(q), split(k), split(v), sinks))
        out = jnp.moveaxis(out, 0, 2).reshape(b, s, nh * d["d_v"])
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
        gates, idx = route(logits, bp["router_bias"], cfg)
        rows_e = min(expert_rows, b * s)

        def one(acc, inp):
            e, w_gate, w_up, w_down = inp
            chose = jnp.any(idx == first + e, -1)
            gate_e = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)

            def expert(xe):
                act = (jax.nn.silu(mm(xe, w_gate.astype(jnp.float32), quant))
                       * mm(xe, w_up.astype(jnp.float32), quant))
                return mm(act, w_down.astype(jnp.float32), quant)

            def its_tokens():
                take = jnp.argsort(~chose)[:rows_e]      # they come first
                return acc.at[take].add(expert(flat[take])
                                        * gate_e[take][:, None])

            def every_token():
                # block by block, each rebuilt in the backward pass
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

    def make_layer(layer, quant):
        @jax.checkpoint
        def run(x, bp, lp):
            x = x + attention(_rms(x, bp["ln_attn"]["scale"], eps),
                              bp["attn"], lp["attn"], quant, layer)
            hn = _rms(x, bp["ln_mlp"]["scale"], eps)
            if _is_sparse(cfg, layer):
                return x + experts(hn, bp["moe"], quant)
            return x + swiglu(hn, bp["mlp"], lp["mlp"], quant)
        return run

    def forward(lora, base, tokens, quant):
        x = base["embed"]["embedding"][tokens].astype(jnp.float32)
        for i in range(cfg["num_hidden_layers"]):
            x = make_layer(i, quant)(x, base[f"layer_{i}"],
                                     lora[f"layer_{i}"])
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
