"""Plain float32 reference of one expert-parallel rank of Kimi-Linear-48B-A3B
(``model_type`` ``kimi_linear``) under LoRA.

Token embedding, pre-norm decoder layers, a final RMSNorm and an untied
head. Layer ``i`` (counted from 1) has Kimi delta attention (KDA; Kimi
Linear, arXiv:2510.26692) where ``linear_attn_config.kda_layers`` names it
and latent attention where ``full_attn_layers`` does; the first
``first_k_dense_replace`` layers have a dense SwiGLU, the others the expert
block. ``x`` is the layer's normed input, H heads of d =
``linear_attn_config.head_dim`` (the top-level ``head_dim`` is hidden / H and
is not read).

KDA: ``q, k, v = SiLU(conv(x W_q)), SiLU(conv(x W_k)), SiLU(conv(x W_v))``
(``conv`` a causal depthwise convolution over the last
``short_conv_kernel_size`` positions, no bias); ``q``, ``k`` divided by
``sqrt(sum of squares + 1e-6)`` a head, ``q`` scaled by ``d^-0.5``; ``beta
= sigmoid(x W_b)`` a head; log-decay ``g = -exp(A_log) softplus(x W_fa W_fb
+ dt_bias)`` a key channel, with no lower bound. A head's state ``S`` in
``R^{d x d}`` starts a row at zero and runs TOKEN BY TOKEN:

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

in a ``lax.scan`` whose segments are rematerialised (the gradient keeps one
state a segment, not one a token); the form is exact at any decay. ``y =
(RMSNorm_head(o) * sigmoid(x W_ga W_gb)) W_o``, a gate a channel.

Latent attention (``mla_use_nope``): ``q = x W_q`` -> heads of ``nope +
rope`` dims (no query latent); ``[c_kv | k_r] = x W_kva``, ``[k_nope | v] =
RMSNorm(c_kv) W_kvb``; ``k = [k_nope | k_r]`` with the one ``k_r`` all
heads share and NO rotary; causal softmax at ``(nope + rope)^-0.5``; ``y =
o W_o``, no gate.

Experts: ``shared(x) + sum over e chosen and held here of w_e E_e(x)``;
``s = sigmoid(x W_r)`` over ALL published experts, the top-k of ``s + b``
(one expert group: no group limit); ``w = scaling * s_sel / (sum s_sel +
1e-20)`` from ``s`` without ``b``. This rank holds experts ``first_expert ..
first_expert + num_experts - 1`` of ``published.num_experts``; what the
absent ones would add is left out.

LoRA on KDA ``q k v o``, latent ``q kv_a kv_b o``, dense and shared ``gate
up down``: the frozen product plus ``(x @ a) @ b * (alpha / rank)``. The loss
is the mean next-token cross-entropy over the sliced vocabulary.

Two keys no configuration sets put a fault in the reference, for the
calibration of the cell's limits: ``fault_clamp_decay`` clamps every
log-decay at -5 (the bounded gate's floor), ``fault_rotary`` turns the
rotary dims of the latent layers at ``rope_theta`` (half-split).

Imports nothing of ``fedml_tpu``. The frozen tree is bfloat16 (``A_log``,
``dt_bias`` and the router's bias float32), in the layout the driver hands
to the system as is; each layer is upcast where it is used and recomputed
in the backward pass, the experts run one at a time over the tokens that
chose them (over every token where an expert drew more than
``reference_expert_rows``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
# the bounded gate's floor, where ``fault_clamp_decay`` clamps
BOUNDED_FLOOR = -5.0


def _dims(cfg):
    linear = cfg["linear_attn_config"]
    return {"h": cfg["hidden_size"], "nh": linear["num_heads"],
            "d": linear["head_dim"], "lh": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"],
            "rope": cfg["qk_rope_head_dim"], "dv": cfg["v_head_dim"],
            "rkv": cfg["kv_lora_rank"], "dense": cfg["intermediate_size"],
            "width": cfg["moe_intermediate_size"],
            "shared": (cfg["moe_intermediate_size"]
                       * cfg["num_shared_experts"]),
            "held": cfg["num_experts"],
            "experts": cfg["published"]["num_experts"],
            "taps": linear["short_conv_kernel_size"]}


def _kda_shapes(d):
    wide = d["nh"] * d["d"]
    return {"q": (d["h"], wide), "k": (d["h"], wide), "v": (d["h"], wide),
            "o": (wide, d["h"])}


def _latent_shapes(d):
    return {"q": (d["h"], d["lh"], d["nope"] + d["rope"]),
            "kv_a": (d["h"], d["rkv"] + d["rope"]),
            "kv_b": (d["rkv"], d["lh"], d["nope"] + d["dv"]),
            "o": (d["lh"] * d["dv"], d["h"])}


def _ffn_shapes(h, width):
    return {"gate": (h, width), "up": (h, width), "down": (width, h)}


def _is_sparse(cfg, layer):
    return layer >= cfg["first_k_dense_replace"]


def _is_linear(cfg, layer):
    """Layer ``layer`` counted from 0; the published lists count from 1."""
    return layer + 1 in cfg["linear_attn_config"]["kda_layers"]


def init_frozen(key, cfg):
    """The frozen base from the seed: normal with ``initializer_range``
    rounded to bfloat16, norms at 1. KDA: ``A_log = log U(1, 16)`` a head,
    ``dt_bias`` the inverse softplus of a log-uniform draw in
    ``dt_softplus_range`` a channel (float32), so the log-decays
    ``-exp(A_log) softplus(.)`` span about (-20, 0). The router's column for
    expert e has its std scaled by ``0.8 + 0.4 u_e`` (``u`` a seeded
    permutation of ``0 .. 1``), its bias is ``router_bias_range`` times
    U(-1, 1). Call under one ``jax.jit``."""
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

    lo, hi = cfg["dt_softplus_range"]
    p = {"embed": {"embedding": normal((cfg["vocab_size"], d["h"]))}}
    for layer in range(cfg["num_hidden_layers"]):
        if _is_linear(cfg, layer):
            wide = d["nh"] * d["d"]
            attn = kernels(dict(
                _kda_shapes(d), b=(d["h"], d["nh"]), f_a=(d["h"], d["d"]),
                f_b=(d["d"], wide), g_a=(d["h"], d["d"]),
                g_b=(d["d"], wide)))
            for name in "qkv":
                # taps that sum to about 1, as a trained smoothing filter
                attn["conv_" + name] = (
                    jax.random.normal(fresh(), (d["taps"], wide), jnp.float32)
                    * d["taps"] ** -0.5).astype(jnp.bfloat16)
            attn["A_log"] = jnp.log(jax.random.uniform(
                fresh(), (d["nh"],), jnp.float32, 1.0, 16.0))
            step = jnp.exp(jax.random.uniform(
                fresh(), (wide,), jnp.float32, math.log(lo), math.log(hi)))
            attn["dt_bias"] = jnp.log(jnp.expm1(step))
            attn["o_norm"] = ones(d["d"])
        else:
            attn = kernels(_latent_shapes(d))
            attn["kv_norm"] = ones(d["rkv"])
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
                "shared": kernels(_ffn_shapes(d["h"], d["shared"])),
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
        lp = {"attn": pairs(_kda_shapes(d) if _is_linear(cfg, layer)
                            else _latent_shapes(d))}
        if _is_sparse(cfg, layer):
            lp["moe"] = {"shared": pairs(_ffn_shapes(d["h"], d["shared"]))}
        else:
            lp["mlp"] = pairs(_ffn_shapes(d["h"], d["dense"]))
        p[f"layer_{layer}"] = lp
    return p


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, freq):
    """x [b, s, heads, d]; positions 0..s-1; half-split rotation."""
    half = x.shape[-1] // 2
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _short_conv(x, w):
    """Causal depthwise convolution: ``y_t = sum_i w[i] x_{t-(K-1)+i}``.
    x [b, s, c], w [K, c]."""
    taps, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, [(0, 0), (taps - 1, 0), (0, 0)])
    return sum(xp[:, i:i + s] * w[i] for i in range(taps))


def delta_rule(q, k, v, g, beta, segment):
    """The recurrence, token by token. q, k, g [b, s, H, d_k], v [b, s, H,
    d_v], beta [b, s, H] -> o [b, s, H, d_v]; all heads and rows of the
    batch advance together inside the scan, ``segment`` tokens at a time
    under ``jax.checkpoint``."""
    b, s, nh, dk = q.shape
    seg = math.gcd(s, segment)

    def token(st, xs):
        q_t, k_t, v_t, g_t, b_t = xs                      # [b, H, .]
        st = st * jnp.exp(g_t)[..., None]                 # Diag(alpha) S
        u = b_t[..., None] * (v_t - jnp.sum(st * k_t[..., None], -2))
        st = st + k_t[..., None] * u[..., None, :]
        return st, jnp.sum(st * q_t[..., None], -2)

    @jax.checkpoint
    def run(st, xs):
        return jax.lax.scan(token, st, xs)

    def by_segment(a):              # [b, s, ...] -> [s / seg, seg, b, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((s // seg, seg) + a.shape[1:])

    st0 = jnp.zeros((b, nh, dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(run, st0, tuple(by_segment(a)
                                        for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape((s,) + o.shape[2:]), 0, 1)


def route(scores_in, bias, cfg):
    """-> (weights [T, k], experts [T, k]) as the module's docstring says."""
    s = jax.nn.sigmoid(scores_in)
    idx = jax.lax.top_k(s + bias, cfg["num_experts_per_token"])[1]
    vals = jnp.take_along_axis(s, idx, -1)
    if cfg.get("moe_renormalize", True):
        vals = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20)
    return vals * cfg["routed_scaling_factor"], idx


def make_model(cfg):
    if cfg.get("num_expert_group", 1) != 1:
        raise NotImplementedError("the reference routes without groups")
    d = _dims(cfg)
    scale = cfg["lora_alpha"] / cfg["lora_rank"]
    eps = cfg["rms_norm_eps"]
    first = cfg.get("first_expert", 0)
    heads_per_group = cfg.get("reference_heads_per_group", 8)
    rows_per_block = cfg.get("reference_rows_per_block", 1)
    expert_rows = cfg.get("reference_expert_rows", 512)
    segment = cfg.get("reference_kda_segment", 64)
    softmax_scale = (d["nope"] + d["rope"]) ** -0.5

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

    def low_rank(x, bp, name, quant):
        """``x W_{name}a W_{name}b``, frozen."""
        return proj(proj(x, bp[name + "_a"], None, quant), bp[name + "_b"],
                    None, quant)

    def linear_attention(x, bp, lp, quant):
        b, s, _ = x.shape
        nh, dd = d["nh"], d["d"]
        heads = lambda a: a.reshape(b, s, nh, dd)  # noqa: E731

        def conv(name):
            y = proj(x, bp[name], lp[name], quant)
            w = bp["conv_" + name].astype(jnp.float32)
            f = _short_conv if quant is None else quant(_short_conv)
            return heads(jax.nn.silu(f(y, w)))

        unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(a * a, -1, keepdims=True) + 1e-6)
        q, k, v = unit(conv("q")) * dd ** -0.5, unit(conv("k")), conv("v")
        g = -jnp.exp(bp["A_log"])[:, None] * jax.nn.softplus(
            heads(low_rank(x, bp, "f", quant) + bp["dt_bias"]))
        if cfg.get("fault_clamp_decay"):
            g = jnp.maximum(g, BOUNDED_FLOOR)
        beta = jax.nn.sigmoid(proj(x, bp["b"], None, quant))
        o = _rms(delta_rule(q, k, v, g, beta, segment),
                 bp["o_norm"]["scale"], eps)
        o = o * jax.nn.sigmoid(heads(low_rank(x, bp, "g", quant)))
        return proj(o.reshape(b, s, nh * dd), bp["o"], lp["o"], quant)

    def latent_attention(x, bp, lp, quant):
        b, s, _ = x.shape
        nh, nope, rope, dv = d["lh"], d["nope"], d["rope"], d["dv"]
        kv_a = proj(x, bp["kv_a"], lp["kv_a"], quant)
        c_kv = _rms(kv_a[..., :d["rkv"]], bp["kv_norm"]["scale"], eps)
        k_r = kv_a[..., d["rkv"]:][:, :, None, :]
        q = proj(x, bp["q"], lp["q"], quant).reshape(b, s, nh, nope + rope)
        kv = proj(c_kv, bp["kv_b"], lp["kv_b"], quant).reshape(
            b, s, nh, nope + dv)
        if cfg.get("fault_rotary"):
            half = rope // 2
            freq = float(cfg["rope_theta"]) ** (
                -jnp.arange(0, half, dtype=jnp.float32) / half)
            k_r = _rope(k_r, freq)
            q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], freq)],
                                -1)
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

        grp = heads_per_group if nh % heads_per_group == 0 else nh
        split = lambda a: jnp.moveaxis(  # noqa: E731
            a.reshape(b, s, nh // grp, grp, a.shape[-1]), 2, 0)
        out = jax.lax.map(heads, (split(q), split(k), split(v)))
        out = jnp.moveaxis(out, 0, 2).reshape(b, s, nh * dv)
        return proj(out, bp["o"], lp["o"], quant)

    def experts(x, bp, lp, quant):
        """shared(x) + the held experts' gated part, one expert at a time. An
        expert that at most ``expert_rows`` tokens chose runs over those
        tokens alone (they are gathered first; a token that did not choose
        it has weight 0 and adds nothing), so it costs its share and not
        all tokens; one that drew more runs over every token. Either way
        every token that chose it is computed."""
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
                # block by block, each rebuilt in the backward pass: the
                # scan over experts keeps room for either branch's
                # residuals, taken or not
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
        return (swiglu(x, bp["shared"], lp["shared"], quant)
                + routed.reshape(b, s, h))

    def make_layer(linear, sparse, quant):
        attention = linear_attention if linear else latent_attention

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
            x = make_layer(_is_linear(cfg, i), _is_sparse(cfg, i), quant)(
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
