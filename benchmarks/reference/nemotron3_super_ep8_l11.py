"""Plain float32 reference of one expert-parallel rank of Nemotron-3-Super
(``model_type`` ``nemotron_h``) under LoRA.

Token embedding, a stack of single-mixer layers, a final RMSNorm and an
untied head. Every layer l is ``x <- x + mixer_l(RMSNorm(x))``: ONE norm
(eps ``layer_norm_epsilon``) and ONE mixer, whose kind is the character
``hybrid_override_pattern[l]``; no biases but the convolution's. With ``h``
the layer's normed input:

``M``, Mamba-2 (``mamba_num_heads`` H heads of ``mamba_head_dim`` P = the
inner width ``expand`` x hidden; state size N = ``ssm_state_size``;
``n_groups`` G groups, head a reads group ``a // (H / G)``):

    [z | xBC | dt] = h W_in       of widths H P | H P + 2 G N | H
    xBC_t <- SiLU(b_c + sum_{j=0..K-1} w_c[j] * xBC_{t-(K-1)+j})
             (depthwise, causal, zeros before a row's first position,
             K = ``conv_kernel``, ``use_conv_bias``)
    xBC_t  = [x_t (H x P) | B_t (G x N) | C_t (G x N)]
    delta_t,a = softplus(dt_t,a + dt_bias_a)      (unclamped)
    A_a    = -exp(A_log_a)
    S_t    = exp(delta_t A) S_{t-1} + delta_t x_t B_t^T      S in R^{P x N}
             a head, zero at a row's start, run TOKEN BY TOKEN
    y_t    = S_t C_t + D_a x_t
    u      = y * SiLU(z), then over each of the G groups of H P / G
             channels  u / sqrt(mean(u^2) + eps) * w
    out    = u W_out

in a ``lax.scan`` over positions with all heads inside, whose segments are
rematerialised (the gradient keeps one state a segment, not one a token).

``*``, attention: q as ``num_attention_heads`` heads of ``head_dim``, k and
v as ``num_key_value_heads`` heads, query head a reads key-value head ``a //
(heads / kv heads)``; NO rotary and no other position term; scores ``q . k
/ sqrt(head_dim)``, causal softmax, ``o``.

``E``, experts: ``sigma = sigmoid(h W_r)`` in float32 over ALL published
experts, the router on the full hidden state; the ``num_experts_per_tok``
with the largest ``sigma + bias`` (``n_group`` = ``topk_group`` = 1: no
group limit); gates ``g_e = routed_scaling_factor * sigma_e / (sum of the
chosen sigma + 1e-20)`` (``norm_topk_prob``). Latent ``l = h W_down``
(hidden -> ``moe_latent_size``); expert e is ``E_e(l) = relu(l U_e)^2 V_e``
at latent -> ``moe_intermediate_size`` -> latent (``mlp_hidden_act``
``relu2``: NO gate product); the mixer's output is ``(sum over e chosen and
held here of g_e E_e(l)) W_up + Shared(h)`` with ``Shared(h) = relu(h
U_s)^2 V_s`` at hidden -> ``moe_shared_expert_intermediate_size`` -> hidden.

This rank holds experts ``first_expert .. first_expert + n_routed_experts
- 1`` of ``published.n_routed_experts``; what the absent ones would add is
left out (``W_up`` is linear: the ranks' routed parts add up, and the
shared expert is what every rank computes alike).

LoRA on ``in_proj out_proj``, ``q k v o``, ``latent_down latent_up`` and
the shared expert's ``up down``: the frozen product plus ``(x @ a) @ b *
(alpha / rank)``; the convolution, ``A_log``, ``D``, ``dt_bias``, the gated
norm, routers, biases and routed experts are frozen. The loss is the mean
next-token cross-entropy over the sliced vocabulary.

Imports nothing of ``fedml_tpu``; no kernel, no cache, no batching. The
frozen tree is bfloat16 (``A_log``, ``D``, ``dt_bias`` and the router's
bias float32), in the layout the driver hands to the system as is; each
layer is upcast where it is used and recomputed in the backward pass, heads
attend in groups, the experts run one at a time over the tokens that chose
them (over every token where an expert drew more than
``reference_expert_rows``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _dims(cfg):
    nh, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {"h": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
            "kv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "nh": nh, "p": p, "g": g, "n": n, "inner": nh * p,
            "wide": nh * p + 2 * g * n, "taps": cfg["conv_kernel"],
            "latent": cfg["moe_latent_size"],
            "width": cfg["moe_intermediate_size"],
            "shared": cfg["moe_shared_expert_intermediate_size"],
            "held": cfg["n_routed_experts"],
            "experts": cfg["published"]["n_routed_experts"]}


def _adapted_shapes(cfg, kind):
    """{name or (parent, name): kernel shape} of the adapted projections of
    a layer of ``kind``."""
    d = _dims(cfg)
    if kind == "M":
        return {"in_proj": (d["h"], d["inner"] + d["wide"] + d["nh"]),
                "out_proj": (d["inner"], d["h"])}
    if kind == "*":
        return {"q": (d["h"], d["heads"], d["d"]),
                "k": (d["h"], d["kv"], d["d"]),
                "v": (d["h"], d["kv"], d["d"]),
                "o": (d["heads"] * d["d"], d["h"])}
    return {"latent_down": (d["h"], d["latent"]),
            "latent_up": (d["latent"], d["h"]),
            ("shared", "up"): (d["h"], d["shared"]),
            ("shared", "down"): (d["shared"], d["h"])}


def _nest(flat):
    out = {}
    for name, value in flat.items():
        if isinstance(name, tuple):
            out.setdefault(name[0], {})[name[1]] = value
        else:
            out[name] = value
    return out


def init_frozen(key, cfg):
    """The frozen base from the seed: normal with ``initializer_range``
    rounded to bfloat16, norms at 1. A Mamba-2 layer's convolution taps are
    normal with std 0.5 and its bias 0.1 x U(-1, 1); ``A_log = log U(1,
    16)`` a head; ``dt_bias`` the inverse softplus of a log-uniform draw in
    (``time_step_min``, ``time_step_max``) floored at ``time_step_floor``
    (the family's initialisation), so that ``delta A`` covers about -1.6 to
    -0.001 a step; ``D`` = 1. The router's column for expert e has its std
    scaled by ``0.8 + 0.4 u_e`` (``u`` a seeded permutation of ``0 .. 1``),
    its bias is ``router_bias_range`` times U(-1, 1). Call under one
    ``jax.jit``."""
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

    def kernels(kind):
        return _nest({k: {"kernel": normal(s)}
                      for k, s in _adapted_shapes(cfg, kind).items()})

    p = {"embed": {"embedding": normal((cfg["vocab_size"], d["h"]))}}
    for layer, kind in enumerate(cfg["hybrid_override_pattern"]):
        mixer = kernels(kind)
        if kind == "M":
            mixer["conv_w"] = (0.5 * jax.random.normal(
                fresh(), (d["taps"], d["wide"]), jnp.float32)
            ).astype(jnp.bfloat16)
            mixer["conv_b"] = (0.1 * jax.random.uniform(
                fresh(), (d["wide"],), jnp.float32, -1.0, 1.0)
            ).astype(jnp.bfloat16)
            mixer["A_log"] = jnp.log(jax.random.uniform(
                fresh(), (d["nh"],), jnp.float32, 1.0, 16.0))
            lo, hi = cfg["time_step_min"], cfg["time_step_max"]
            step = jnp.maximum(jnp.exp(jax.random.uniform(
                fresh(), (d["nh"],), jnp.float32, math.log(lo),
                math.log(hi))), cfg["time_step_floor"])
            mixer["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
            mixer["D"] = jnp.ones((d["nh"],), jnp.float32)
            mixer["norm"] = ones(d["inner"])
        elif kind == "E":
            u = jax.random.permutation(
                fresh(), jnp.arange(d["experts"], dtype=jnp.float32)
            ) / max(d["experts"] - 1, 1)
            mixer["router"] = {"kernel": normal((d["h"], d["experts"]),
                                                0.8 + 0.4 * u[None, :])}
            mixer["router_bias"] = (
                cfg["router_bias_range"] * jax.random.uniform(
                    fresh(), (d["experts"],), jnp.float32, -1.0, 1.0))
            mixer["experts_up"] = normal((d["held"], d["latent"], d["width"]))
            mixer["experts_down"] = normal((d["held"], d["width"],
                                            d["latent"]))
        p[f"layer_{layer}"] = {"norm": ones(d["h"]), "mixer": mixer}
    p["ln_f"] = ones(d["h"])
    p["lm_head"] = {"kernel": normal((d["h"], cfg["vocab_size"]))}
    return p


def init_trainable(key, cfg):
    """Adapters in the middle of a fine-tune (``a`` normal with std 1/rank,
    ``b`` normal with std ``lora_b_std``: at ``b = 0`` every ``a`` has a
    zero gradient), float32."""
    rank = cfg["lora_rank"]
    n = [0]

    def pair(shape):
        n[0] += 1
        ka, kb = jax.random.split(jax.random.fold_in(key, n[0]))
        return {"lora_a": jax.random.normal(ka, (shape[0], rank),
                                            jnp.float32) / rank,
                "lora_b": jax.random.normal(
                    kb, (rank, math.prod(shape[1:])), jnp.float32)
                * cfg["lora_b_std"]}

    return {f"layer_{layer}": {"mixer": _nest(
        {k: pair(s) for k, s in _adapted_shapes(cfg, kind).items()})}
        for layer, kind in enumerate(cfg["hybrid_override_pattern"])}


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _causal_conv(x, w):
    """Causal depthwise convolution: ``y_t = sum_j w[j] x_{t-(K-1)+j}``.
    x [b, s, c], w [K, c]."""
    taps, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x, [(0, 0), (taps - 1, 0), (0, 0)])
    return sum(xp[:, j:j + s] * w[j] for j in range(taps))


def state_space(x, dt, a, bm, cm, skip, segment, decay=True):
    """The recurrence, token by token. x [b, s, H, P], dt [b, s, H], a [H],
    bm, cm [b, s, G, N], skip [H] -> y [b, s, H, P]; all heads and rows of
    the batch advance together inside the scan, ``segment`` tokens at a
    time under ``jax.checkpoint``. ``decay`` False is the planted fault
    ``no_decay`` (``A = 0``: the state is a plain running sum)."""
    b, s, nh, p = x.shape
    rep = nh // bm.shape[2]
    seg = math.gcd(s, segment)

    def token(st, ins):
        x_t, dt_t, b_t, c_t = ins                     # [b, H, .], [b, G, N]
        b_t, c_t = jnp.repeat(b_t, rep, 1), jnp.repeat(c_t, rep, 1)
        keep = jnp.exp(dt_t * a) if decay else jnp.ones_like(dt_t)
        st = (st * keep[..., None, None]
              + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return st, jnp.sum(st * c_t[..., None, :], -1) + skip[:, None] * x_t

    @jax.checkpoint
    def run(st, ins):
        return jax.lax.scan(token, st, ins)

    def by_segment(t):              # [b, s, ...] -> [s / seg, seg, b, ...]
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((s // seg, seg) + t.shape[1:])

    st0 = jnp.zeros((b, nh, p, bm.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(run, st0, tuple(by_segment(t)
                                        for t in (x, dt, bm, cm)))
    return jnp.moveaxis(y.reshape((s,) + y.shape[2:]), 0, 1)


def route(scores_in, bias, cfg):
    """-> (weights [T, k], experts [T, k]) as the module's docstring says."""
    s = jax.nn.sigmoid(scores_in)
    idx = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])[1]
    vals = jnp.take_along_axis(s, idx, -1)
    if cfg.get("norm_topk_prob", True):
        vals = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20)
    return vals * cfg["routed_scaling_factor"], idx


def make_model(cfg):
    d = _dims(cfg)
    scale = cfg["lora_alpha"] / cfg["lora_rank"]
    eps = cfg["layer_norm_epsilon"]
    first = cfg.get("first_expert", 0)
    heads_per_group = cfg.get("reference_heads_per_group", 8)
    rows_per_block = cfg.get("reference_rows_per_block", 1)
    expert_rows = cfg.get("reference_expert_rows", 1024)
    segment = cfg.get("reference_ssm_segment", 64)
    # the two faults that are this model's own (tools/
    # calibrate_fault_nemotron.py): never set in a configuration file
    decay = not cfg.get("fault_no_decay", False)
    act = (jax.nn.relu if cfg.get("fault_plain_relu", False)
           else lambda a: jnp.square(jax.nn.relu(a)))

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

    def mamba(x, bp, lp, quant):
        b, s, _ = x.shape
        nh, p, g, n, inner, wide = (d["nh"], d["p"], d["g"], d["n"],
                                    d["inner"], d["wide"])
        zxbcdt = proj(x, bp["in_proj"], lp["in_proj"], quant)
        z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner:inner + wide]
        dt = jax.nn.softplus(zxbcdt[..., inner + wide:] + bp["dt_bias"])
        conv = _causal_conv if quant is None else quant(_causal_conv)
        xbc = jax.nn.silu(conv(xbc, bp["conv_w"].astype(jnp.float32))
                          + bp["conv_b"].astype(jnp.float32))
        y = state_space(
            xbc[..., :inner].reshape(b, s, nh, p), dt, -jnp.exp(bp["A_log"]),
            xbc[..., inner:inner + g * n].reshape(b, s, g, n),
            xbc[..., inner + g * n:].reshape(b, s, g, n), bp["D"], segment,
            decay)
        u = (y.reshape(b, s, inner) * jax.nn.silu(z)).reshape(
            b, s, g, inner // g)
        u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True)
                              + eps)
        u = u.reshape(b, s, inner) * bp["norm"]["scale"].astype(jnp.float32)
        return proj(u, bp["out_proj"], lp["out_proj"], quant)

    def attention(x, bp, lp, quant):
        b, s, _ = x.shape
        nh, kv, dd = d["heads"], d["kv"], d["d"]
        rep = nh // kv
        q = proj(x, bp["q"], lp["q"], quant).reshape(b, s, nh, dd)
        k = proj(x, bp["k"], lp["k"], quant).reshape(b, s, kv, dd)
        v = proj(x, bp["v"], lp["v"], quant).reshape(b, s, kv, dd)
        pos = jnp.arange(s)
        live = pos[:, None] >= pos[None, :]

        @jax.checkpoint
        def heads(qkv):
            """One group of query heads with the key-value heads they read
            (repeated to them): q [b, s, g, d], k, v [b, s, g / rep, d]."""
            q, k, v = qkv
            k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
            qk = lambda a, c: jnp.einsum(  # noqa: E731
                "bqhd,bkhd->bhqk", a, c, precision=HIGHEST)
            scores = (qk(q, k) if quant is None else quant(qk)(q, k)
                      ) * dd ** -0.5
            probs = jax.nn.softmax(
                jnp.where(live[None, None], scores, -1e30), axis=-1)
            pv = lambda a, c: jnp.einsum(  # noqa: E731
                "bhqk,bkhd->bqhd", a, c, precision=HIGHEST)
            return pv(probs, v) if quant is None else quant(pv)(probs, v)

        # whole key-value heads a group: a multiple of ``rep`` query heads
        grp = heads_per_group if (nh % heads_per_group == 0
                                  and heads_per_group % rep == 0) else nh
        split = lambda a, per: jnp.moveaxis(  # noqa: E731
            a.reshape(b, s, a.shape[2] // per, per, dd), 2, 0)
        out = jax.lax.map(heads, (split(q, grp), split(k, grp // rep),
                                  split(v, grp // rep)))
        out = jnp.moveaxis(out, 0, 2).reshape(b, s, nh * dd)
        return proj(out, bp["o"], lp["o"], quant)

    def relu2(x, base, lora, quant):
        return proj(act(proj(x, base["up"], lora["up"], quant)),
                    base["down"], lora["down"], quant)

    def experts(x, bp, lp, quant):
        """Shared(h) + the held experts' gated part in the latent, one
        expert at a time. An expert that at most ``expert_rows`` tokens
        chose runs over those tokens alone (they are gathered first; a
        token that did not choose it has weight 0 and adds nothing), so it
        costs its share and not all tokens; one that drew more runs over
        every token. Either way every token that chose it is computed."""
        b, s, h = x.shape
        flat = x.reshape(b * s, h)
        logits = jnp.dot(flat, bp["router"]["kernel"].astype(jnp.float32),
                         precision=HIGHEST)
        gates, idx = route(logits, bp["router_bias"], cfg)
        low = proj(flat, bp["latent_down"], lp["latent_down"], quant)
        rows_e = min(expert_rows, b * s)

        def one(acc, inp):
            e, w_up, w_down = inp
            chose = jnp.any(idx == first + e, -1)
            gate_e = jnp.sum(jnp.where(idx == first + e, gates, 0.0), -1)

            def expert(xe):
                return mm(act(mm(xe, w_up.astype(jnp.float32), quant)),
                          w_down.astype(jnp.float32), quant)

            def its_tokens():
                take = jnp.argsort(~chose)[:rows_e]      # they come first
                return acc.at[take].add(expert(low[take])
                                        * gate_e[take][:, None])

            def every_token():
                # block by block, each rebuilt in the backward pass: the
                # scan over experts keeps room for either branch's
                # residuals, taken or not
                blocks = (low.reshape(-1, rows_e, low.shape[-1]),
                          gate_e.reshape(-1, rows_e))
                y = jax.lax.map(jax.checkpoint(
                    lambda blk: expert(blk[0]) * blk[1][:, None]), blocks)
                return acc + y.reshape(low.shape)

            return jax.lax.cond(jnp.sum(chose) > rows_e, every_token,
                                its_tokens), None

        routed, _ = jax.lax.scan(
            one, jnp.zeros_like(low),
            (jnp.arange(d["held"]), bp["experts_up"], bp["experts_down"]))
        routed = proj(routed, bp["latent_up"], lp["latent_up"], quant)
        return (relu2(x, bp["shared"], lp["shared"], quant)
                + routed.reshape(b, s, h))

    mixers = {"M": mamba, "*": attention, "E": experts}

    def make_layer(kind, quant):
        @jax.checkpoint
        def layer(x, bp, lp):
            return x + mixers[kind](_rms(x, bp["norm"]["scale"], eps),
                                    bp["mixer"], lp["mixer"], quant)
        return layer

    def forward(lora, base, tokens, quant):
        x = base["embed"]["embedding"][tokens].astype(jnp.float32)
        for i, kind in enumerate(cfg["hybrid_override_pattern"]):
            x = make_layer(kind, quant)(x, base[f"layer_{i}"],
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
    grad_fn.mixers = mixers     # kind -> (h, base, lora, quant) -> output
    return grad_fn
