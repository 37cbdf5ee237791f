"""Plain float32 reference of the Mistral-7B-v0.1 decoder under LoRA.

The model as its ``config.json`` and the Mistral 7B paper (Jiang et al. 2023)
describe it: token embedding, pre-norm decoder layers (RMSNorm, grouped-query
attention with rotary positions in the half-split convention, SwiGLU),
a final RMSNorm and an untied output head. The sliding window (4096) is
applied as written; at the cells' sequence lengths (<= 4096) it never binds.

LoRA (Hu et al. 2021) on q, k, v, o, gate, up and down: the frozen kernel's
product plus ``(x @ a) @ b * (alpha / rank)``. Only ``a`` and ``b`` train.
The loss is the mean next-token cross-entropy over all positions.

Imports nothing of ``fedml_tpu``. Kernels are held ``[in, out]`` (q, k, v as
``[in, heads, head_dim]``), the layout the driver hands to the system as is.
The gradient of a batch is accumulated over blocks of ``rows_per_block`` rows
so that float32 activations at the full widths fit beside the weights.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

TARGETS = (("attn", "q"), ("attn", "k"), ("attn", "v"), ("attn", "o"),
           ("mlp", "gate"), ("mlp", "up"), ("mlp", "down"))
HIGHEST = jax.lax.Precision.HIGHEST


def _shapes(cfg):
    """{(group, name): kernel shape} of one layer."""
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return {("attn", "q"): (h, nh, hd), ("attn", "k"): (h, nkv, hd),
            ("attn", "v"): (h, nkv, hd), ("attn", "o"): (nh * hd, h),
            ("mlp", "gate"): (h, i), ("mlp", "up"): (h, i),
            ("mlp", "down"): (i, h)}


def init_frozen(key, cfg):
    """The frozen base from the seed, float32, normal with the config's
    ``initializer_range``; norms at 1. Call under one ``jax.jit``."""
    std = cfg.get("initializer_range", 0.02)
    n = [0]

    def normal(shape):
        n[0] += 1
        return jax.random.normal(jax.random.fold_in(key, n[0]), shape,
                                 jnp.float32) * std

    h = cfg["hidden_size"]
    p = {"embed": {"embedding": normal((cfg["vocab_size"], h))}}
    for layer in range(cfg["num_hidden_layers"]):
        lp = {"attn": {}, "mlp": {},
              "ln_attn": {"scale": jnp.ones((h,), jnp.float32)},
              "ln_mlp": {"scale": jnp.ones((h,), jnp.float32)}}
        for (group, name), shape in _shapes(cfg).items():
            lp[group][name] = {"kernel": normal(shape)}
        p[f"layer_{layer}"] = lp
    p["ln_f"] = {"scale": jnp.ones((h,), jnp.float32)}
    p["lm_head"] = {"kernel": normal((h, cfg["vocab_size"]))}
    return p


def init_trainable(key, cfg):
    """Adapters in the middle of a fine-tune: ``a`` normal with std 1/rank as
    the LoRA paper starts it, ``b`` small and non-zero (std ``lora_b_std``).
    At ``b = 0`` the gradient of every ``a`` is exactly zero on the first
    step, and a comparison of first steps would be blind to half the leaves."""
    rank = cfg["lora_rank"]
    p, n = {}, 0
    for layer in range(cfg["num_hidden_layers"]):
        lp = {"attn": {}, "mlp": {}}
        for (group, name), shape in _shapes(cfg).items():
            n += 1
            ka, kb = jax.random.split(jax.random.fold_in(key, n))
            d_out = math.prod(shape[1:])
            lp[group][name] = {
                "lora_a": jax.random.normal(ka, (shape[0], rank),
                                            jnp.float32) / rank,
                "lora_b": jax.random.normal(kb, (rank, d_out), jnp.float32)
                * cfg["lora_b_std"]}
        p[f"layer_{layer}"] = lp
    return p


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x [b, s, heads, hd]; positions 0..s-1; half-split rotation."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def make_model(cfg):
    scale = cfg["lora_alpha"] / cfg["lora_rank"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    nh, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    window = cfg.get("sliding_window") or 0
    rows_per_block = cfg.get("reference_rows_per_block", 2)

    def mm(x, w, quant):
        """``x @ w``; the control routes it through its lower precision."""
        f = lambda a, b: jnp.dot(a, b, precision=HIGHEST)  # noqa: E731
        return f(x, w) if quant is None else quant(f)(x, w)

    def proj(x, base, lora, quant):
        """x [b, s, in] through one adapted kernel -> [b, s, prod(out)]."""
        w = base["kernel"].reshape(base["kernel"].shape[0], -1)
        y = mm(x, w, quant)
        return y + mm(mm(x, lora["lora_a"], quant), lora["lora_b"],
                      quant) * scale

    def forward(lora, base, tokens, quant):
        b, s = tokens.shape
        x = base["embed"]["embedding"][tokens]
        pos = jnp.arange(s)
        live = pos[:, None] >= pos[None, :]
        if window:
            live = jnp.logical_and(live, pos[:, None] - pos[None, :] < window)
        for layer in range(cfg["num_hidden_layers"]):
            bp, lp = base[f"layer_{layer}"], lora[f"layer_{layer}"]
            h = _rms(x, bp["ln_attn"]["scale"], eps)
            q = proj(h, bp["attn"]["q"], lp["attn"]["q"], quant)
            k = proj(h, bp["attn"]["k"], lp["attn"]["k"], quant)
            v = proj(h, bp["attn"]["v"], lp["attn"]["v"], quant)
            q = _rope(q.reshape(b, s, nh, hd), theta)
            k = _rope(k.reshape(b, s, nkv, hd), theta)
            v = v.reshape(b, s, nkv, hd)
            k = jnp.repeat(k, nh // nkv, axis=2)
            v = jnp.repeat(v, nh // nkv, axis=2)
            qk = lambda a, b: jnp.einsum(  # noqa: E731
                "bqhd,bkhd->bhqk", a, b, precision=HIGHEST)
            scores = (qk(q, k) if quant is None else quant(qk)(q, k)
                      ) / math.sqrt(hd)
            scores = jnp.where(live[None, None], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            pv = lambda a, b: jnp.einsum(  # noqa: E731
                "bhqk,bkhd->bqhd", a, b, precision=HIGHEST)
            att = pv(probs, v) if quant is None else quant(pv)(probs, v)
            x = x + proj(att.reshape(b, s, nh * hd), bp["attn"]["o"],
                         lp["attn"]["o"], quant)
            h = _rms(x, bp["ln_mlp"]["scale"], eps)
            gate = proj(h, bp["mlp"]["gate"], lp["mlp"]["gate"], quant)
            up = proj(h, bp["mlp"]["up"], lp["mlp"]["up"], quant)
            x = x + proj(jax.nn.silu(gate) * up, bp["mlp"]["down"],
                         lp["mlp"]["down"], quant)
        x = _rms(x, base["ln_f"]["scale"], eps)
        return mm(x, base["lm_head"]["kernel"], quant)

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

    return grad_fn
