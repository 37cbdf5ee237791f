"""TPU-native causal-LM for the FedLLM path.

Parity target: the reference's LLM stack builds on HF transformers
(``train/llm/configurations.py:156`` ``ModelArguments`` → ``AutoModel``
with optional flash-attn patch ``train/llm/models/attention.py:30``).
Here the model is a from-scratch flax decoder in the Llama style
(RMSNorm / rotary / SwiGLU) designed for the MXU: all hot ops are large
batched matmuls, compute dtype is configurable (bf16 by default on TPU),
and every kernel carries a partition spec over the ``fsdp`` / ``tensor``
mesh axes (the XLA-FSDP analogue of the reference's DeepSpeed ZeRO path,
``train/llm/distributed.py:21-70``).

HF checkpoint import for weight parity lives in ``hf.py``; attention
variants (Pallas flash kernel, ring attention over the ``sp`` axis) live
in ``attention.py``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


@dataclasses.dataclass
class LLMConfig:
    """Static architecture config (reference ``ModelArguments``,
    ``configurations.py:156``, minus the HF-hub plumbing)."""

    vocab_size: int = 512
    hidden_size: int = 128
    intermediate_size: int = 352
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: Optional[int] = None  # grouped-query attention; None = MHA
    max_seq_len: int = 256
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    # compute dtype for activations/matmuls; params stay float32 masters
    dtype: str = "float32"
    # attention implementation: "dense" | "flash" (Pallas) | "ring"
    attention_impl: str = "dense"
    # tie input embedding and LM head (small models)
    tie_embeddings: bool = True

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def flops_per_token(self) -> float:
        """Approximate fwd+bwd FLOPs per token (6 * params + attention),
        used by the bench's MFU report."""
        p = self.param_count()
        attn = 12 * self.num_layers * self.hidden_size * self.max_seq_len
        return 6.0 * p + attn

    def param_count(self) -> int:
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        per_layer = (h * h * 2 +                       # q, o
                     2 * h * self.kv_heads * self.head_dim +  # k, v
                     3 * h * i +                       # gate, up, down
                     2 * h)                            # 2 rmsnorms
        emb = v * h if self.tie_embeddings else 2 * v * h
        return self.num_layers * per_layer + emb + h


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary position embedding. x: [b, s, heads, head_dim]."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freq  # [b, s, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        normed = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        return (normed * scale).astype(x.dtype)


def _add_lora(x: jnp.ndarray, ys: dict, adapter, scale: float) -> dict:
    """``ys = {name: x @ W_name}``, the frozen products of the projections
    that read the same ``x``, each with its low-rank side path added:
    ``x @ W + (x @ a) @ b * scale`` (the S-LoRA batched apply). Adapters stay
    factored and are never merged into W, so a per-slot adapter gather is
    two small einsums, not a weight copy, and a training step takes the
    rank-r gradients of ``a`` and ``b`` and no weight gradient of the frozen
    kernel. The ``a`` of the projections stand side by side in ONE product:
    ``x`` is read once forward and once backward however many share it.

    ``adapter = {name: {"lora_a", "lora_b"}}`` (``None``, or a name left
    out: no side path) with leaves either shared ``[d_in, r]`` /
    ``[r, d_out]`` or per-slot ``[b, d_in, r]`` / ``[b, r, d_out]``
    (gathered from a stacked adapter bank)."""
    names = [n for n in ys if adapter is not None and n in adapter]
    if not names:
        return ys
    a = jnp.concatenate([adapter[n]["lora_a"] for n in names], axis=-1)
    xf = x.astype(jnp.float32)
    per_slot = a.ndim == 3
    h = jnp.einsum("bsd,bdr->bsr", xf, a) if per_slot else xf @ a
    out, lo = dict(ys), 0
    for n in names:
        bb = adapter[n]["lora_b"]
        hn = h[..., lo:lo + bb.shape[-2]]
        lo += bb.shape[-2]
        delta = (jnp.einsum("bsr,bro->bso", hn, bb) if per_slot
                 else hn @ bb) * scale
        out[n] = ys[n] + delta.reshape(ys[n].shape).astype(ys[n].dtype)
    return out


class Attention(nn.Module):
    cfg: LLMConfig

    @nn.compact
    def __call__(self, x, positions, attn_mask=None, kv_view=None,
                 adapter=None, lora_scale: float = 1.0):
        """Default path (``kv_view=None``): full causal self-attention,
        returns ``(out, None)``. Cache path: ``kv_view = (k_all, v_all)``
        position-ordered dense views ``[b, T, kv_heads, head_dim]`` of the
        slot's cached keys/values; the current tokens' K/V are written
        into the view at ``positions`` before attending, and returned as
        ``(out, (k_cur, v_cur))`` for the caller to scatter into the
        paged pool. ``adapter``: optional ``{q,k,v,o: {lora_a, lora_b}}``
        low-rank side paths (per-slot when leaves carry a leading batch
        axis)."""
        cfg = self.cfg
        b, s, _ = x.shape
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            feats, axis=-1, use_bias=False, name=name,
            dtype=cfg.compute_dtype, param_dtype=jnp.float32)

        qkv = _add_lora(x, {
            "q": dense((cfg.num_heads, cfg.head_dim), "q")(x),
            "k": dense((cfg.kv_heads, cfg.head_dim), "k")(x),
            "v": dense((cfg.kv_heads, cfg.head_dim), "v")(x),
        }, adapter, lora_scale)
        q, k, v = qkv["q"], qkv["k"], qkv["v"]
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)

        from .attention import cached_attention, causal_attention
        if kv_view is not None:
            k_all, v_all = kv_view
            new_kv = (k, v)
            # write the current tokens into the gathered view at their
            # logical positions (out-of-range sentinel positions — padded
            # prefill rows, inactive slots — are dropped)
            bidx = jnp.arange(b)[:, None]
            k_all = k_all.at[bidx, positions].set(k, mode="drop")
            v_all = v_all.at[bidx, positions].set(v, mode="drop")
            if cfg.kv_heads != cfg.num_heads:
                rep = cfg.num_heads // cfg.kv_heads
                k_all = jnp.repeat(k_all, rep, axis=2)
                v_all = jnp.repeat(v_all, rep, axis=2)
            out = cached_attention(q, k_all, v_all, positions)
        else:
            new_kv = None
            if cfg.kv_heads != cfg.num_heads:
                rep = cfg.num_heads // cfg.kv_heads
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            out = causal_attention(q, k, v, impl=cfg.attention_impl,
                                   attn_mask=attn_mask)
        out = out.reshape(b, s, cfg.num_heads * cfg.head_dim)
        y = nn.DenseGeneral(cfg.hidden_size, use_bias=False, name="o",
                            dtype=cfg.compute_dtype,
                            param_dtype=jnp.float32)(out)
        return _add_lora(out, {"o": y}, adapter, lora_scale)["o"], new_kv


class MLP(nn.Module):
    cfg: LLMConfig

    @nn.compact
    def __call__(self, x, adapter=None, lora_scale: float = 1.0):
        cfg = self.cfg
        dense = lambda feats, name: nn.DenseGeneral(  # noqa: E731
            feats, use_bias=False, name=name, dtype=cfg.compute_dtype,
            param_dtype=jnp.float32)

        ys = _add_lora(x, {
            "gate": dense(cfg.intermediate_size, "gate")(x),
            "up": dense(cfg.intermediate_size, "up")(x),
        }, adapter, lora_scale)
        act = nn.silu(ys["gate"]) * ys["up"]
        return _add_lora(act, {"down": dense(cfg.hidden_size, "down")(act)},
                         adapter, lora_scale)["down"]


class DecoderLayer(nn.Module):
    cfg: LLMConfig

    @nn.compact
    def __call__(self, x, positions, attn_mask=None, kv_view=None,
                 adapter=None, lora_scale: float = 1.0):
        attn = adapter.get("attn") if adapter is not None else None
        mlp = adapter.get("mlp") if adapter is not None else None
        a_out, new_kv = Attention(self.cfg, name="attn")(
            RMSNorm(self.cfg.rms_eps, name="ln_attn")(x), positions,
            attn_mask, kv_view=kv_view, adapter=attn,
            lora_scale=lora_scale)
        h = x + a_out
        h = h + MLP(self.cfg, name="mlp")(
            RMSNorm(self.cfg.rms_eps, name="ln_mlp")(h), adapter=mlp,
            lora_scale=lora_scale)
        return h, new_kv


class CausalLM(nn.Module):
    """Decoder-only LM. ``__call__(tokens [b, s]) -> logits [b, s, vocab]``.

    Cache-aware path (continuous-batching serving): pass ``positions``
    ([b, s] absolute positions; out-of-range values mark padded/inactive
    rows whose cache writes are dropped) and ``kv_view`` (per-layer
    ``(k_all, v_all)`` gathered cache views) — returns
    ``(logits, [(k_cur, v_cur), ...])`` so the caller can scatter the new
    rows into its paged pool. ``adapters``: a LoRA tree shaped like
    :func:`~fedml_tpu.llm.lora.lora_init`'s output, optionally with a
    leading per-slot batch axis on every leaf (gathered from a stacked
    adapter bank) — applied as factored side paths, never merged."""

    cfg: LLMConfig

    @nn.compact
    def __call__(self, tokens, train: bool = False, attn_mask=None,
                 positions=None, kv_view=None, adapters=None,
                 lora_scale: float = 1.0):
        cfg = self.cfg
        emb = nn.Embed(cfg.vocab_size, cfg.hidden_size, name="embed",
                       dtype=cfg.compute_dtype, param_dtype=jnp.float32)
        x = emb(tokens)
        if positions is None:
            pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)
            if cfg.attention_impl == "ring":
                # sequence is sharded over the ring axis: offset to global
                # positions so RoPE and the causal mask stay correct per
                # shard
                from .attention import _RING_AXIS
                ax = _RING_AXIS.get()
                if ax is not None:
                    pos = pos + jax.lax.axis_index(ax[0]) * tokens.shape[1]
            positions = jnp.broadcast_to(pos[None, :], tokens.shape)
        new_kvs = []
        for i in range(cfg.num_layers):
            x, new_kv = DecoderLayer(cfg, name=f"layer_{i}")(
                x, positions, attn_mask,
                kv_view=None if kv_view is None else kv_view[i],
                adapter=None if adapters is None
                else adapters.get(f"layer_{i}"),
                lora_scale=lora_scale)
            new_kvs.append(new_kv)
        x = RMSNorm(cfg.rms_eps, name="ln_f")(x)
        if cfg.tie_embeddings:
            logits = emb.attend(x)
        else:
            logits = nn.DenseGeneral(cfg.vocab_size, use_bias=False,
                                     name="lm_head", dtype=cfg.compute_dtype,
                                     param_dtype=jnp.float32)(x)
        logits = logits.astype(jnp.float32)
        if kv_view is not None:
            return logits, new_kvs
        return logits


def init_llm(cfg: LLMConfig, rng: jax.Array) -> Tuple[CausalLM, PyTree]:
    """Build the module and init params on a tiny dummy batch."""
    model = CausalLM(cfg)
    tokens = jnp.zeros((1, min(8, cfg.max_seq_len)), jnp.int32)
    params = model.init(rng, tokens)["params"]
    return model, params


def count_params(params: PyTree) -> int:
    return int(sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params)))
