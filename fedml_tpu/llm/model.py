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
import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..core.obs.scopes import scope

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
    # rotary frequencies' rescaling, the published ``rope_scaling`` group
    # (``type: yarn``) or None
    rope_scaling: Optional[dict] = None
    # latent attention (``kv_lora_rank > 0``): queries and keys/values pass
    # through low-rank latents with their own RMSNorms; a head's query and
    # key carry ``qk_nope_head_dim`` content dims and ``qk_rope_head_dim``
    # rotary dims (the rotary key is one vector a token, shared by all
    # heads), its value ``v_head_dim``
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # what each layer is, one entry a layer (empty: ``num_layers`` x
    # ``full+mlp``): a mixer and a feed-forward, ``mixer+ff``, or a mixer
    # alone (:class:`DecoderLayer`). Mixers: ``full`` / ``window``
    # grouped-query softmax attention, ``sparse`` grouped-query attention
    # over the keys a lightning indexer selects, ``latent`` latent
    # attention, ``linear`` Kimi delta attention, ``ssm`` Mamba-2, ``moe``
    # the expert block; feed-forwards ``mlp`` (dense) and ``moe``
    layers: Tuple[str, ...] = ()
    # the expert block: each token routed to ``num_experts_per_tok`` of
    # ``n_routed_experts`` SwiGLU experts of ``moe_intermediate_size``
    # beside ``n_shared_experts`` that every token passes. This rank of an
    # expert-parallel layer holds ``experts_held`` of them from
    # ``first_expert`` on (0 = all)
    n_routed_experts: int = 0
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    first_expert: int = 0
    experts_held: int = 0
    # group-limited routing with a score-correction bias (``noaux_tc``):
    # the ``n_group`` consecutive groups of experts are scored by their two
    # best ``score + bias``, ``topk_group`` of them stay (``n_group <= 1``:
    # plain top-k); ``router_bias`` gives the router its frozen bias
    n_group: int = 0
    topk_group: int = 0
    router_bias: bool = False
    # Kimi delta (linear) attention: heads of ``linear_head_dim``, q k v
    # through a causal depthwise convolution over 4 positions, a log-decay
    # for every key channel; the gate's form ``bounded`` (in
    # ``(kda_lower_bound, 0)``) or ``softplus``, Kimi Linear's ``g =
    # -exp(A_log) softplus(x W_fa W_fb + dt_bias)`` with no lower bound, the
    # decay's and the output gate's projections low-rank pairs through
    # ``linear_head_dim`` and the output gate a sigmoid a channel
    linear_head_dim: int = 0
    kda_lower_bound: float = -5.0
    kda_gate: str = "bounded"
    # a sigmoid gate a head on the attention's output, before ``o``
    # (latent attention; linear attention always has one)
    attn_output_gate: bool = False
    # grouped-query attention with stated head sizes: a query and key head
    # of ``head_size`` (0: ``hidden_size / num_heads``), a value head of
    # ``v_head_dim`` (0: the key head's), rotary on the first ``rotary_dim``
    # dims of a head (0: all of them), values times ``attn_value_scale``
    head_size: int = 0
    rotary_dim: int = 0
    attn_value_scale: float = 1.0
    # a window layer's query sees its last ``sliding_window`` keys, itself
    # among them, and has its own count of key-value heads and rotary base
    # (0: the full layers'); ``window_sink`` / ``full_sink`` give a kind's
    # softmax one learned logit a query head that takes mass and carries no
    # value
    sliding_window: int = 0
    window_kv_heads: int = 0
    window_rope_theta: float = 0.0
    window_sink: bool = False
    full_sink: bool = False
    # a state-space mixer: ``ssm_heads`` heads of ``ssm_head_dim`` channels
    # with a state of ``ssm_state_size`` a channel, B and C shared by the
    # heads of each of ``ssm_groups`` groups, a causal depthwise
    # convolution over ``ssm_conv_kernel`` positions (with a bias where
    # ``ssm_conv_bias``), in chunks of ``ssm_chunk`` positions
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state_size: int = 0
    ssm_groups: int = 1
    ssm_conv_kernel: int = 4
    ssm_conv_bias: bool = True
    ssm_chunk: int = 128
    # the feed-forward's activation, dense, shared and routed alike:
    # ``swiglu`` (``silu(x W_gate) * (x W_up)``, three kernels) or ``relu2``
    # (``relu(x W_up)^2``, two kernels and no gate product)
    mlp_activation: str = "swiglu"
    # routed experts that work in a latent of ``moe_latent_size`` (0: on the
    # hidden state itself) between a projection down and one up; the router
    # and the shared expert read the hidden state. ``shared_expert_size``:
    # the shared expert's width (0: ``moe_intermediate_size *
    # n_shared_experts``)
    moe_latent_size: int = 0
    shared_expert_size: int = 0
    # False: attention without rotary or any other position term
    use_rope: bool = True
    # the router's scores: ``sigmoid`` of each logit, or ``softmax`` over
    # all ``n_routed_experts`` logits (both in float32)
    router_scoring: str = "sigmoid"
    # learned sparse attention (``sparse`` layers): grouped-query heads with
    # an RMSNorm a head on q and k where ``qk_norm``; a lightning indexer of
    # ``index_heads`` heads of ``index_head_dim`` and one index key head
    # picks the ``index_topk`` keys each query attends to
    qk_norm: bool = False
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # rematerialize each layer in the backward pass: a layer keeps its input
    # (and a sparse layer its selection) for the backward and computes the
    # rest of its forward again there, so the saved activations no longer
    # grow with the layers held
    remat: bool = False

    def __post_init__(self):
        self.layers = tuple(self.layers)
        parts = [k.partition("+") for k in self.plan]
        if len(parts) != self.num_layers or any(
                m not in _MIXERS or ff not in (_FEED_FORWARDS if plus else "")
                for m, plus, ff in parts):
            raise ValueError(
                f"layers {self.layers}: one entry for each of the "
                f"{self.num_layers} layers, a mixer of {tuple(_MIXERS)} "
                f"alone or with '+' a feed-forward of "
                f"{tuple(_FEED_FORWARDS)}")

    @property
    def plan(self) -> Tuple[str, ...]:
        """Each layer's kind: ``layers``, or ``num_layers`` x ``full+mlp``
        where that is empty."""
        return self.layers or ("full+mlp",) * self.num_layers

    @property
    def head_dim(self) -> int:
        return self.head_size or self.hidden_size // self.num_heads

    @property
    def held(self) -> int:
        return self.experts_held or self.n_routed_experts

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    def param_count(self) -> int:
        if (set(self.plan) - {"full+mlp"} or self.head_size
                or self.v_head_dim or self.mlp_activation != "swiglu"):
            raise NotImplementedError(
                "LLMConfig.param_count counts the dense grouped-query "
                "SwiGLU decoder at head size hidden_size / num_heads alone; "
                "a configuration with layers other than full+mlp, stated "
                "head sizes or a non-gated feed-forward (mlp_activation) is "
                "counted from its shapes under benchmarks/flops/")
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        per_layer = (h * h * 2 +                       # q, o
                     2 * h * self.kv_heads * self.head_dim +  # k, v
                     3 * h * i +                       # gate, up, down
                     2 * h)                            # 2 rmsnorms
        emb = v * h if self.tie_embeddings else 2 * v * h
        return self.num_layers * per_layer + emb + h


def rope_frequencies(dim: int, theta: float,
                     scaling: Optional[dict] = None) -> jnp.ndarray:
    """The ``dim // 2`` rotary frequencies. ``scaling`` (``type: yarn``;
    Peng et al. 2023 as the DeepSeek-V3 modelling code computes it) blends
    each plain frequency with its ``factor``-times-slower interpolation:
    dimensions that turn more than ``beta_fast`` times within the original
    context keep theirs, those under ``beta_slow`` turns are interpolated,
    a linear ramp between."""
    half = dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if scaling is None:
        return freq
    if scaling.get("type", scaling.get("rope_type")) != "yarn":
        raise NotImplementedError(f"rope_scaling {scaling!r}")
    orig = scaling["original_max_position_embeddings"]

    def turns_dim(turns):
        return dim * np.log(orig / (turns * 2 * np.pi)) / (2 * np.log(theta))

    low = max(np.floor(turns_dim(scaling["beta_fast"])), 0)
    high = min(np.ceil(turns_dim(scaling["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0, 1)
    return freq / scaling["factor"] * ramp + freq * (1 - ramp)


def yarn_mscale(scaling: Optional[dict], key: str) -> float:
    """YaRN's magnitude term ``0.1 * m * ln(factor) + 1`` for the group's
    ``mscale`` or ``mscale_all_dim`` (1 without scaling or at m = 0)."""
    if scaling is None or scaling.get("factor", 1) <= 1:
        return 1.0
    return 0.1 * scaling.get(key, 0) * float(np.log(scaling["factor"])) + 1.0


def _rope(x: jnp.ndarray, positions: jnp.ndarray, freq) -> jnp.ndarray:
    """Rotary position embedding in the half-split convention.
    x: [b, s, heads, head_dim]; ``freq``: its ``head_dim // 2``
    frequencies."""
    half = x.shape[-1] // 2
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


def _dense(cfg: LLMConfig, feats, name: str, **kw) -> nn.DenseGeneral:
    """A projection of the last axis without a bias, in the compute dtype
    over float32 parameters."""
    return nn.DenseGeneral(feats, use_bias=False, name=name,
                           dtype=cfg.compute_dtype, param_dtype=jnp.float32,
                           **kw)


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
    with scope("lora"):
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
    """Grouped-query softmax attention. ``window``: this layer is of the
    configuration's window kind (its key-value heads, rotary base, sliding
    window and sink flag). ``q = x W_q`` as heads of ``head_dim``, ``k``
    likewise, ``v`` as heads of ``v_head_dim`` (default: the same size);
    rotary on the first ``rotary_dim`` dims of every query and key head (on
    none where ``use_rope`` is off: the scores then carry no position);
    ``v`` times ``attn_value_scale``; scores at ``head_dim ** -0.5``; with a
    sink, a frozen logit a query head beside the row's scores.

    A cache path exists for full causal layers of one head size without a
    sink (partial rotary and the value scale ride along); a window layer,
    a sink or ``head_dim != v_head_dim`` are the training path's alone:
    ``llm/kv_cache.py`` holds one shape of block for every layer and frees
    none as a window slides."""

    cfg: LLMConfig
    window: bool = False

    @nn.compact
    def __call__(self, x, positions, attn_mask=None, kv_view=None,
                 adapter=None, lora_scale: float = 1.0):
        """Default path (``kv_view=None``): causal self-attention over the
        row, returns ``(out, None)``. Cache path: ``kv_view = (k_all,
        v_all)`` position-ordered dense views ``[b, T, kv_heads,
        head_dim]`` of the slot's cached keys/values; the current tokens'
        K/V are written into the view at ``positions`` before attending,
        and returned as ``(out, (k_cur, v_cur))`` for the caller to scatter
        into the paged pool. ``adapter``: optional ``{q,k,v,o: {lora_a,
        lora_b}}`` low-rank side paths (per-slot when leaves carry a
        leading batch axis)."""
        cfg = self.cfg
        b, s, _ = x.shape
        d_qk, d_v = cfg.head_dim, cfg.v_head_dim or cfg.head_dim
        kv_heads = (cfg.window_kv_heads if self.window and cfg.window_kv_heads
                    else cfg.kv_heads)
        theta = (cfg.window_rope_theta if self.window and cfg.window_rope_theta
                 else cfg.rope_theta)
        window = cfg.sliding_window if self.window else None
        has_sink = cfg.window_sink if self.window else cfg.full_sink
        if kv_view is not None and (window or has_sink or d_qk != d_v):
            raise NotImplementedError(
                "attention with a sliding window, a sink or a value head "
                "narrower than the key head has no cache path: "
                "llm/kv_cache.py holds blocks of one shape for every layer "
                "and keeps them all, where a window layer frees what slid "
                "out")
        dense = functools.partial(_dense, cfg)

        qkv = _add_lora(x, {
            "q": dense((cfg.num_heads, d_qk), "q")(x),
            "k": dense((kv_heads, d_qk), "k")(x),
            "v": dense((kv_heads, d_v), "v")(x),
        }, adapter, lora_scale)
        q, k, v = qkv["q"], qkv["k"], qkv["v"]
        rotary = (cfg.rotary_dim or d_qk) if cfg.use_rope else 0
        if rotary:      # 0: no position term at all
            freq = rope_frequencies(rotary, theta, cfg.rope_scaling)
        if rotary == d_qk:
            q = _rope(q, positions, freq)
            k = _rope(k, positions, freq)
        elif rotary:    # the head's first dims turn, the others pass
            q, k = (jnp.concatenate(
                [_rope(a[..., :rotary], positions, freq), a[..., rotary:]],
                -1) for a in (q, k))
        if cfg.attn_value_scale != 1.0:
            v = (v.astype(jnp.float32) * cfg.attn_value_scale).astype(v.dtype)
        sink = (self.param("sink", nn.initializers.zeros, (cfg.num_heads,))
                if has_sink else None)

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
            if kv_heads != cfg.num_heads:
                rep = cfg.num_heads // kv_heads
                k_all = jnp.repeat(k_all, rep, axis=2)
                v_all = jnp.repeat(v_all, rep, axis=2)
            out = cached_attention(q, k_all, v_all, positions)
        else:
            new_kv = None
            # the window kernels read a key-value head once for its group
            grouped = window and cfg.attention_impl == "flash"
            if kv_heads != cfg.num_heads and not grouped:
                rep = cfg.num_heads // kv_heads
                k = jnp.repeat(k, rep, axis=2)
                v = jnp.repeat(v, rep, axis=2)
            extra = {"window": window} if window else {}
            if has_sink:
                extra["sink"] = sink.astype(jnp.float32)
            out = causal_attention(q, k, v, impl=cfg.attention_impl,
                                   attn_mask=attn_mask, **extra)
            if self.window:
                self.sow("attn_stats", "window_layer_steps", jnp.float32(1),
                         init_fn=lambda: jnp.float32(0), reduce_fn=jnp.add)
        out = out.reshape(b, s, cfg.num_heads * d_v)
        y = dense(cfg.hidden_size, "o")(out)
        return _add_lora(out, {"o": y}, adapter, lora_scale)["o"], new_kv


def _head_gate(out, logits):
    """out [b, s, h, d] times ``sigmoid(logits)`` [b, s, h], one gate a
    head, in float32."""
    gate = jax.nn.sigmoid(logits.astype(jnp.float32))[..., None]
    return (out.astype(jnp.float32) * gate).astype(out.dtype)


class _LayerNorm(nn.Module):
    """LayerNorm over the last axis with a weight and a bias, in float32."""

    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        width = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (width,))
        bias = self.param("bias", nn.initializers.zeros, (width,))
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + self.eps)
        return (y * scale.astype(jnp.float32)
                + bias.astype(jnp.float32)).astype(x.dtype)


class SparseAttention(nn.Module):
    """Grouped-query attention over the keys a lightning indexer selects
    (DeepSeek-V3.2-Exp's sparse attention; ``llm/sparse_attention.py``).
    ``q = x W_q``, ``k = x W_k``, ``v = x W_v`` as heads of ``head_dim``;
    with ``qk_norm`` an RMSNorm a head over q and k; rotary on every dim.
    The indexer reads ``x`` with no gradient: ``qi = rot(x W_iq)`` as
    ``index_heads`` heads of ``index_head_dim``, ``ki = rot(LayerNorm(x
    W_ik))`` one head, the rotary on the first half of an index head's
    dims at ``rope_theta``, ``w = x W_iw * (index_heads * index_head_dim)
    ** -0.5`` in float32; query ``t`` keeps the ``min(index_topk, t + 1)``
    keys of the largest ``sum_j w_j relu(qi_j . ki)``, exactly, and every
    query head attends to those alone at ``head_dim ** -0.5``. Adapters on
    ``q k v o``; the norms and the indexer are frozen. Training path only:
    ``llm/kv_cache.py`` holds no index key a position."""

    cfg: LLMConfig

    @nn.compact
    def __call__(self, x, positions, attn_mask=None, kv_view=None,
                 adapter=None, lora_scale: float = 1.0):
        if kv_view is not None or attn_mask is not None:
            raise NotImplementedError(
                "sparse attention has no cache path and no key mask: "
                "llm/kv_cache.py holds no index key a position, and a "
                "decode step selects among the cached keys by them")
        from .sparse_attention import select_keys, sparse_attend

        cfg = self.cfg
        b, s, _ = x.shape
        d, heads, width = cfg.head_dim, cfg.index_heads, cfg.index_head_dim
        dense = functools.partial(_dense, cfg)
        qkv = _add_lora(x, {
            "q": dense((cfg.num_heads, d), "q")(x),
            "k": dense((cfg.kv_heads, d), "k")(x),
            "v": dense((cfg.kv_heads, d), "v")(x),
        }, adapter, lora_scale)
        q, k = qkv["q"], qkv["k"]
        if cfg.qk_norm:
            q = RMSNorm(cfg.rms_eps, name="q_norm")(q)
            k = RMSNorm(cfg.rms_eps, name="k_norm")(k)
        freq = rope_frequencies(d, cfg.rope_theta, cfg.rope_scaling)
        q, k = _rope(q, positions, freq), _rope(k, positions, freq)
        with scope("attn.index"):
            xs = jax.lax.stop_gradient(x)
            qi = dense((heads, width), "index_q")(xs)
            ki = _LayerNorm(cfg.rms_eps, name="index_norm")(
                dense(width, "index_k")(xs))[:, :, None, :]
            w = nn.DenseGeneral(heads, use_bias=False, name="index_w",
                                dtype=jnp.float32, param_dtype=jnp.float32)(
                xs.astype(jnp.float32)) * (heads * width) ** -0.5
            rot = width // 2
            ifreq = rope_frequencies(rot, cfg.rope_theta)
            qi, ki = (jnp.concatenate(
                [_rope(a[..., :rot], positions, ifreq), a[..., rot:]], -1)
                for a in (qi, ki))
            words = select_keys(qi, ki[:, :, 0], w, cfg.index_topk,
                                impl=cfg.attention_impl)
            # kept by a rematerialized layer (``LLMConfig.remat``): the
            # backward reads the selection and does not run the indexer
            words = checkpoint_name(words, "selection")
        # the selection's bits, for a caller that asks for intermediates
        self.sow("intermediates", "selection", words)
        out = sparse_attend(q, k, qkv["v"], words, cfg.index_topk,
                            impl=cfg.attention_impl)
        out = out.reshape(b, s, cfg.num_heads * d)
        y = dense(cfg.hidden_size, "o")(out)
        return _add_lora(out, {"o": y}, adapter, lora_scale)["o"], None


class LinearAttention(nn.Module):
    """Kimi delta attention (``llm/linear_attention.py``): ``q, k, v =
    SiLU(conv(x W))`` through a causal depthwise convolution, ``q`` and
    ``k`` L2-normalised a head and ``q`` scaled by ``d ** -0.5``; ``beta =
    sigmoid(x W_b)`` a head; log-decay ``g = lower * sigmoid(exp(A_log) *
    (x W_f + dt_bias))`` for every key channel, so ``exp(g)`` lies in
    ``(e^lower, 1)``; the gated delta rule over the row from a zero
    state; ``y = (RMSNorm_head(o) * sigmoid(x W_g)_h) W_o``. Adapters on
    ``q k v f o``; the convolutions, ``W_b``, ``W_g``, ``A_log`` and
    ``dt_bias`` are frozen. With ``kda_gate`` ``softplus`` (Kimi Linear):
    ``g = -exp(A_log) softplus(x W_fa W_fb + dt_bias)``, unbounded below,
    and ``y = (RMSNorm_head(o) * sigmoid(x W_ga W_gb)) W_o``, a gate a
    channel; ``W_fa``, ``W_ga`` [hidden, d] and ``W_fb``, ``W_gb`` [d, h *
    d] frozen, adapters on ``q k v o``. A masked key neither writes nor
    decays the state. The module makes the products; everything between
    them is ``kda_layer``'s (fused passes around the kernels, float32
    inside). Training path only: a recurrent state is no list of cached
    blocks."""

    cfg: LLMConfig

    @nn.compact
    def __call__(self, x, positions, attn_mask=None, kv_view=None,
                 adapter=None, lora_scale: float = 1.0):
        del positions   # the recurrence carries order; no rotary
        if kv_view is not None:
            raise NotImplementedError(
                "linear attention has no cache path: llm/kv_cache.py holds "
                "a list of key/value blocks a position, not the one "
                "recurrent state and convolution tail a row of a KDA layer "
                "carries")
        from .linear_attention import (MIN_LOG_DECAY, SHORT_CONV_TAPS,
                                       kda_layer)

        cfg = self.cfg
        unbounded = cfg.kda_gate == "softplus"
        if not unbounded and not MIN_LOG_DECAY <= cfg.kda_lower_bound < 0:
            raise ValueError(
                f"kda_lower_bound {cfg.kda_lower_bound}: the chunked delta "
                f"rule is exact for log-decays in [{MIN_LOG_DECAY}, 0)")
        nh, d, taps = cfg.num_heads, cfg.linear_head_dim, SHORT_CONV_TAPS
        dense = functools.partial(_dense, cfg)
        # the decay projection leaves its product in float32: the gate
        # multiplies it by up to exp(A_log) = 16
        wide = functools.partial(dense, nh * d, dot_general=functools.partial(
            jax.lax.dot_general, preferred_element_type=jnp.float32))
        if unbounded:
            ys = _add_lora(x, {n: dense(nh * d, n)(x) for n in "qkv"},
                           adapter, lora_scale)
            ys["f"] = wide("f_b")(dense(d, "f_a")(x))
        else:
            ys = _add_lora(x, {**{n: dense(nh * d, n)(x) for n in "qkv"},
                               "f": wide("f")(x)}, adapter, lora_scale)
        conv = [self.param(f"conv_{n}", nn.initializers.lecun_normal(),
                           (taps, nh * d)) for n in "qkv"]
        a_log = self.param("A_log", nn.initializers.zeros, (nh,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (nh * d,))
        beta = dense(nh, "b")(x)
        gates = (dense(nh * d, "g_b")(dense(d, "g_a")(x)) if unbounded
                 else dense(nh, "g")(x))
        # everything between the products and the kernels, and between the
        # kernels and the output product, is the layer's own fused pass
        out = kda_layer(ys, beta, gates, conv, a_log, dt_bias,
                        _NormScale(name="o_norm")(d), attn_mask, heads=nh,
                        lower=None if unbounded else cfg.kda_lower_bound,
                        eps=cfg.rms_eps, impl=cfg.attention_impl)
        stats = {"layer_steps": jnp.float32(1)}
        if unbounded:       # the live and the steep log-decays, counted
            out, (stats["decays"], stats["steep_decays"]) = out
        y = dense(cfg.hidden_size, "o")(out)
        for name, value in stats.items():
            self.sow("kda_stats", name, value,
                     init_fn=lambda: jnp.float32(0), reduce_fn=jnp.add)
        return _add_lora(out, {"o": y}, adapter, lora_scale)["o"], None


class _NormScale(nn.Module):
    """An :class:`RMSNorm`'s parameter under its name, for a caller that
    applies the norm itself (the KDA layer's fused pass)."""

    @nn.compact
    def __call__(self, width: int):
        return self.param("scale", nn.initializers.ones, (width,))


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3): ``c_q = norm(x W_qa)``,
    ``q = c_q W_qb`` -> heads of ``nope + rope`` dims (``q = x W_q``
    where ``q_lora_rank`` is 0: no query latent); ``[c_kv | k_r] =
    x W_kva``, ``[k_nope | v] = norm(c_kv) W_kvb``; rotary on ``q_rope`` and
    on the one ``k_r`` all heads share (neither turns where ``use_rope`` is
    off: the scores then carry no position); scores scaled by ``(nope +
    rope) ** -0.5`` times YaRN's ``mscale_all_dim`` term squared; with
    ``attn_output_gate`` a head's output is scaled by ``sigmoid(x W_g)_h``
    before ``W_o``. Training path only: a cache of latents is serving
    work."""

    cfg: LLMConfig

    @nn.compact
    def __call__(self, x, positions, attn_mask=None, kv_view=None,
                 adapter=None, lora_scale: float = 1.0):
        if kv_view is not None:
            raise NotImplementedError(
                "latent attention has no cache path: llm/kv_cache.py holds "
                "per-head keys and values, not the kv_lora_rank latent and "
                "the shared rotary key a latent cache stores")
        cfg = self.cfg
        b, s, _ = x.shape
        nh, nope, rope, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                              cfg.qk_rope_head_dim, cfg.v_head_dim)
        dense = functools.partial(_dense, cfg)

        first = ({"q_a": dense(cfg.q_lora_rank, "q_a")(x)}
                 if cfg.q_lora_rank
                 else {"q": dense((nh, nope + rope), "q")(x)})
        down = _add_lora(x, {
            **first, "kv_a": dense(cfg.kv_lora_rank + rope, "kv_a")(x),
        }, adapter, lora_scale)
        c_q = (RMSNorm(cfg.rms_eps, name="q_norm")(down["q_a"])
               if cfg.q_lora_rank else None)
        c_kv = RMSNorm(cfg.rms_eps, name="kv_norm")(
            down["kv_a"][..., :cfg.kv_lora_rank])
        k_r = down["kv_a"][..., cfg.kv_lora_rank:]
        q = (_add_lora(c_q, {"q_b": dense((nh, nope + rope), "q_b")(c_q)},
                       adapter, lora_scale)["q_b"]
             if cfg.q_lora_rank else down["q"])
        kv = _add_lora(c_kv, {"kv_b": dense((nh, nope + dv), "kv_b")(c_kv)},
                       adapter, lora_scale)["kv_b"]
        if cfg.use_rope:
            freq = rope_frequencies(rope, cfg.rope_theta, cfg.rope_scaling)
            q = jnp.concatenate(
                [q[..., :nope], _rope(q[..., nope:], positions, freq)], -1)
            k_r = _rope(k_r[:, :, None, :], positions, freq)
        else:
            k_r = k_r[:, :, None, :]
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_r, (b, s, nh, rope))], -1)
        m = yarn_mscale(cfg.rope_scaling, "mscale_all_dim")

        from .attention import causal_attention
        out = causal_attention(q, k, kv[..., nope:],
                               impl=cfg.attention_impl,
                               attn_mask=attn_mask,
                               scale=(nope + rope) ** -0.5 * m * m)
        if cfg.attn_output_gate:
            out = _head_gate(out, dense(nh, "g")(x))
        out = out.reshape(b, s, nh * dv)
        y = dense(cfg.hidden_size, "o")(out)
        return _add_lora(out, {"o": y}, adapter, lora_scale)["o"], None


class MLP(nn.Module):
    """The dense feed-forward (and the shared expert): ``(silu(x W_gate) *
    (x W_up)) W_down`` where ``cfg.mlp_activation`` is ``swiglu``,
    ``relu(x W_up)^2 W_down`` (no ``gate`` kernel) where it is ``relu2``;
    no other activation exists."""

    cfg: LLMConfig
    width: Optional[int] = None   # None: cfg.intermediate_size

    @nn.compact
    def __call__(self, x, adapter=None, lora_scale: float = 1.0):
        cfg = self.cfg
        width = self.width or cfg.intermediate_size
        dense = functools.partial(_dense, cfg)

        with scope("mlp"):
            if cfg.mlp_activation == "relu2":
                up = _add_lora(x, {"up": dense(width, "up")(x)}, adapter,
                               lora_scale)["up"]
                act = jnp.square(nn.relu(up))
                return _add_lora(
                    act, {"down": dense(cfg.hidden_size, "down")(act)},
                    adapter, lora_scale)["down"]
            ys = _add_lora(x, {
                "gate": dense(width, "gate")(x),
                "up": dense(width, "up")(x),
            }, adapter, lora_scale)
            act = nn.silu(ys["gate"]) * ys["up"]
            return _add_lora(
                act, {"down": dense(cfg.hidden_size, "down")(act)},
                adapter, lora_scale)["down"]


class MoE(nn.Module):
    """``shared(x) + sum over the top-k experts this rank holds of g_e
    E_e(x)`` (the routed sum alone where ``n_shared_experts`` is 0): sigmoid
    (or, by ``router_scoring``, softmax) scores in float32 over ALL
    ``n_routed_experts``, plain
    top-k (group-limited with a score-correction bias where ``n_group`` or
    ``router_bias`` is set: ``moe.route``), the chosen scores normalised
    and scaled; the rank computes the
    part of its own ``cfg.held`` experts (``first_expert`` on) and leaves
    out what absent experts would add. Dropless. The routed experts and the
    router are frozen (no adapters, no weight gradient); the shared expert
    is an :class:`MLP` and takes ``adapter["shared"]``. Router load leaves
    through the ``moe_stats`` collection as sums.

    The experts are SwiGLUs (``experts_gate`` / ``_up`` / ``_down``) or,
    with ``mlp_activation`` ``relu2``, non-gated ``relu(x U)^2 V``
    (``experts_up`` / ``_down`` alone); no other activation exists. With
    ``moe_latent_size`` they work in a latent: ``l = x W_latent_down``, the
    routed sum over ``E_e(l)``, then ``W_latent_up`` back to the hidden
    size (both projections take adapters); the router and the shared
    expert read ``x`` itself."""

    cfg: LLMConfig

    @nn.compact
    def __call__(self, x, adapter=None, lora_scale: float = 1.0):
        from . import moe

        cfg = self.cfg
        b, s, h = x.shape
        held, width = cfg.held, cfg.moe_intermediate_size
        inner = cfg.moe_latent_size or h      # the width the experts read
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate = (None if cfg.mlp_activation == "relu2" else
                  self.param("experts_gate", init, (held, inner, width)))
        w_up = self.param("experts_up", init, (held, inner, width))
        w_down = self.param("experts_down", init, (held, width, inner))
        shared = None
        if cfg.n_shared_experts:
            shared = MLP(cfg, cfg.shared_expert_size
                         or width * cfg.n_shared_experts, name="shared")(
                x, adapter=None if adapter is None else adapter.get("shared"),
                lora_scale=lora_scale)
        flat = x.reshape(b * s, h)
        with scope("moe.route"):
            logits = nn.DenseGeneral(
                cfg.n_routed_experts, use_bias=False, name="router",
                dtype=jnp.float32, param_dtype=jnp.float32)(
                flat.astype(jnp.float32))
            bias = (self.param("router_bias", nn.initializers.zeros,
                               (cfg.n_routed_experts,))
                    if cfg.router_bias else None)
            gates, chosen = moe.route(logits, cfg.num_experts_per_tok,
                                      cfg.routed_scaling_factor,
                                      cfg.norm_topk_prob, bias, cfg.n_group,
                                      cfg.topk_group, cfg.router_scoring)

        def latent(t, feats, name):
            """One of the two projections around the routed experts, its
            adapter's side path added."""
            with scope("moe.latent"):
                y = _dense(cfg, feats, name)(t)
                return _add_lora(t, {name: y}, adapter, lora_scale)[name]

        if cfg.moe_latent_size:
            flat = latent(x, inner, "latent_down").reshape(b * s, inner)
        with scope("moe.experts"):
            routed, stats = moe.routed_experts(
                flat, gates, chosen, w_gate, w_up, w_down, cfg.first_expert,
                cfg.n_routed_experts)
        if cfg.n_group > 1:
            # under a group limit a token may send this rank nothing
            local = chosen - cfg.first_expert
            stats["tokens_here"] = jnp.sum(jnp.any(
                (local >= 0) & (local < held), -1)).astype(jnp.float32)
        for k, v in stats.items():
            self.sow("moe_stats", k, v, init_fn=lambda: jnp.float32(0),
                     reduce_fn=jnp.add)
        if cfg.moe_latent_size:
            routed = latent(routed.reshape(b, s, inner).astype(x.dtype), h,
                            "latent_up")
        if shared is None:
            return routed.reshape(b, s, h).astype(x.dtype)
        return shared + routed.reshape(b, s, h).astype(shared.dtype)


def _causal_conv(x, w, bias):
    """Causal depthwise convolution ``y_t = bias + sum_j w[j] x_{t-(K-1)+j}``
    with zeros before the row, in float32. x [b, s, c], w [K, c]."""
    taps, s = w.shape[0], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), [(0, 0), (taps - 1, 0), (0, 0)])
    y = sum(xp[:, j:j + s] * w[j].astype(jnp.float32) for j in range(taps))
    return y if bias is None else y + bias.astype(jnp.float32)


class Mamba2(nn.Module):
    """The Mamba-2 mixer (``llm/state_space.py``): ``[z | xBC | dt] = x
    W_in``; ``xBC = SiLU(conv(xBC))`` through a causal depthwise convolution
    with a bias, split into ``x`` (``ssm_heads`` heads of ``ssm_head_dim``),
    ``B`` and ``C`` (``ssm_groups`` groups of ``ssm_state_size``); step size
    ``delta = softplus(dt + dt_bias)`` a head, unclamped; decay ``A =
    -exp(A_log)`` a head; the recurrence ``S_t = exp(delta A) S_{t-1} +
    delta x_t B_t^T``, ``y_t = S_t C_t + D x_t`` over the row from a zero
    state; ``u = y * SiLU(z)`` normalised over each group's channels
    (``u / sqrt(mean u^2 + eps) * w``); ``u W_out``. Adapters on ``in_proj``
    and ``out_proj``; the convolution, ``A_log``, ``D``, ``dt_bias`` and the
    norm are frozen. A masked position neither writes nor decays the state.
    The module makes the two products; on the ``flash`` path everything
    between them is ``ssm_layer``'s (fused passes around the kernels,
    float32 inside), on any other it is :meth:`_dense`'s ``jax.numpy``.
    Training path only: a recurrent state is no list of cached blocks."""

    cfg: LLMConfig

    @nn.compact
    def __call__(self, x, positions, attn_mask=None, kv_view=None,
                 adapter=None, lora_scale: float = 1.0):
        del positions   # the recurrence carries order
        if kv_view is not None:
            raise NotImplementedError(
                "a state-space mixer has no cache path: llm/kv_cache.py "
                "holds a list of key/value blocks a position, not the one "
                "recurrent state and convolution tail a row of a Mamba-2 "
                "layer carries")
        from .state_space import ssm_layer

        cfg = self.cfg
        nh, p, n, g = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size,
                       cfg.ssm_groups)
        inner, wide = nh * p, nh * p + 2 * g * n
        dense = functools.partial(_dense, cfg)

        zxbcdt = _add_lora(x, {"in_proj": dense(inner + wide + nh,
                                                "in_proj")(x)},
                           adapter, lora_scale)["in_proj"]
        conv_w = self.param("conv_w", nn.initializers.lecun_normal(),
                            (cfg.ssm_conv_kernel, wide))
        conv_b = (self.param("conv_b", nn.initializers.zeros, (wide,))
                  if cfg.ssm_conv_bias else None)
        a_log = self.param("A_log", nn.initializers.zeros, (nh,))
        skip = self.param("D", nn.initializers.ones, (nh,))
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (nh,))
        scale = _NormScale(name="norm")(inner)
        if cfg.attention_impl == "flash":
            # everything between the products and the kernels, and between
            # the kernels and the output product, is the layer's own pass
            u = ssm_layer(zxbcdt, attn_mask, conv_w, conv_b, a_log, skip,
                          dt_bias, scale, heads=nh, head_dim=p, groups=g,
                          state=n, chunk=cfg.ssm_chunk, eps=cfg.rms_eps)
        else:
            u = self._dense(zxbcdt, attn_mask, conv_w, conv_b, a_log, skip,
                            dt_bias, scale, x.dtype)
        out = dense(cfg.hidden_size, "out_proj")(u)
        self.sow("ssm_stats", "layer_steps", jnp.float32(1),
                 init_fn=lambda: jnp.float32(0), reduce_fn=jnp.add)
        return _add_lora(u, {"out_proj": out}, adapter,
                         lora_scale)["out_proj"], None

    def _dense(self, zxbcdt, attn_mask, conv_w, conv_b, a_log, skip, dt_bias,
               scale, dtype):
        """The same layer between the products as ``jax.numpy`` around
        :func:`ssd_scan` (the ``dense`` path, and the fused passes'
        reference)."""
        from .state_space import ssd_scan

        cfg = self.cfg
        b, s, _ = zxbcdt.shape
        f32 = jnp.float32
        nh, p, n, g = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state_size,
                       cfg.ssm_groups)
        inner, wide = nh * p, nh * p + 2 * g * n
        z, xbc = zxbcdt[..., :inner], zxbcdt[..., inner:inner + wide]
        dt = jax.nn.softplus(zxbcdt[..., inner + wide:].astype(f32)
                             + dt_bias.astype(f32))
        if attn_mask is not None:
            keep = attn_mask.astype(f32)[..., None]
            xbc, dt = xbc * keep.astype(xbc.dtype), dt * keep
        # rebuilt in the backward pass from its bfloat16 input: the float32
        # rows before the SiLU are not kept
        xbc = jax.checkpoint(lambda t, w, c: jax.nn.silu(
            _causal_conv(t, w, c)).astype(dtype))(xbc, conv_w, conv_b)
        y = ssd_scan(xbc[..., :inner].reshape(b, s, nh, p), dt,
                     -jnp.exp(a_log.astype(f32)),
                     xbc[..., inner:inner + g * n].reshape(b, s, g, n),
                     xbc[..., inner + g * n:].reshape(b, s, g, n),
                     skip, chunk=cfg.ssm_chunk)
        @jax.checkpoint     # float32 inside, rebuilt from y and z
        def gated_norm(y, z, scale):
            """The gate multiplies before the norm, which runs a group."""
            u = (y.reshape(b, s, inner).astype(f32)
                 * jax.nn.silu(z.astype(f32))).reshape(b, s, g, inner // g)
            u = u * jax.lax.rsqrt(jnp.mean(jnp.square(u), -1, keepdims=True)
                                  + cfg.rms_eps)
            return (u.reshape(b, s, inner) * scale.astype(f32)).astype(dtype)

        return gated_norm(y, z, scale)


# the vocabulary of ``LLMConfig.layers``: a mixer -> its module and the
# scope its call runs in (None: the module's own scopes alone)
_MIXERS = {
    "full": (Attention, "attn.full"),
    "window": (functools.partial(Attention, window=True), "attn.window"),
    "latent": (LatentAttention, "attn.latent"),
    "linear": (LinearAttention, "attn.linear"),
    "ssm": (Mamba2, "attn.ssm"),
    "sparse": (SparseAttention, "attn.sparse"),
    "moe": (MoE, None),
}
_FEED_FORWARDS = {"mlp": MLP, "moe": MoE}


def layer_stats(cfg: LLMConfig) -> dict:
    """The sums the kinds in ``cfg.plan`` sow a step: ``{prefix: sums}``,
    each sown into the collection ``<prefix>_stats``."""
    from . import moe
    kinds = {part for kind in cfg.plan for part in kind.split("+")}
    moe_sums = moe.STATS
    if cfg.n_group > 1:
        moe_sums += ("tokens_here",)
    kda_sums = ("layer_steps",)
    if cfg.kda_gate == "softplus":
        kda_sums += ("decays", "steep_decays")
    table = (("moe", "moe", moe_sums), ("linear", "kda", kda_sums),
             ("window", "attn", ("window_layer_steps",)),
             ("ssm", "ssm", ("layer_steps",)))
    return {prefix: sums for kind, prefix, sums in table if kind in kinds}


def _mix(kind, cfg, name, x, positions, attn_mask, kv_view, adapter,
         lora_scale):
    """The ``kind`` mixer of a layer, made under ``name``: ``(out,
    new_kv)``."""
    module, where = _MIXERS[kind]
    if where is None:       # the expert block reads no position
        return module(cfg, name=name)(x, adapter=adapter,
                                      lora_scale=lora_scale), None
    with scope(where):
        return module(cfg, name=name)(
            x, positions, attn_mask, kv_view=kv_view, adapter=adapter,
            lora_scale=lora_scale)


class DecoderLayer(nn.Module):
    """One pre-norm layer of the ``kind`` its ``LLMConfig.layers`` entry
    names: a mixer alone, ``x + mixer(norm(x))`` (``norm``, ``mixer``), or
    ``mixer+ff``, ``h = x + mixer(ln_attn(x))`` then ``h + ff(ln_mlp(h))``
    (``ln_attn``, ``attn``, ``ln_mlp``, ``mlp`` or ``moe``)."""

    cfg: LLMConfig
    kind: str = "full+mlp"

    @nn.compact
    def __call__(self, x, positions, attn_mask=None, kv_view=None,
                 adapter=None, lora_scale: float = 1.0):
        adapter = adapter or {}
        mixer, _, ff = self.kind.partition("+")
        norm, name = ("ln_attn", "attn") if ff else ("norm", "mixer")
        with scope("norm"):
            normed = RMSNorm(self.cfg.rms_eps, name=norm)(x)
        out, new_kv = _mix(mixer, self.cfg, name, normed, positions,
                           attn_mask, kv_view, adapter.get(name), lora_scale)
        with scope("norm"):
            h = x + out
            if not ff:
                return h, new_kv
            normed = RMSNorm(self.cfg.rms_eps, name="ln_mlp")(h)
        out = _FEED_FORWARDS[ff](self.cfg, name=ff)(
            normed, adapter=adapter.get(ff), lora_scale=lora_scale)
        with scope("norm"):
            return h + out, new_kv


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
        with scope("embed"):
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
        layer = DecoderLayer
        if cfg.remat:
            layer = nn.remat(DecoderLayer, policy=jax.checkpoint_policies
                             .save_only_these_names("selection"))
        new_kvs = []
        for i, kind in enumerate(cfg.plan):
            x, new_kv = layer(cfg, kind, name=f"layer_{i}")(
                x, positions, attn_mask,
                None if kv_view is None else kv_view[i],
                None if adapters is None else adapters.get(f"layer_{i}"),
                lora_scale)
            new_kvs.append(new_kv)
        with scope("head"):
            x = RMSNorm(cfg.rms_eps, name="ln_f")(x)
            if cfg.tie_embeddings:
                logits = emb.attend(x)
            else:
                logits = _dense(cfg, cfg.vocab_size, "lm_head")(x)
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
