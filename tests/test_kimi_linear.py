"""Kimi Linear (``model_type`` ``kimi_linear``): Kimi delta attention under
the unbounded softplus gate 3:1 with latent attention without positions,
sigmoid experts top-k by score + bias beside a shared one. The chunk step
and the kernels at log-decays far under the bounded gate's floor, the
layer's fused passes with a gate a channel, the loader on the published
keys, and a small model against the plain reference
(``benchmarks/reference/kimi_linear_ep8_l9.py``: float32, the recurrence
token by token, imports nothing of ``fedml_tpu``) at the benchmark
configuration's rehearsal widths."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.llm import linear_attention as la
from fedml_tpu.llm.federated import LLMBundle, llm_config_from_hf
from fedml_tpu.llm.lora import lora_init
from fedml_tpu.llm.model import CausalLM, LatentAttention, LLMConfig
from fedml_tpu.llm.trainer import CausalLMTrainer
from tests.test_hybrid_linear import check_the_kept_backward

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmarks", "configs", "kimi_linear_ep8_l9.json")


def _reference():
    path = os.path.join(REPO, "benchmarks", "reference",
                        "kimi_linear_ep8_l9.py")
    spec = importlib.util.spec_from_file_location("ref_kimi", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def published():
    """The configuration file with every reduced key at its published
    value: the catalog's keys of the whole model."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    return dict(cfg, **cfg["published"])


def small_cfg(**over):
    """The configuration's rehearsal widths, weights drawn wider than the
    published 0.02 so that every adapter's gradient is far from zero at
    hidden 64."""
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg = dict(cfg, **cfg["rehearsal"])
    cfg.update(initializer_range=0.2, lora_rank=4, lora_alpha=8.0,
               lora_b_std=0.05, router_bias_range=0.05,
               reference_kda_segment=8, **over)
    return cfg


def system_cfg(cfg, seq, impl="dense"):
    return llm_config_from_hf(
        dict(cfg, num_experts=cfg["published"]["num_experts"]),
        max_seq_len=seq, dtype="float32", attention_impl=impl,
        first_expert=cfg["first_expert"], experts_held=cfg["num_experts"])


def weights(cfg, seed=0):
    key = jax.random.PRNGKey(seed)
    base = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  REF.init_frozen(key, cfg))
    return base, REF.init_trainable(jax.random.fold_in(key, 7), cfg)


def tokens(cfg, rows=2, seq=32, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0,
                              cfg["vocab_size"]).astype(jnp.int32)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def held_to(a, b, others):
    """``|a - b|`` over the larger of ``|b|`` and a hundredth of the
    largest norm among ``others`` (a gradient that all but cancels)."""
    norm = lambda x: float(jnp.linalg.norm(x.astype(jnp.float32)))  # noqa
    scale = max(norm(b), 1e-2 * max(norm(x) for x in others))
    return norm(a.astype(jnp.float32) - b.astype(jnp.float32)) / scale


# ------------------------------------ the chunk step at steep decays ---

def kda_inputs(s, lo, hi, b=1, h=2, dk=128, dv=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + s), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = jax.random.uniform(ks[3], (b, s, h, dk), minval=lo, maxval=hi)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta


def _value_and_grads(fn, args):
    w = jax.random.normal(jax.random.PRNGKey(7), args[2].shape)
    return jax.jit(jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * w),
                                      argnums=(0, 1, 2, 3, 4)))(*args)


@pytest.mark.parametrize("impl", ["dense", "flash"])
@pytest.mark.parametrize("s,lo,hi", [
    (128, -30.0, 0.0),      # two chunks, decays all over (-30, 0)
    (48, -30.0, -20.0),     # a short row, every channel forgets at once
])
def test_the_exact_chunk_step_matches_the_recurrence_at_steep_decays(
        impl, s, lo, hi):
    """The unbounded form (each sub-chunk's block against itself element by
    element) against the token recurrence in both chunked forms (the
    kernels interpreted here): the output and the gradients toward q, k,
    v, the log-decay and beta, at log-decays down to -30 a step."""
    args = kda_inputs(s, lo, hi)
    want, want_g = _value_and_grads(la.kda_recurrence, args)
    got, got_g = _value_and_grads(
        lambda *a: la.kda_attention(*a, impl=impl, unbounded=True), args)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    for name, a, b in zip("q k v g beta".split(), got_g, want_g):
        assert held_to(a, b, want_g) < 5e-5, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,masked", [
    (128, False),       # two chunks
    (192, True),        # three chunks, the last third masked
    (48, False),        # a short row: one chunk of three sub-chunks
])
def test_the_exact_backward_reads_what_the_forward_kept_bit_for_bit(
        s, masked, dtype):
    """The form exact at any decay (log-decays over (-30, 0)): the backward
    pass that reads the chunks' inverse and ``P`` from the forward pass,
    where it made them again before (``_within``'s products of scaled
    copies among them), against the one that made them again."""
    check_the_kept_backward(s, -30.0, dtype, masked, unbounded=True)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_the_bounded_chunk_step_fails_at_steep_decays(impl):
    """The bounded form's factorised product (exact while ``g >= -5``) is
    far off the recurrence there: the test above tells the forms apart,
    and both agree where the bounded form is exact."""
    args = kda_inputs(128, -30.0, 0.0)
    want = la.kda_recurrence(*args)
    assert rel(la.kda_attention(*args, impl=impl), want) > 0.1
    mild = kda_inputs(64, -5.0, 0.0)
    bounded = la.kda_attention(*mild, impl=impl)
    exact = la.kda_attention(*mild, impl=impl, unbounded=True)
    assert rel(exact, bounded) < 1e-5


# ----------------------- the fused passes under the unbounded gate ---

def module_in_xla(ys, beta_logits, gate_logits, conv, a_log, dt_bias,
                  o_scale, attn_mask=None, *, heads, impl):
    """The layer between its products as plain ``jax.numpy`` around
    :func:`la.kda_attention`: the definition the fused passes are held
    to under the softplus gate and a gate a channel."""
    b, s, hd = ys["q"].shape
    d = hd // heads
    by_head = lambda a: a.reshape(b, s, heads, d)  # noqa: E731

    def conv_silu(y, w):
        yp = jnp.pad(y.astype(jnp.float32), [(0, 0), (3, 0), (0, 0)])
        return jax.nn.silu(sum(yp[:, i:i + s] * w[i] for i in range(4)))

    q, k, v = (by_head(conv_silu(ys[n], w)) for n, w in zip("qkv", conv))
    unit = lambda a: a * jax.lax.rsqrt(  # noqa: E731
        jnp.sum(a * a, -1, keepdims=True) + 1e-6)
    q, k = unit(q) * d ** -0.5, unit(k)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        by_head(ys["f"] + dt_bias))
    beta = jax.nn.sigmoid(beta_logits)
    if attn_mask is not None:
        keep = attn_mask[:, :, None]
        g, beta = g * keep[..., None], beta * keep
    out = la.kda_attention(q, k, v, g, beta, impl=impl, unbounded=True)
    out = out * jax.lax.rsqrt(jnp.mean(out * out, -1, keepdims=True)
                              + 1e-5) * o_scale
    out = out * jax.nn.sigmoid(by_head(gate_logits))
    return out.reshape(b, s, hd), g


def layer_inputs(s, masked, h=2, d=128):
    ks = jax.random.split(jax.random.PRNGKey(s), 13)
    hd = h * d
    ys = {n: jax.random.normal(ks[i], (1, s, hd)) for i, n in enumerate("qkvf")}
    beta_logits = jax.random.normal(ks[4], (1, s, h))
    gate_logits = jax.random.normal(ks[5], (1, s, hd))
    conv = [0.5 * jax.random.normal(k, (4, hd)) for k in ks[6:9]]
    # A = exp(A_log) in (1, 16): log-decays from about -30 to near 0
    frozen = (jnp.log(jax.random.uniform(ks[9], (h,), minval=1.0,
                                         maxval=16.0)),
              jax.random.uniform(ks[10], (hd,), minval=-5.0, maxval=1.0),
              1 + 0.1 * jax.random.normal(ks[11], (d,)))
    mask = jnp.ones((1, s)).at[:, 2 * s // 3:].set(0) if masked else None
    return (ys, beta_logits, gate_logits, conv, *frozen), mask, \
        jax.random.normal(ks[12], (1, s, hd))


@pytest.mark.parametrize("impl,masked", [("dense", False), ("flash", True)])
def test_the_unbounded_layer_matches_its_jax_numpy(impl, masked):
    """``kda_layer`` with ``lower=None`` and a gate a channel (both passes'
    forms; a row of 200 is padded to whole chunks, so both run the masked
    passes) against the same layer in plain ``jax.numpy``: the output, the
    gradient toward every product, both logits and every frozen parameter,
    and the counts of live and steep log-decays."""
    args, mask, weight = layer_inputs(200, masked)

    def run(fn):
        def loss(*a):
            y, extra = fn(*a, mask, heads=2, impl=impl)
            return jnp.sum(y * weight), extra

        return jax.jit(jax.value_and_grad(loss, argnums=tuple(range(7)),
                                          has_aux=True))(*args)

    (_, g), want_g = run(module_in_xla)
    (_, counts), got_g = run(lambda *a, **kw: la.kda_layer(
        *a, lower=None, eps=1e-5, **kw))
    flat = lambda t: jax.tree_util.tree_leaves(t)  # noqa: E731
    for a, b in zip(flat(got_g), flat(want_g)):
        assert held_to(a, b, flat(want_g)[:4]) < 5e-5
    live = 200 * 256 if mask is None else float(jnp.sum(mask)) * 256
    assert float(counts[0]) == live
    assert float(counts[1]) == float(jnp.sum(g < la.MIN_LOG_DECAY))
    assert 0.05 < float(counts[1]) / live < 0.95
    # the configuration that builds this layer: Kimi Linear's unbounded
    # gate, where the default (and Ling's) is the bounded one
    assert system_cfg(small_cfg(), 32).kda_gate == "softplus"
    assert LLMConfig().kda_gate == "bounded"


# --------------------------------------------------------- the loader ---

def test_the_loader_reads_the_published_keys():
    """The catalog's keys of the whole model: 1-based lists with layer 27
    among the latent ones, the KDA head size from ``linear_attn_config``
    (not the top-level ``head_dim`` 72), no rotary and no output gate in the
    latent layers, top-8 by score + bias without groups."""
    lc = llm_config_from_hf(published(), max_seq_len=64)
    assert lc.num_layers == 27
    assert [i + 1 for i, k in enumerate(lc.layers)
            if not k.startswith("linear")] == [4, 8, 12, 16, 20, 24, 27]
    assert lc.linear_head_dim == 128 and lc.head_dim == 72
    assert lc.head_size == 0 and lc.num_heads == 32
    assert lc.kda_gate == "softplus" and not lc.use_rope
    assert not lc.attn_output_gate and lc.q_lora_rank == 0
    assert (lc.kv_lora_rank, lc.qk_nope_head_dim, lc.qk_rope_head_dim,
            lc.v_head_dim) == (512, 128, 64, 128)
    assert (lc.n_routed_experts, lc.num_experts_per_tok, lc.n_shared_experts,
            lc.moe_intermediate_size) == (256, 8, 1, 1024)
    assert lc.routed_scaling_factor == 2.446 and lc.norm_topk_prob
    assert lc.router_bias and lc.n_group == 1 and lc.topk_group == 1
    assert lc.layers[:2] == ("linear+mlp", "linear+moe")
    assert lc.intermediate_size == 9216
    assert lc.rms_eps == 1e-5 and not lc.tie_embeddings
    assert lc.vocab_size == 163840
    # the cut's lists: the published layers 1-9
    cut = llm_config_from_hf(
        dict(published(), **{k: v for k, v in json.load(open(CONFIG)).items()
                             if k in ("num_hidden_layers",
                                      "linear_attn_config")}),
        max_seq_len=64)
    assert cut.layers == ("linear+mlp", "linear+moe", "linear+moe",
                          "latent+moe", "linear+moe", "linear+moe",
                          "linear+moe", "latent+moe", "linear+moe")


def _relisted(**linear):
    cfg = published()
    return dict(cfg, linear_attn_config=dict(cfg["linear_attn_config"],
                                             **linear))


@pytest.mark.parametrize("cfg,match", [
    (_relisted(full_attn_layers=[4, 8, 12, 16, 20, 24]), "each of the 27"),
    (_relisted(kda_layers=[0, 1, 2, 4, 5, 6, 8, 9, 10, 12, 13, 14, 16, 17,
                           18, 20, 21, 22, 24, 25]), "counting from 1"),
    (_relisted(full_attn_layers=[3, 4, 8, 12, 16, 20, 24, 27]),
     "each of the 27"),
    (_relisted(num_heads=16), "num_heads"),
    (_relisted(short_conv_kernel_size=2), "short_conv_kernel_size"),
    (dict(published(), moe_router_activation_func="softmax"),
     "moe_router_activation_func"),
    (dict(published(), num_nextn_predict_layers=1), "multi-token"),
])
def test_what_is_not_built_is_refused(cfg, match):
    with pytest.raises(NotImplementedError, match=match):
        llm_config_from_hf(cfg, max_seq_len=64)


# ---------------------------------------- the model against the reference ---

def test_latent_attention_without_positions_ignores_a_shift():
    """``mla_use_nope``: neither q nor the shared key turns, so positions
    shifted, or spread apart, give the same output to the bit; with rotary
    the spread ones do not (a shift alone moves no relative position)."""
    cfg = system_cfg(small_cfg(), 32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))
    mod = LatentAttention(cfg)
    params = mod.init(jax.random.PRNGKey(2), x, pos)
    at = lambda m, p: m.apply(params, x, p)[0]  # noqa: E731
    assert rel(at(mod, pos + 1000), at(mod, pos)) == 0.0
    assert rel(at(mod, 7 * pos + 3), at(mod, pos)) == 0.0
    turned = LatentAttention(dataclasses.replace(cfg, use_rope=True))
    assert rel(at(turned, 7 * pos + 3), at(turned, pos)) > 1e-3


def test_loss_and_adapter_gradients_match_the_reference():
    """Layer 0 dense with KDA, then K K M K (the first five of the cut's
    nine): the system's loss and every adapter leaf's gradient against the
    independent reference, whose KDA is the token recurrence (float32
    ``highest``)."""
    cfg = small_cfg(num_hidden_layers=5, linear_attn_config=dict(
        small_cfg()["linear_attn_config"], kda_layers=[1, 2, 3, 5],
        full_attn_layers=[4]))
    base, lora = weights(cfg)
    tok = tokens(cfg)
    x, y = tok[:, :-1], tok[:, 1:]
    lc = system_cfg(cfg, 32)
    bundle = LLMBundle(CausalLM(lc), lc, base, cfg["lora_rank"],
                       cfg["lora_alpha"])
    assert bundle.extra_metrics[-3:] == ("kda_layer_steps", "kda_decays",
                                         "kda_steep_decays")
    grad_fn = REF.make_model(cfg)
    batch = {"x": x, "y": y, "mask": jnp.ones((2,))}
    with jax.default_matmul_precision("highest"):
        want_g, want_ls, want_n = grad_fn(lora, base, batch, None)
        spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
        (_, aux), got_g = jax.jit(jax.value_and_grad(
            lambda p: spec.loss(p, batch, None), has_aux=True))(lora)
    assert abs(float(aux["loss_sum"]) - float(want_ls)) < 1e-4 * float(want_ls)
    assert float(aux["count"]) == float(want_n) == 64.0
    assert float(aux["kda_layer_steps"]) == 4.0
    assert float(aux["kda_decays"]) == 4 * 64 * 64
    assert 0 < float(aux["kda_steep_decays"]) < float(aux["kda_decays"])
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = dict(jax.tree_util.tree_leaves_with_path(got_g))
    # 4 KDA layers x 4 targets, 1 latent x 4, 1 dense and 4 shared x 3
    assert len(flat_w) == len(flat_g) == 2 * (4 * 4 + 4 + 5 * 3)
    for path, w in flat_w:
        assert float(jnp.abs(w).max()) > 0, path      # no blind leaf
        assert rel(flat_g[path], w) < 5e-4, jax.tree_util.keystr(path)
    mine = lora_init(jax.random.PRNGKey(0), base, rank=cfg["lora_rank"])
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(lora))


def test_shares_add_up_to_the_uncut_layer():
    """The expert-parallel cut's share test: the parts the 8 ranks give
    for one expert layer (4 experts each, top-4 of 32 by score + bias), the
    shared expert counted once, add up to the uncut layer."""
    experts, per_rank = 32, 4
    whole = small_cfg(num_experts=experts, first_expert=0,
                      num_hidden_layers=1, first_k_dense_replace=0,
                      linear_attn_config=dict(
                          small_cfg()["linear_attn_config"],
                          kda_layers=[1], full_attn_layers=[]))
    base, lora = weights(whole)
    x = tokens(whole)[:, :-1]

    def layer_out(cfg, b):
        mod = CausalLM(system_cfg(cfg, 32))
        _, state = mod.apply({"params": b}, x, adapters=lora,
                             lora_scale=2.0, capture_intermediates=(
                                 lambda m, _: m.name == "layer_0"),
                             mutable=["intermediates", "moe_stats",
                                      "kda_stats"])
        return state["intermediates"]["layer_0"]["__call__"][0][0]

    def held(b, lo, hi):
        b = dict(b)
        m = dict(b["layer_0"]["moe"])
        for k in ("experts_gate", "experts_up", "experts_down"):
            m[k] = m[k][lo:hi] if hi > lo else jnp.zeros_like(m[k][:1])
        b["layer_0"] = dict(b["layer_0"], moe=m)
        return b

    whole_out = layer_out(whole, base)
    shared_only = layer_out(dict(whole, num_experts=1), held(base, 0, 0))
    total = shared_only
    for r in range(experts // per_rank):
        cut = dict(whole, num_experts=per_rank, first_expert=r * per_rank)
        total = total + (layer_out(cut, held(base, r * per_rank,
                                             (r + 1) * per_rank))
                         - shared_only)
    assert rel(total, whole_out) < 1e-5
