"""Sliding-window attention with a learned sink, grouped-query heads of
unequal key and value size, partial rotary and an expert layer without a
shared expert, through the federated LoRA path, against the plain reference
(``benchmarks/reference/mimo_v2_flash_ep16_l7.py``: float32, imports nothing
of ``fedml_tpu``) at small widths that keep every ratio of the published
model: window layers between full ones, more key-value heads in a window
layer, ``d_qk != d_v``, a third of a head rotary, more experts than top-k,
fewer held than experts, one leading dense layer."""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.obs import REGISTRY
from fedml_tpu.llm.attention import (FLASH_KERNEL_NAMES, WINDOW_KERNEL_NAMES,
                                     causal_attention, dense_causal_attention,
                                     flash_block_plan, flash_causal_attention)
from fedml_tpu.llm.federated import LLMBundle, llm_config_from_hf
from fedml_tpu.llm.lora import lora_init
from fedml_tpu.llm.model import Attention, CausalLM, LLMConfig, MoE
from fedml_tpu.llm.trainer import CausalLMTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(REPO, "benchmarks", "reference",
                        "mimo_v2_flash_ep16_l7.py")
    spec = importlib.util.spec_from_file_location("ref_mimo", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def small_cfg(held=4, first=5, experts=12, **over):
    cfg = {
        "model_type": "mimo_v2_flash", "vocab_size": 96, "hidden_size": 48,
        "intermediate_size": 80, "num_hidden_layers": 4,
        "hybrid_layer_pattern": [0, 1, 1, 0], "moe_layer_freq": [0, 1, 1, 1],
        "num_attention_heads": 8, "num_key_value_heads": 2,
        "swa_num_key_value_heads": 4, "head_dim": 24, "v_head_dim": 16,
        "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
        "sliding_window": 6, "rope_theta": 5000000, "swa_rope_theta": 10000,
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False, "attention_bias": False,
        "layernorm_epsilon": 1e-5, "n_routed_experts": held,
        "published": {"n_routed_experts": experts}, "first_expert": first,
        "num_experts_per_tok": 3, "moe_intermediate_size": 24,
        "n_shared_experts": None, "routed_scaling_factor": None,
        "norm_topk_prob": True, "scoring_func": "sigmoid",
        "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
        "tie_word_embeddings": False, "initializer_range": 0.2,
        "router_bias_range": 0.3, "lora_rank": 4, "lora_alpha": 8.0,
        "lora_b_std": 0.05, "reference_heads_per_group": 4}
    cfg.update(over)
    return cfg


def system_cfg(cfg, seq, dtype="float32", impl="dense") -> LLMConfig:
    published = dict(cfg, n_routed_experts=cfg["published"]["n_routed_experts"])
    return llm_config_from_hf(
        published, max_seq_len=seq, dtype=dtype, attention_impl=impl,
        first_expert=cfg["first_expert"],
        experts_held=cfg["n_routed_experts"])


def weights(cfg, seed=0, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    base = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                  REF.init_frozen(key, cfg))
    return base, REF.init_trainable(jax.random.fold_in(key, 7), cfg)


def tokens(cfg, rows=2, seq=16, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0,
                              cfg["vocab_size"]).astype(jnp.int32)


def bundle_for(cfg, base, seq, **kw):
    lc = system_cfg(cfg, seq, **kw)
    return LLMBundle(CausalLM(lc), lc, base, cfg["lora_rank"],
                     cfg["lora_alpha"])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------- system against reference ---

def test_logits_loss_and_adapter_gradients_match_the_reference():
    """A dense full layer, two window layers with a sink and expert blocks,
    a full layer with an expert block, float32: the system's logits, loss
    and every adapter leaf's gradient against the independent reference."""
    cfg = small_cfg()
    base, lora = weights(cfg)
    tok = tokens(cfg)
    x, y = tok[:, :-1], tok[:, 1:]
    bundle = bundle_for(cfg, base, 16)
    grad_fn = REF.make_model(cfg)
    batch = {"x": x, "y": y, "mask": jnp.ones((2,))}
    with jax.default_matmul_precision("highest"):
        want_logits = grad_fn.forward(lora, base, x, None)
        want_g, want_ls, want_n = grad_fn(lora, base, batch, None)
    assert rel(bundle.apply(lora, x), want_logits) < 2e-5
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    (_, aux), got_g = jax.value_and_grad(spec.loss, has_aux=True)(
        lora, batch, None)
    assert abs(float(aux["loss_sum"]) - float(want_ls)) < 1e-4 * float(want_ls)
    assert float(aux["count"]) == float(want_n) == 32.0
    assert float(aux["attn_window_layer_steps"]) == 2.0
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = dict(jax.tree_util.tree_leaves_with_path(got_g))
    assert len(flat_w) == len(flat_g) == 2 * (4 * 4 + 3)
    for path, w in flat_w:
        assert float(jnp.abs(w).max()) > 0, path      # no blind leaf
        assert rel(flat_g[path], w) < 2e-4, jax.tree_util.keystr(path)


def test_the_window_and_the_sink_are_seen_by_the_loss():
    """What the benchmark's faults plant moves the small model too: window
    layers that attend causally, and a sink left out."""
    cfg = small_cfg()
    base, lora = weights(cfg)
    x = tokens(cfg)[:, :-1]
    sound = bundle_for(cfg, base, 16).apply(lora, x)
    no_window = bundle_for(dict(cfg, sliding_window=16), base, 16)
    assert rel(no_window.apply(lora, x), sound) > 1e-3
    without = jax.tree_util.tree_map(lambda a: a, base)
    for i in (1, 2):
        attn = dict(without[f"layer_{i}"]["attn"])
        attn.pop("sink")
        without[f"layer_{i}"] = dict(without[f"layer_{i}"], attn=attn)
    no_sink = bundle_for(dict(cfg, add_swa_attention_sink_bias=False),
                         without, 16)
    assert rel(no_sink.apply(lora, x), sound) > 1e-3


def test_adapter_tree_is_the_references_and_the_rest_stays_frozen():
    cfg = small_cfg()
    base, lora = weights(cfg)
    mine = lora_init(jax.random.PRNGKey(0), base, rank=cfg["lora_rank"])
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(lora))
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(lora)):
        assert a.shape == b.shape
    # the sink and the expert block (router, bias, experts) take no adapter
    assert set(mine["layer_1"]) == {"attn"}
    assert set(mine["layer_1"]["attn"]) == set("qkvo")
    assert set(mine["layer_0"]) == {"attn", "mlp"}
    assert base["layer_1"]["attn"]["sink"].shape == (8,)
    assert "sink" not in base["layer_0"]["attn"]
    assert base["layer_1"]["attn"]["k"]["kernel"].shape == (48, 4, 24)
    assert base["layer_0"]["attn"]["k"]["kernel"].shape == (48, 2, 24)
    assert base["layer_0"]["attn"]["v"]["kernel"].shape == (48, 2, 16)


def test_the_ranks_parts_add_up_to_the_uncut_references_layer():
    """The guide's share test under bias-corrected top-k: the 16 ranks'
    parts of one expert layer add up to the uncut layer (there is no shared
    expert, so nothing is counted once), and the uncut system is the uncut
    reference."""
    experts, per_rank, top_k = 32, 2, 8
    whole = small_cfg(held=experts, first=0, experts=experts,
                      num_hidden_layers=2, hybrid_layer_pattern=[0, 1],
                      moe_layer_freq=[0, 1], num_experts_per_tok=top_k)
    base, lora = weights(whole)
    x = tokens(whole)[:, :-1]

    def layer_out(cfg, b):
        mod = CausalLM(system_cfg(cfg, 16))
        _, state = mod.apply({"params": b}, x, adapters=lora,
                             lora_scale=2.0, capture_intermediates=(
                                 lambda m, _: m.name == "layer_1"),
                             mutable=["intermediates", "moe_stats",
                                      "attn_stats"])
        return state["intermediates"]["layer_1"]["__call__"][0][0]

    def with_experts(lo, hi, zero=False):
        b = jax.tree_util.tree_map(lambda a: a, base)
        m = dict(b["layer_1"]["moe"])
        for k in ("experts_gate", "experts_up", "experts_down"):
            m[k] = jnp.zeros_like(m[k][lo:hi]) if zero else m[k][lo:hi]
        b["layer_1"] = dict(b["layer_1"], moe=m)
        return b

    whole_out = layer_out(whole, base)
    # the layer without any expert's part: the residual and the attention
    nothing = layer_out(dict(whole, n_routed_experts=1),
                        with_experts(0, 1, zero=True))
    total = nothing
    for r in range(experts // per_rank):
        cut = dict(whole, n_routed_experts=per_rank, first_expert=r * per_rank)
        part = layer_out(cut, with_experts(r * per_rank, (r + 1) * per_rank))
        assert rel(part, nothing) > 1e-6        # every rank adds something
        total = total + (part - nothing)
    assert rel(total, whole_out) < 1e-5
    with jax.default_matmul_precision("highest"):
        want = REF.make_model(whole).forward(lora, base, x, None)
    assert rel(bundle_for(whole, base, 16).apply(lora, x), want) < 2e-5


def test_an_expert_layer_without_a_shared_expert_is_the_routed_sum():
    cfg = small_cfg()
    base, _ = weights(cfg)
    lc = system_cfg(cfg, 16)
    params = base["layer_1"]["moe"]
    assert "shared" not in params
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 48))
    got, state = MoE(lc).apply({"params": params}, x, mutable=["moe_stats"])
    flat = x.reshape(32, 48)
    gates, chosen = REF.route(flat @ params["router"]["kernel"],
                              params["router_bias"], cfg)
    want = jnp.zeros_like(flat)
    for e in range(cfg["n_routed_experts"]):
        g = jnp.sum(jnp.where(chosen == cfg["first_expert"] + e, gates, 0), -1)
        want += ((jax.nn.silu(flat @ params["experts_gate"][e])
                  * (flat @ params["experts_up"][e]))
                 @ params["experts_down"][e]) * g[:, None]
    assert rel(got.reshape(32, 48), want) < 1e-5
    assert float(state["moe_stats"]["layer_steps"]) == 1.0
    # a configuration WITH a shared expert still builds one
    shared = MoE(system_cfg(dict(cfg, n_shared_experts=1), 16)).init(
        jax.random.PRNGKey(0), x)["params"]
    assert "shared" in shared


# ------------------------------------------------- the dense (CPU) path ---

def _naive(q, k, v, window, sink, scale=None):
    """Row by row: the live keys' softmax with the sink's column beside."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    b, s, h, d = q.shape
    scale = d ** -0.5 if scale is None else scale
    out = np.zeros((b, s, h, v.shape[-1]))
    for bi in range(b):
        for a in range(h):
            for i in range(s):
                lo = 0 if window is None else max(0, i - window + 1)
                sc = k[bi, lo:i + 1, a] @ q[bi, i, a] * scale
                e = np.exp(sc - sc.max())
                extra = 0.0 if sink is None else np.exp(
                    float(sink[a]) - sc.max())
                out[bi, i, a] = (e / (e.sum() + extra)) @ v[bi, lo:i + 1, a]
    return out


@pytest.mark.parametrize("window", [None, 1, 5, 40])
@pytest.mark.parametrize("with_sink", [False, True])
def test_dense_attention_with_a_window_and_a_sink_is_the_naive_softmax(
        window, with_sink):
    key = jax.random.PRNGKey(4)
    b, s, h, d_qk, d_v = 2, 24, 3, 12, 8
    q = jax.random.normal(key, (b, s, h, d_qk))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, d_qk))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, d_v))
    sink = jnp.asarray([-1.0, 0.5, 2.0]) if with_sink else None
    got = dense_causal_attention(q, k, v, window=window, sink=sink)
    assert rel(got, _naive(q, k, v, window, sink)) < 1e-5
    if window is None and not with_sink:
        assert jnp.array_equal(got, dense_causal_attention(q, k, v))


def test_ring_attention_refuses_a_window_and_a_sink():
    from fedml_tpu.llm.attention import ring_axis
    q = jnp.zeros((1, 8, 2, 4))
    with ring_axis("sp", 1):
        for kw in ({"window": 4}, {"sink": jnp.zeros((2,))}):
            with pytest.raises(NotImplementedError, match="window"):
                causal_attention(q, q, q, impl="ring", **kw)


# ------------------------------------------------------ the block plan ---

@pytest.mark.parametrize("s,block_q,block_k", [
    (256, 128, 128), (512, 128, 256), (512, 256, 128), (512, 512, 512),
    (1024, 256, 256), (1024, 128, 512), (1024, 512, 128), (384, 128, 384)])
def test_the_window_plan_covers_the_band_and_no_more(s, block_q, block_k):
    """Every live (i, j) lies in a computed block, every compare-free block
    is live all over, no computed block lies wholly outside the band, the
    two orientations name the same blocks and ``counts()`` adds up."""
    for window in (1, 16, 127, 128, 129, 200, 513, s - 1, s, 4 * s):
        plan = flash_block_plan(s, block_q, block_k, window)
        if window >= s:
            assert plan.window is None
            assert plan == flash_block_plan(s, block_q, block_k)
            continue
        i, j = np.mgrid[:s, :s]
        live = (j <= i) & (i - j < window)
        blocks = live.reshape(plan.n_q, block_q, plan.n_k, block_k)
        some, every = blocks.any((1, 3)), blocks.all((1, 3))
        kind = np.zeros((plan.n_q, plan.n_k), int)     # 0 skipped
        for qi in range(plan.n_q):
            n_first, n_edge, n_full, n_live = plan.q_major_window(qi)
            assert 0 <= n_first <= n_edge <= n_full <= n_live <= plan.n_k
            kind[qi, n_first:n_edge] = 2                # window compare
            kind[qi, n_edge:n_full] = 1                 # no compare
            kind[qi, n_full:n_live] = 3                 # both compares
        assert np.array_equal(kind > 0, some), window
        assert every[kind == 1].all(), window
        # an edge block lies under the diagonal: the window compare is enough
        causal = (j <= i).reshape(plan.n_q, block_q, plan.n_k,
                                  block_k).all((1, 3))
        assert causal[kind == 2].all(), window
        by_k = np.zeros_like(kind)
        for kj in range(plan.n_k):
            j0, j_full, j_edge, j_last = plan.k_major_window(kj)
            assert 0 <= j0 <= j_full <= j_edge <= j_last <= plan.n_q
            by_k[j0:j_full, kj] = 3
            by_k[j_full:j_edge, kj] = 1
            by_k[j_edge:j_last, kj] = 2
        assert np.array_equal(by_k, kind), window
        # dK/dV holds band_rows() queries from band_start(j): a kv block's
        # every live q block lies inside them
        rows = plan.band_rows()
        for kj in range(plan.n_k):
            j0, _, _, j_last = plan.k_major_window(kj)
            first = plan.band_start(kj, rows)
            assert first % block_q == 0 and 0 <= first <= j0 * block_q
            assert j_last * block_q <= first + rows <= s, (window, kj)
        want = (int((kind == 1).sum()), int((kind > 1).sum()),
                int((kind == 0).sum()))
        assert plan.counts() == plan.counts(k_major=True) == want
        assert sum(want) == plan.n_q * plan.n_k


def test_the_window_plan_at_the_cells_shape_by_hand():
    # 4,096 positions, window 128: a q block sees its own block and, but for
    # the first, the one before
    assert flash_block_plan(4096, 512, 512, 128).counts() == (0, 15, 49)
    assert flash_block_plan(4096, 256, 256, 128).counts() == (0, 31, 225)
    assert flash_block_plan(4096, 128, 128, 128).counts() == (0, 63, 961)
    # a window of several blocks leaves interior blocks between its edges
    assert flash_block_plan(4096, 128, 128, 1024).counts()[0] > 0
    assert flash_block_plan(4096, 512, 512).counts() == (28, 8, 28)
    # dK/dV's band: a kv block's own q block and the next one, and nine
    # blocks of 128 for a window of 1,024
    assert flash_block_plan(4096, 128, 128, 128).band_rows() == 256
    assert flash_block_plan(4096, 256, 256, 128).band_rows() == 512
    assert flash_block_plan(4096, 128, 128, 1024).band_rows() == 1152
    assert flash_block_plan(4096, 128, 128, 128).band_start(31, 256) == 3840
    assert flash_block_plan(4096, 128, 128, 128).band_start(7, 256) == 896


# ------------------------------------------------------ the flash kernels ---

def _qkv(b, s, h, d_qk=24, d_v=16, seed=0):
    key = jax.random.PRNGKey(seed)
    return (jax.random.normal(key, (b, s, h, d_qk)),
            jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, d_qk)),
            jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, d_v)),
            jax.random.normal(jax.random.fold_in(key, 3), (b, s, h, d_v)))


def _grouped_qkv(b, s, heads, kv_heads, seed=0):
    """q and the cotangent at ``heads``, k and v at ``kv_heads``."""
    q, k, v, c = _qkv(b, s, heads, seed=seed)
    return q, k[:, :, :kv_heads], v[:, :, :kv_heads], c


def _repeated(fn, heads):
    """``fn`` on k and v repeated to ``heads`` (the dense path's operands:
    its gradients flow back to the key-value heads through the repeat)."""
    def call(q, k, v, *args, **kw):
        k, v = (jnp.repeat(a, heads // a.shape[2], axis=2) for a in (k, v))
        return fn(q, k, v, *args, **kw)
    return call


@pytest.mark.pallas
@pytest.mark.parametrize("with_sink", [False, True])
@pytest.mark.parametrize("s,window,block_q,block_k,group", [
    *(pytest.param(*case, 1, id="-".join(map(str, case))) for case in (
        (256, 16, 128, 128), (256, 128, 128, 128), (256, 200, 128, 128),
        (512, 16, 256, 128), (512, 128, 128, 256), (512, 200, 256, 256),
        (1024, 16, 512, 512), (1024, 128, 256, 256), (1024, 200, 128, 512))),
    pytest.param(256, 16, 128, 128, 2, id="256-16-128-128-group2"),
    pytest.param(512, 300, 128, 128, 2, id="512-300-128-128-group2"),
    pytest.param(512, 40, 128, 128, 8, id="512-40-128-128-group8"),
    pytest.param(768, 300, 128, 128, 8, id="768-300-128-128-group8")])
def test_window_kernels_match_dense(s, window, block_q, block_k, group,
                                    with_sink):
    """Interpreted: forward, the three gradients and the sink's against the
    dense path, for windows narrower than, equal to and wider than a block,
    blocks the window does and does not cross; 2 query heads on 2, 1 or 2
    on 4 query heads and 1 on 8 key-value heads (``group``: query heads a
    key-value head), the dense path on keys and values repeated, their
    gradients at the key-value heads."""
    kv_heads = 2 if group < 8 else 1
    q, k, v, c = _grouped_qkv(1, s, kv_heads * group, kv_heads)
    sink = (jnp.linspace(0.7, 3.0, kv_heads * group) if with_sink
            else None)

    def loss(fn):
        return lambda q, k, v, sink: jnp.sum(fn(q, k, v, sink) * c)

    flash = lambda q, k, v, sink: flash_causal_attention(  # noqa: E731
        q, k, v, block_q=block_q, block_k=block_k, window=window, sink=sink)
    dense = _repeated(lambda q, k, v, sink: dense_causal_attention(
        q, k, v, window=window, sink=sink), kv_heads * group)
    assert rel(flash(q, k, v, sink), dense(q, k, v, sink)) < 1e-5
    args = (0, 1, 2, 3) if with_sink else (0, 1, 2)
    got = jax.grad(loss(flash), argnums=args)(q, k, v, sink)
    want = jax.grad(loss(dense), argnums=args)(q, k, v, sink)
    for g, w in zip(got, want):
        assert g.shape == w.shape and rel(g, w) < 1e-5


def _key_mask_and_padding_case(heads, kv_heads):
    q, k, v, c = _grouped_qkv(2, 300, heads, kv_heads)
    mask = (jax.random.uniform(jax.random.PRNGKey(9), (2, 300)) > 0.2
            ).astype(jnp.float32).at[:, 0].set(1.0)
    sink = jnp.linspace(0.2, 1.5, heads)
    f = lambda fn: lambda q, k, v, sink: jnp.sum(fn(  # noqa: E731
        q, k, v, attn_mask=mask, window=40, sink=sink) * c)
    got = jax.grad(f(flash_causal_attention), argnums=(0, 1, 2, 3))(
        q, k, v, sink)
    want = jax.grad(f(_repeated(dense_causal_attention, heads)),
                    argnums=(0, 1, 2, 3))(q, k, v, sink)
    for g, w in zip(got, want):
        assert g.shape == w.shape and rel(g, w) < 1e-5


@pytest.mark.pallas
def test_window_kernels_with_a_key_mask_and_padding_match_dense():
    """A length off the 128 grid and a key mask: every block then makes
    both compares and gates on them."""
    _key_mask_and_padding_case(2, 2)


@pytest.mark.pallas
@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 1)])
def test_grouped_window_kernels_with_a_key_mask_and_padding_match_dense(
        heads, kv_heads):
    """The same with a key-value head's group of query heads in a grid
    step: the key mask's row is the batch row's, whatever the head."""
    _key_mask_and_padding_case(heads, kv_heads)


@pytest.mark.pallas
@pytest.mark.parametrize("group", [2, 8])
def test_a_group_is_its_query_heads_on_repeated_keys(group):
    """Keys and values at ``h / group`` heads give, bit for bit, the output,
    dQ and the sink's gradient that the same call on them repeated to ``h``
    heads gives (one query head a grid step, the kernels' form before the
    group), and dK / dV that are that call's gradients summed over the
    group: a query head's arithmetic is its own, whatever its step holds."""
    q, k, v, c = _grouped_qkv(1, 512, 2 * group, 2)
    sink = jnp.linspace(-0.5, 2.5, 2 * group)
    run = lambda fn: jax.value_and_grad(  # noqa: E731
        lambda q, k, v, sink: jnp.sum(fn(
            q, k, v, block_q=128, block_k=128, window=100, sink=sink) * c),
        argnums=(0, 1, 2, 3))(q, k, v, sink)
    (lg, (dq_g, dk_g, dv_g, ds_g)) = run(flash_causal_attention)
    (lr, (dq_r, dk_r, dv_r, ds_r)) = run(_repeated(flash_causal_attention,
                                                   2 * group))
    out = lambda q, k, v: flash_causal_attention(  # noqa: E731
        q, k, v, block_q=128, block_k=128, window=100, sink=sink)
    assert jnp.array_equal(out(q, k, v), _repeated(out, 2 * group)(q, k, v))
    for a, b in ((lg, lr), (dq_g, dq_r), (ds_g, ds_r)):
        assert jnp.array_equal(a, b)
    for a, b in ((dk_g, dk_r), (dv_g, dv_r)):
        assert a.shape == (1, 512, 2, a.shape[-1]) and rel(a, b) < 1e-6


@pytest.mark.pallas
def test_a_window_over_every_key_is_the_causal_kernels_bit_for_bit():
    q, k, v, c = _qkv(1, 256, 2)
    f = lambda **kw: jax.value_and_grad(  # noqa: E731
        lambda q, k, v: jnp.sum(flash_causal_attention(
            q, k, v, block_q=128, block_k=128, **kw) * c),
        argnums=(0, 1, 2))(q, k, v)
    plain = f()
    for kw in ({"window": 256}, {"window": 100000}):
        for a, b in zip(jax.tree_util.tree_leaves(f(**kw)),
                        jax.tree_util.tree_leaves(plain)):
            assert jnp.array_equal(a, b), kw
    text = lambda **kw: str(jax.make_jaxpr(jax.grad(  # noqa: E731
        lambda q, k, v: jnp.sum(flash_causal_attention(q, k, v, **kw)),
        argnums=(0, 1, 2)))(q, k, v))
    assert text(window=256) == text()
    assert all(n in text() for n in FLASH_KERNEL_NAMES)
    assert not any(n in text() for n in WINDOW_KERNEL_NAMES)
    assert all(n in text(window=64) for n in WINDOW_KERNEL_NAMES)


@pytest.mark.pallas
@pytest.mark.parametrize("window", [None, 40])
def test_a_sink_at_minus_infinity_is_no_sink(window):
    q, k, v, c = _qkv(1, 256, 2)
    run = lambda sink: jax.value_and_grad(  # noqa: E731
        lambda q, k, v: jnp.sum(flash_causal_attention(
            q, k, v, block_q=128, block_k=128, window=window, sink=sink) * c),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(run(jnp.full((2,), -jnp.inf))),
                    jax.tree_util.tree_leaves(run(None))):
        assert jnp.array_equal(a, b)


def test_a_window_call_sets_its_gauges_and_a_plain_call_does_not():
    REGISTRY.reset()
    q, k, v, _ = _qkv(1, 512, 2)
    jax.eval_shape(lambda q, k, v: flash_causal_attention(q, k, v), q, k, v)
    assert REGISTRY.gauge("fed_flash_window").value() is None
    assert REGISTRY.gauge("fed_flash_window_heads_per_step").value() is None
    assert REGISTRY.gauge("fed_flash_interior_block_share").value() is not None
    jax.eval_shape(lambda q, k, v: flash_causal_attention(
        q, k, v, block_q=128, block_k=128, window=16,
        sink=jnp.zeros((2,))), q, k, v)
    assert REGISTRY.gauge("fed_flash_window").value() == 16.0
    assert REGISTRY.gauge("fed_flash_sink").value() == 1.0
    # a q block's own kv block and, but for the first, the one before: 7 of
    # the causal plan's 10 blocks
    assert abs(REGISTRY.gauge("fed_flash_window_block_share").value()
               - 0.7) < 1e-12
    # a grid step takes a key-value head and its group: one query head here,
    # the cell's 8 where 64 query heads read 8 key-value heads
    assert REGISTRY.gauge("fed_flash_window_heads_per_step").value() == 1.0
    q64 = jax.ShapeDtypeStruct((1, 512, 64, 24), jnp.float32)
    k8 = jax.ShapeDtypeStruct((1, 512, 8, 24), jnp.float32)
    jax.eval_shape(lambda q, k: flash_causal_attention(
        q, k, k, window=128, sink=jnp.zeros((64,))), q64, k8)
    assert REGISTRY.gauge("fed_flash_window_heads_per_step").value() == 8.0
    REGISTRY.reset()


# ------------------------------------- grouped heads, sizes, partial rotary ---

def _turn(x, positions, theta):
    """Half-split rotary over ALL of x's last axis, written out pair by
    pair."""
    x = np.asarray(x, np.float64)
    half = x.shape[-1] // 2
    out = x.copy()
    for i in range(half):
        ang = positions * theta ** (-i / half)
        cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
        a, b = x[:, :, :, i], x[:, :, :, i + half]
        out[:, :, :, i] = a * cos[None] - b * sin[None]
        out[:, :, :, i + half] = b * cos[None] + a * sin[None]
    return out


@pytest.mark.parametrize("kv_heads", [2, 1])
@pytest.mark.parametrize("window_layer", [False, True])
def test_grouped_heads_of_unequal_sizes_with_part_of_a_head_rotary(
        kv_heads, window_layer):
    """8 query heads on 2 or 1 key-value heads, keys of 24 and values of 16,
    the first 8 dims rotary and the last 16 untouched, values scaled: the
    module against the equations written out head by head."""
    lc = LLMConfig(hidden_size=32, num_heads=8, num_kv_heads=4, head_size=24,
                   v_head_dim=16, rotary_dim=8, attn_value_scale=0.707,
                   layers=("full+mlp", "window+mlp"), sliding_window=5,
                   window_kv_heads=kv_heads, window_rope_theta=100.0,
                   rope_theta=5e6, window_sink=True)
    if not window_layer:
        lc.num_kv_heads = kv_heads
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 32))
    pos = jnp.broadcast_to(jnp.arange(12), (2, 12))
    mod = Attention(lc, window=window_layer)
    params = mod.init(jax.random.PRNGKey(1), x, pos)["params"]
    assert params["k"]["kernel"].shape == (32, kv_heads, 24)
    assert params["v"]["kernel"].shape == (32, kv_heads, 16)
    assert params["o"]["kernel"].shape == (8 * 16, 32)
    assert ("sink" in params) == window_layer
    if window_layer:
        params = dict(params, sink=jnp.linspace(-1.0, 2.0, 8))
    got, _ = mod.apply({"params": params}, x, pos)

    xs = np.asarray(x, np.float64)
    proj = lambda n: np.einsum(  # noqa: E731
        "bsh,hnd->bsnd", xs, np.asarray(params[n]["kernel"], np.float64))
    q, k, v = proj("q"), proj("k"), proj("v") * 0.707
    theta = 100.0 if window_layer else 5e6
    p = np.arange(12, dtype=np.float64)
    q = np.concatenate([_turn(q[..., :8], p, theta), q[..., 8:]], -1)
    k = np.concatenate([_turn(k[..., :8], p, theta), k[..., 8:]], -1)
    rep = 8 // kv_heads
    k, v = (np.stack([a[:, :, h // rep] for h in range(8)], 2)
            for a in (k, v))
    out = _naive(q, k, v, 5 if window_layer else None,
                 params.get("sink"))
    want = out.reshape(2, 12, 8 * 16) @ np.asarray(params["o"]["kernel"],
                                                   np.float64)
    assert rel(got, want) < 1e-5


@pytest.mark.pallas
def test_the_small_stack_through_the_flash_kernels_matches_dense():
    cfg = small_cfg(sliding_window=40)
    base, lora = weights(cfg)
    x = tokens(cfg, seq=256)[:, :-1]
    dense = bundle_for(cfg, base, 256).apply(lora, x)
    flash = bundle_for(cfg, base, 256, impl="flash").apply(lora, x)
    assert rel(flash, dense) < 2e-5


# ------------------------------------------------- loader, refusals, cache ---

def test_the_loader_reads_the_published_key_names():
    lc = system_cfg(small_cfg(), 16)
    assert lc.head_dim == lc.head_size == 24 and lc.v_head_dim == 16
    assert lc.rotary_dim == 8 and lc.attn_value_scale == 0.707
    assert lc.layers == ("full+mlp", "window+moe", "window+moe", "full+moe")
    assert lc.sliding_window == 6 and lc.window_kv_heads == 4
    assert lc.kv_heads == 2 and lc.window_rope_theta == 1e4
    assert lc.rope_theta == 5e6 and lc.rms_eps == 1e-5
    assert lc.window_sink and not lc.full_sink
    assert lc.n_shared_experts == 0
    assert lc.routed_scaling_factor == 1.0 and lc.router_bias
    assert lc.n_group == 1 and lc.n_routed_experts == 12 and lc.held == 4
    # the published file's own numbers
    import json
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "mimo_v2_flash_ep16_l7.json")) as f:
        full = json.load(f)
    big = llm_config_from_hf(
        dict(full, n_routed_experts=full["published"]["n_routed_experts"]),
        max_seq_len=4096, first_expert=full["first_expert"],
        experts_held=full["n_routed_experts"])
    assert (big.head_dim, big.v_head_dim, big.rotary_dim) == (192, 128, 64)
    assert (big.num_heads, big.kv_heads, big.window_kv_heads) == (64, 4, 8)
    assert big.layers == ("full+mlp",) + ("window+moe",) * 5 + ("full+moe",)
    assert big.sliding_window == 128
    assert (big.n_routed_experts, big.held, big.first_expert) == (256, 16, 80)
    # a head size equal to the quotient is no stated head size, and a
    # window key without a pattern stays unread, as it always was
    plain = llm_config_from_hf(
        {"vocab_size": 64, "hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 2, "num_attention_heads": 4, "head_dim": 16,
         "sliding_window": 4096}, max_seq_len=32)
    assert plain.head_size == 0 and plain.sliding_window == 0
    assert plain.layers == ("full+mlp",) * 2 and plain.param_count() > 0


@pytest.mark.parametrize("over,match", [
    ({"hybrid_layer_pattern": [0, 1, 1]}, "hybrid_layer_pattern"),
    ({"partial_rotary_factor": 0.3}, "rotary"),
    ({"attention_bias": True}, "attention_bias"),
    ({"moe_layer_freq": [0, 1, 0, 1]}, "moe_layer_freq"),
    ({"moe_layer_freq": [0, 1, 1]}, "moe_layer_freq"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
])
def test_the_loader_refuses_what_stays_unbuilt(over, match):
    with pytest.raises(NotImplementedError, match=match):
        system_cfg(small_cfg(**over), 16)


def test_a_window_layer_has_no_cache_path_and_says_so():
    cfg = small_cfg()
    base, _ = weights(cfg)
    lc = system_cfg(cfg, 16)
    x = tokens(cfg)[:, :-1]
    view = [(jnp.zeros((2, 16, 2, 24)), jnp.zeros((2, 16, 2, 16)))] * 4
    with pytest.raises(NotImplementedError, match="window"):
        CausalLM(lc).apply({"params": base}, x, kv_view=view,
                           positions=jnp.broadcast_to(jnp.arange(16), (2, 16)))
    with pytest.raises(NotImplementedError, match="benchmarks/flops"):
        lc.param_count()
    # partial rotary and the value scale alone keep the cache path
    plain = LLMConfig(rotary_dim=8, attn_value_scale=0.5)
    xs = jnp.zeros((1, 4, 128))
    pos = jnp.arange(4)[None]
    mod = Attention(plain)
    params = mod.init(jax.random.PRNGKey(0), xs, pos)["params"]
    view = (jnp.zeros((1, 8, 4, 32)), jnp.zeros((1, 8, 4, 32)))
    out, new_kv = mod.apply({"params": params}, xs, pos, kv_view=view)
    assert out.shape == (1, 4, 128) and new_kv[0].shape == (1, 4, 4, 32)


def test_the_window_counter_reaches_the_registry_from_the_round_program():
    """A federated LoRA round of the small model through ``TPUSimulator``:
    ``fed_attn_window_layer_steps_total`` counts the round program's own
    passes through window layers; a model without them names no such
    metric."""
    import fedml_tpu
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.types import ClientData, TrainHyper
    from fedml_tpu.data.containers import FederatedDataset
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    cfg = small_cfg()
    base, lora = weights(cfg, dtype=jnp.bfloat16)
    args = fedml_tpu.init(Arguments(
        backend="tpu", precision="bfloat16", client_num_in_total=2,
        client_num_per_round=2, batch_size=1, epochs=1, learning_rate=0.05,
        client_optimizer="sgd", federated_optimizer="FedAvg",
        comm_round=100, frequency_of_the_test=0, random_seed=3,
        dataset="llm", model="causal_lm", llm_max_seq_len=16,
        lora_rank=cfg["lora_rank"], lora_alpha=cfg["lora_alpha"]))
    tok = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (2, 2, 1, 17),
                                        0, cfg["vocab_size"]), np.int32)
    train = ClientData(x=jnp.asarray(tok[..., :-1]),
                       y=jnp.asarray(tok[..., 1:]),
                       mask=jnp.ones((2, 2, 1), jnp.float32),
                       num_samples=jnp.asarray([2.0, 2.0]))
    fed = FederatedDataset(
        train=train, test={"x": train.x[0, :1], "y": train.y[0, :1],
                           "mask": train.mask[0, :1]},
        num_classes=cfg["vocab_size"], input_shape=(16,), num_clients=2,
        client_num_samples=np.asarray([2, 2]), task="llm",
        provenance="synthetic")
    bundle = bundle_for(cfg, base, 16, dtype="bfloat16")
    assert "attn_window_layer_steps" in bundle.extra_metrics
    assert "moe_tokens_here" not in bundle.extra_metrics
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    sim = TPUSimulator(args, fed, bundle, create_optimizer(args, spec), spec)
    hyper = TrainHyper(learning_rate=jnp.float32(0.05), epochs=1)
    before = REGISTRY.counter("fed_attn_window_layer_steps_total").value()
    m0 = sim.run_round(0, hyper)
    # 2 window layers x (2 silos x 2 steps)
    assert float(m0["attn_window_layer_steps"]) == 8.0
    assert float(m0["moe_layer_steps"]) == 12.0
    float(m0["loss_sum"])
    sim.flush_program_counters()
    assert REGISTRY.counter("fed_attn_window_layer_steps_total").value() \
        == before + 8.0
    # a model without window layers names no such metric
    plain = LLMConfig()
    assert LLMBundle(CausalLM(plain), plain, None, 0, 1.0).extra_metrics == ()
