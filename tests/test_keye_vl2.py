"""Keye-VL-2.0's language model (``model_type`` ``KeyeVL2``): grouped-query
attention over the keys a lightning indexer selects, softmax-routed experts
in every layer. The exact selection and its tie rule, the sparse attention
kernels (interpreted here) against the dense path, the softmax router
beside the sigmoid one the other expert models keep bit for bit, the
loader on the published keys, and a small model against the plain
reference (``benchmarks/reference/keye_vl2_ep8_l12.py``: float32, imports
nothing of ``fedml_tpu``) at the benchmark configuration's rehearsal
widths."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.obs import REGISTRY
from fedml_tpu.llm import moe
from fedml_tpu.llm import sparse_attention as sa
from fedml_tpu.llm.federated import LLMBundle, llm_config_from_hf
from fedml_tpu.llm.lora import lora_init
from fedml_tpu.llm.model import CausalLM, SparseAttention
from fedml_tpu.llm.trainer import CausalLMTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "benchmarks", "configs", "keye_vl2_ep8_l12.json")


def _reference():
    path = os.path.join(REPO, "benchmarks", "reference",
                        "keye_vl2_ep8_l12.py")
    spec = importlib.util.spec_from_file_location("ref_keye", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _file():
    with open(CONFIG) as f:
        return json.load(f)


def published():
    """The configuration file with every reduced key at its published
    value: the catalog's keys of the whole model."""
    cfg = _file()
    return dict(cfg, **cfg["published"])


def small_cfg(**over):
    """The configuration's rehearsal widths, weights drawn wider than the
    published 0.02 so that every adapter's gradient is far from zero at
    hidden 64."""
    cfg = _file()
    cfg = dict(cfg, **cfg["rehearsal"])
    cfg.update(initializer_range=0.2, lora_rank=4, lora_alpha=8.0,
               lora_b_std=0.05, **over)
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


def tokens(cfg, rows=2, seq=64, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0,
                              cfg["vocab_size"]).astype(jnp.int32)


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ------------------------------------------------------- the selection ---

def exact_top_k(qi, ki, w, topk):
    """bool [b, s, s] in float64 numpy: the scores, then each row's
    ``min(topk, t + 1)`` largest causal ones, the earlier position first
    among equal scores (a stable sort on the negated score)."""
    qi, ki, w = (np.asarray(a, np.float64) for a in (qi, ki, w))
    dots = np.maximum(np.einsum("bthd,bud->bthu", qi, ki), 0.0)
    scores = np.einsum("bthu,bth->btu", dots, w)
    b, s, _ = scores.shape
    keep = np.zeros((b, s, s), bool)
    for i in range(b):
        for t in range(s):
            order = np.argsort(-scores[i, t, :t + 1], kind="stable")
            keep[i, t, order[:min(topk, t + 1)]] = True
    return keep


def index_inputs(s, heads=4, dim=64, b=1, seed=0, distinct_keys=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    qi = jax.random.normal(ks[0], (b, s, heads, dim))
    ki = jax.random.normal(ks[1], (b, s, dim))
    if distinct_keys:       # few distinct keys: every row is full of ties
        ki = ki[:, jnp.arange(s) % distinct_keys]
    w = jax.random.normal(ks[2], (b, s, heads))
    return qi, ki, w


@pytest.mark.pallas
@pytest.mark.parametrize("impl", ["dense", "flash"])
@pytest.mark.parametrize("s,topk", [
    (384, 100),     # rows 0-99 keep every causal key, the rest 100
    (200, 150),     # a row padded to 256, the first block keeps all
])
def test_the_selection_is_the_exact_top_k(impl, s, topk):
    qi, ki, w = index_inputs(s)
    got = sa.unpack(sa.select_keys(qi, ki, w, topk, impl), s)[:, :s]
    want = exact_top_k(qi, ki, w, topk)
    assert np.array_equal(np.asarray(got), want)
    counts = np.asarray(got).sum(-1)[0]
    assert list(counts) == [min(topk, t + 1) for t in range(s)]


@pytest.mark.pallas
@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_ties_at_the_boundary_go_to_the_earlier_key(impl):
    """Five distinct index keys over 256 positions: every row's threshold
    falls inside a run of equal scores, and the row keeps the earliest of
    them; a row of all-zero weights (every score 0, some of them -0) keeps
    its first ``topk`` keys."""
    s, topk = 256, 40
    qi, ki, w = index_inputs(s, distinct_keys=5, seed=1)
    w = w.at[0, 200].set(0.0).at[0, 201].set(-0.0)
    got = np.asarray(sa.unpack(sa.select_keys(qi, ki, w, topk, impl), s))
    want = exact_top_k(qi, ki, w, topk)
    assert np.array_equal(got[:, :s], want)
    assert got[0, 200, :topk].all() and not got[0, 200, topk:].any()
    assert got[0, 201, :topk].all() and not got[0, 201, topk:].any()
    # ties were cut inside a run: a row kept some keys of one score, not all
    scores = np.asarray(sa.index_scores(qi, ki, w))[0]
    cut = 0
    for t in range(topk, s):
        kept = scores[t, :t + 1][got[0, t, :t + 1]]
        left = scores[t, :t + 1][~got[0, t, :t + 1]]
        cut += bool(np.isin(kept.min(), left))
    assert cut > 100


def test_the_mask_layout_packs_and_unpacks():
    keep = jax.random.bernoulli(jax.random.PRNGKey(0), 0.3, (2, 5, 4500))
    words = sa.pack(keep)
    assert words.shape == (2, 5, 256) and words.dtype == jnp.int32
    assert np.array_equal(np.asarray(sa.unpack(words, 4500)),
                          np.asarray(keep))
    # bit j of word c * 128 + l is key c * 4096 + j * 128 + l
    one = sa.pack(jnp.zeros((1, 1, 4500), bool).at[0, 0, 4096 + 3 * 128 + 5]
                  .set(True))
    assert int(one[0, 0, 128 + 5]) == 1 << 3


# ------------------------------------------------- the attention kernels ---

@pytest.mark.pallas
@pytest.mark.parametrize("s,h,h_kv,topk", [
    (384, 4, 2, 100),
    (200, 8, 1, 64),        # padded to 256, one key-value head for eight
])
def test_sparse_kernels_match_dense(s, h, h_kv, topk):
    """Forward and the gradients toward q, k and v of the kernels against
    the dense path, on a selection the indexer made."""
    qi, ki, w = index_inputs(s, seed=s)
    words = sa.select_keys(qi, ki, w, topk, "dense")
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    q = jax.random.normal(ks[0], (1, s, h, 128))
    k = jax.random.normal(ks[1], (1, s, h_kv, 128))
    v = jax.random.normal(ks[2], (1, s, h_kv, 128))
    ct = jax.random.normal(ks[3], (1, s, h, 128))

    def run(impl):
        return jax.value_and_grad(lambda q, k, v: jnp.sum(
            sa.sparse_attend(q, k, v, words, topk, impl) * ct),
            (0, 1, 2))(q, k, v)

    (want, want_g), (got, got_g) = run("dense"), run("flash")
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    for a, b in zip(got_g, want_g):
        assert rel(a, b) < 1e-5


@pytest.mark.pallas
def test_a_flash_call_sets_the_gauge_and_a_dense_one_does_not():
    REGISTRY.reset()
    qi, ki, w = index_inputs(256)
    words = sa.select_keys(qi, ki, w, 64, "dense")
    q = jnp.ones((1, 256, 2, 128))
    gauge = REGISTRY.gauge("fed_sparse_computed_over_selected")
    sa.sparse_attend(q, q, q, words, 64, "dense")
    assert not gauge.value()
    sa.sparse_attend(q, q, q, words, 64, "flash")
    # 3 of 4 blocks of 128 x 128 over 64 * 65 / 2 + 192 * 64 kept scores
    assert gauge.value() == pytest.approx(3 * 128 * 128 / (2080 + 12288))
    REGISTRY.reset()


def test_the_ratio_at_the_cells_shape_by_hand():
    """16,384 positions, 2,048 keys a query: 129 x 128 / 2 blocks of 128 x
    128 a head against 1,920.06 kept keys a query on average."""
    got = sa.computed_over_selected(16384, 2048)
    selected = 2048 * 2049 // 2 + (16384 - 2048) * 2048
    assert got == 128 * 129 // 2 * 128 * 128 / selected
    assert 4.2 < got < 4.4


# ----------------------------------------------------------- the router ---

def _parent_route(scores_in, top_k, scaling, norm_topk=True, bias=None,
                  n_group=0, topk_group=0):
    """``moe.route`` as the commit before softmax scoring had it."""
    s = jax.nn.sigmoid(scores_in.astype(jnp.float32))
    if bias is None and n_group <= 1:
        vals, idx = jax.lax.top_k(s, top_k)
    else:
        choice = s if bias is None else s + bias.astype(jnp.float32)
        if n_group > 1:
            t, e = choice.shape
            groups = choice.reshape(t, n_group, e // n_group)
            group_score = jnp.sum(jax.lax.top_k(groups, 2)[0], -1)
            _, kept = jax.lax.top_k(group_score, topk_group)
            stays = jnp.any(kept[:, :, None] == jnp.arange(n_group), 1)
            choice = jnp.where(stays[:, :, None], groups,
                               -jnp.inf).reshape(t, e)
        _, idx = jax.lax.top_k(choice, top_k)
        vals = jnp.take_along_axis(s, idx, -1)
    if norm_topk:
        vals = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20)
    return vals * scaling, idx.astype(jnp.int32)


@pytest.mark.parametrize("config", [
    "axk1_ep16_l5", "ling3flash_ep8_l7", "mimo_v2_flash_ep16_l7",
    "nemotron3_super_ep8_l11", "kimi_linear_ep8_l9"])
def test_the_sigmoid_router_is_the_parents_bit_for_bit(config):
    """The five expert configurations' routing (their published router
    width, top-k, scaling, group limit and bias) through ``moe.route`` as
    the model calls it now and as the parent commit had it."""
    with open(os.path.join(REPO, "benchmarks", "configs",
                           config + ".json")) as f:
        cfg = json.load(f)
    held = "num_experts" if "num_experts" in cfg else "n_routed_experts"
    lc = llm_config_from_hf(dict(cfg, **{held: cfg["published"][held]}),
                            max_seq_len=64)
    assert lc.router_scoring == "sigmoid"
    e = lc.n_routed_experts
    logits = jax.random.normal(jax.random.PRNGKey(e), (96, e)) * 2.0
    bias = (jax.random.uniform(jax.random.PRNGKey(1), (e,)) * 0.01
            if lc.router_bias else None)
    args = (logits, lc.num_experts_per_tok, lc.routed_scaling_factor,
            lc.norm_topk_prob, bias, lc.n_group, lc.topk_group)
    got = jax.jit(lambda *a: moe.route(*a, lc.router_scoring),
                  static_argnums=(1, 2, 3, 5, 6))(*args)
    want = jax.jit(_parent_route, static_argnums=(1, 2, 3, 5, 6))(*args)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_the_softmax_router_is_a_softmax_then_top_k():
    logits = jax.random.normal(jax.random.PRNGKey(2), (64, 128)) * 3.0
    gates, idx = moe.route(logits, 8, 1.0, True, scoring="softmax")
    p = np.asarray(jax.nn.softmax(logits.astype(jnp.float64)
                                  if jax.config.jax_enable_x64 else logits,
                                  -1), np.float64)
    want_idx = np.argsort(-p, -1, kind="stable")[:, :8]
    assert np.array_equal(np.asarray(idx), want_idx)
    top = np.take_along_axis(p, want_idx, -1)
    np.testing.assert_allclose(np.asarray(gates),
                               top / top.sum(-1, keepdims=True), rtol=1e-5)
    with pytest.raises(ValueError, match="sigmoid or softmax"):
        moe.route(logits, 8, 1.0, scoring="relu")


# ----------------------------------------------------------- the loader ---

def test_the_loader_reads_the_published_keys():
    lc = llm_config_from_hf(published(), max_seq_len=64)
    assert lc.layers == ("sparse+moe",) * 48
    assert (lc.hidden_size, lc.num_heads, lc.kv_heads, lc.head_dim) == (
        2048, 32, 4, 128)
    assert lc.head_size == 128 and lc.rotary_dim == 0
    assert lc.rope_scaling is None and lc.rope_theta == 1e7
    assert lc.qk_norm and lc.router_scoring == "softmax"
    assert (lc.index_heads, lc.index_head_dim, lc.index_topk) == (16, 64,
                                                                  2048)
    assert (lc.n_routed_experts, lc.num_experts_per_tok,
            lc.moe_intermediate_size, lc.n_shared_experts) == (128, 8, 768, 0)
    assert lc.norm_topk_prob and lc.routed_scaling_factor == 1.0
    assert not lc.router_bias and lc.n_group == 0
    assert lc.vocab_size == 151936 and not lc.tie_embeddings
    assert lc.rms_eps == 1e-6
    cut = llm_config_from_hf(_file(), max_seq_len=64)
    assert cut.layers == ("sparse+moe",) * 12
    # ``gradient_checkpointing``, a training key the file sets, and only it
    assert cut.remat
    assert not llm_config_from_hf(
        {k: v for k, v in _file().items() if k != "gradient_checkpointing"},
        max_seq_len=64).remat


def _with(**over):
    return dict(published(), **over)


@pytest.mark.parametrize("cfg,match", [
    (_with(sa_config=dict(published()["sa_config"], indexer_num_kv_heads=2)),
     "indexer_num_kv_heads"),
    (_with(decoder_sparse_step=2), "decoder_sparse_step"),
    (_with(mlp_only_layers=[0]), "mlp_only_layers"),
    (_with(use_sliding_window=True), "use_sliding_window"),
    (_with(rope_scaling={"mrope_section": [16, 24, 16],
                         "rope_type": "default"}), "mrope_section"),
    (_with(attention_bias=True), "attention_bias"),
])
def test_what_is_not_built_is_refused(cfg, match):
    with pytest.raises(NotImplementedError, match=match):
        llm_config_from_hf(cfg, max_seq_len=64)


def test_sparse_attention_has_no_cache_path_and_says_so():
    lc = system_cfg(small_cfg(), 16)
    x = jnp.ones((1, 16, lc.hidden_size))
    pos = jnp.broadcast_to(jnp.arange(16), (1, 16))
    mod = SparseAttention(lc)
    params = mod.init(jax.random.PRNGKey(0), x, pos)
    kv = jnp.zeros((1, 16, lc.kv_heads, lc.head_dim))
    with pytest.raises(NotImplementedError, match="no cache path"):
        mod.apply(params, x, pos, kv_view=(kv, kv))


# --------------------------------------- the model against the reference ---

def _step(cfg, lc, base, batch):
    bundle = LLMBundle(CausalLM(lc), lc, base, cfg["lora_rank"],
                       cfg["lora_alpha"])
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    return jax.jit(jax.value_and_grad(
        lambda p: spec.loss(p, batch, None), has_aux=True))


def test_logits_loss_and_adapter_gradients_match_the_reference():
    """Four sparse layers at the rehearsal widths, 64 positions with 16
    keys a query: the logits, the loss and every adapter leaf's gradient
    against the independent reference (float32 ``highest``); the adapter
    tree is the reference's, and ``lora_init`` gives the indexer, the norms
    and the router none."""
    cfg = small_cfg()
    base, lora = weights(cfg)
    tok = tokens(cfg)
    x, y = tok[:, :-1], tok[:, 1:]
    lc = system_cfg(cfg, 64)
    grad_fn = REF.make_model(cfg)
    batch = {"x": x, "y": y, "mask": jnp.ones((2,))}
    with jax.default_matmul_precision("highest"):
        want_logits = grad_fn.forward(lora, base, x, None)
        got_logits = CausalLM(lc).apply({"params": base}, x, adapters=lora,
                                        lora_scale=2.0)
        want_g, want_ls, want_n = grad_fn(lora, base, batch, None)
        (_, aux), got_g = _step(cfg, lc, base, batch)(lora)
    assert rel(got_logits, want_logits) < 1e-5
    assert abs(float(aux["loss_sum"]) - float(want_ls)) < 1e-5 * float(want_ls)
    assert float(aux["count"]) == float(want_n) == 128.0
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = dict(jax.tree_util.tree_leaves_with_path(got_g))
    assert len(flat_w) == len(flat_g) == 4 * 4 * 2
    for path, w in flat_w:
        assert float(jnp.abs(w).max()) > 0, path      # no blind leaf
        assert rel(flat_g[path], w) < 1e-4, jax.tree_util.keystr(path)
    mine = lora_init(jax.random.PRNGKey(0), base, rank=cfg["lora_rank"])
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(lora))


def test_the_selection_is_seen_by_the_loss():
    """The reference with every causal key kept, or with the index scores
    taken without their ReLU, is another model: its logits part from the
    system's."""
    cfg = small_cfg()
    base, lora = weights(cfg)
    x = tokens(cfg)[:, :-1]
    with jax.default_matmul_precision("highest"):
        got = CausalLM(system_cfg(cfg, 64)).apply(
            {"params": base}, x, adapters=lora, lora_scale=2.0)
        for fault in ({"sa_config": dict(cfg["sa_config"], topk=64)},
                      {"fault_no_relu": True}):
            other = REF.make_model(dict(cfg, **fault)).forward(
                lora, base, x, None)
            assert rel(got, other) > 1e-3, fault


@pytest.mark.pallas
def test_the_small_model_through_the_kernels_matches_dense():
    """The same step on the ``flash`` path (the index and attention
    kernels interpreted) against the ``dense`` one: loss and gradients."""
    cfg = small_cfg()
    base, lora = weights(cfg)
    tok = tokens(cfg)
    batch = {"x": tok[:, :-1], "y": tok[:, 1:], "mask": jnp.ones((2,))}
    with jax.default_matmul_precision("highest"):
        (want, _), want_g = _step(cfg, system_cfg(cfg, 64), base, batch)(lora)
        (got, _), got_g = _step(cfg, system_cfg(cfg, 64, "flash"), base,
                                batch)(lora)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        assert rel(a, b) < 1e-4


def test_no_gradient_reaches_the_indexer():
    """The loss's gradient toward the frozen base: zero at every indexer
    weight (the selection is discrete and reads its inputs without a
    gradient), nonzero at the attention's projections."""
    cfg = small_cfg()
    base, lora = weights(cfg)
    tok = tokens(cfg)
    lc = system_cfg(cfg, 64)
    batch = {"x": tok[:, :-1], "y": tok[:, 1:], "mask": jnp.ones((2,))}

    def loss(b):
        bundle = LLMBundle(CausalLM(lc), lc, b, cfg["lora_rank"],
                           cfg["lora_alpha"])
        return CausalLMTrainer(bundle.apply).loss(lora, batch, None)[0]

    g = jax.grad(loss)(base)["layer_1"]["attn"]
    for name in ("index_q", "index_k", "index_norm", "index_w"):
        for leaf in jax.tree_util.tree_leaves(g[name]):
            assert float(jnp.abs(leaf).max()) == 0.0, name
    assert float(jnp.abs(g["q"]["kernel"]).max()) > 0


def test_the_ranks_parts_add_up_to_the_uncut_layer():
    """The expert-parallel cut's share test: the parts the 8 ranks give
    for one layer (4 experts each, top-8 of 32 by softmax), what every
    rank computes alike (the attention and the residual) counted once, add
    up to the uncut layer."""
    experts, per_rank = 32, 4
    whole = small_cfg(num_experts=experts, first_expert=0,
                      num_hidden_layers=1,
                      published=dict(small_cfg()["published"],
                                     num_experts=experts))
    base, lora = weights(whole)
    x = tokens(whole)[:, :-1]

    def layer_out(cfg, b):
        mod = CausalLM(system_cfg(cfg, 64))
        _, state = mod.apply({"params": b}, x, adapters=lora,
                             lora_scale=2.0, capture_intermediates=(
                                 lambda m, _: m.name == "layer_0"),
                             mutable=["intermediates", "moe_stats"])
        return state["intermediates"]["layer_0"]["__call__"][0][0]

    def held(b, lo, hi):
        b = dict(b)
        m = dict(b["layer_0"]["moe"])
        for k in ("experts_gate", "experts_up", "experts_down"):
            m[k] = m[k][lo:hi] if hi > lo else jnp.zeros_like(m[k][:1])
        b["layer_0"] = dict(b["layer_0"], moe=m)
        return b

    whole_out = layer_out(whole, base)
    none = layer_out(dict(whole, num_experts=1), held(base, 0, 0))
    total = none
    for r in range(experts // per_rank):
        cut = dict(whole, num_experts=per_rank, first_expert=r * per_rank)
        total = total + (layer_out(cut, held(base, r * per_rank,
                                             (r + 1) * per_rank)) - none)
    assert rel(total, whole_out) < 1e-5
    assert rel(none, whole_out) > 1e-2


# ------------------------------------------- rematerialized layers ---

@pytest.mark.pallas
@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_a_rematerialized_layer_is_the_same_layer(impl):
    """``gradient_checkpointing`` (the configuration sets it) builds each
    layer rematerialized: the loss and every adapter gradient as without
    it, and on the kernel path the backward pass runs the attention's
    forward kernel again but not the indexer, whose selection it keeps."""
    cfg = small_cfg()
    assert system_cfg(cfg, 64).remat
    base, lora = weights(cfg)
    tok = tokens(cfg)
    batch = {"x": tok[:, :-1], "y": tok[:, 1:], "mask": jnp.ones((2,))}
    runs, calls = {}, {}
    for remat in (False, True):
        lc = dataclasses.replace(system_cfg(cfg, 64, impl), remat=remat)
        with jax.default_matmul_precision("highest"):
            runs[remat] = _step(cfg, lc, base, batch)(lora)
        bundle = LLMBundle(CausalLM(lc), lc, base, cfg["lora_rank"],
                           cfg["lora_alpha"])
        spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
        text = str(jax.make_jaxpr(jax.grad(
            lambda p: spec.loss(p, batch, None)[0]))(lora))
        calls[remat] = [text.count(f"name={k}") for k in sa.SPARSE_KERNEL_NAMES]
    (got, _), got_g = runs[True]
    (want, _), want_g = runs[False]
    assert abs(float(got) - float(want)) < 1e-6 * abs(float(want))
    for a, b in zip(jax.tree_util.tree_leaves(got_g),
                    jax.tree_util.tree_leaves(want_g)):
        assert rel(a, b) < 1e-5
    layers = cfg["num_hidden_layers"]
    if impl == "flash":
        by_name = dict(zip(sa.SPARSE_KERNEL_NAMES, calls[True]))
        assert dict(zip(sa.SPARSE_KERNEL_NAMES, calls[False])) == dict.fromkeys(
            sa.SPARSE_KERNEL_NAMES, layers)
        assert by_name == dict.fromkeys(sa.SPARSE_KERNEL_NAMES, layers) | {
            "sparse_fwd": 2 * layers}
    else:
        assert calls[True] == calls[False] == [0] * len(calls[True])


def test_the_references_causal_quarters_change_nothing():
    """The reference takes a quarter of the rows against the keys up to
    that quarter's end where its query blocks allow (8 blocks of 8 rows at
    64 positions): the selections bit for bit, and the logits, the loss and
    the gradients, as when every block takes every key (one block of 64)."""
    cfg = small_cfg()
    base, lora = weights(cfg)
    tok = tokens(cfg)
    x = tok[:, :-1]
    batch = {"x": x, "y": tok[:, 1:], "mask": jnp.ones((2,))}
    out = {}
    for rows in (64, 8):
        grad_fn = REF.make_model(dict(cfg, reference_q_block=rows))
        with jax.default_matmul_precision("highest"):
            out[rows] = (grad_fn.forward(lora, base, x, None),
                         grad_fn(lora, base, batch, None),
                         grad_fn.selections(lora, base, x))
    (logits, (g, ls, _), keeps), (w_logits, (w_g, w_ls, _), w_keeps) = (
        out[8], out[64])
    for a, b in zip(keeps, w_keeps):
        assert bool(jnp.all(a == b))
    assert rel(logits, w_logits) < 1e-6
    assert abs(float(ls) - float(w_ls)) < 1e-6 * float(w_ls)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(w_g)):
        assert rel(a, b) < 1e-5
