"""Latent attention, sparse experts of which a rank holds some, and a bfloat16
base through the federated LoRA path, against the plain reference
(``benchmarks/reference/axk1_ep16_l5.py``: float32, imports nothing of
``fedml_tpu``), at small widths that keep every ratio of the published
model: the rotary part half of the content part, ``d_qk != d_v``, more
experts than top-k, fewer held than experts, one leading dense layer."""

from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.obs import REGISTRY, metrics as obs_metrics
from fedml_tpu.llm import moe
from fedml_tpu.llm.attention import (dense_causal_attention,
                                     flash_causal_attention)
from fedml_tpu.llm.federated import LLMBundle, llm_config_from_hf
from fedml_tpu.llm.lora import lora_init
from fedml_tpu.llm.model import CausalLM, LLMConfig
from fedml_tpu.llm.trainer import CausalLMTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(REPO, "benchmarks", "reference", "axk1_ep16_l5.py")
    spec = importlib.util.spec_from_file_location("ref_axk1", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 32, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}


def small_cfg(held=4, first=5, experts=12, layers=3, **over):
    cfg = {
        "vocab_size": 96, "hidden_size": 48, "intermediate_size": 80,
        "num_hidden_layers": layers, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_routed_experts": held, "published": {"n_routed_experts": experts},
        "first_expert": first, "num_experts_per_tok": 3,
        "moe_intermediate_size": 24, "n_shared_experts": 1,
        "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
        "norm_topk_prob": True, "scoring_func": "sigmoid",
        "topk_method": "none", "rms_norm_eps": 1e-6, "rope_theta": 10000,
        "rope_scaling": YARN, "tie_word_embeddings": False,
        "initializer_range": 0.2, "lora_rank": 4, "lora_alpha": 8.0,
        "lora_b_std": 0.05, "reference_heads_per_group": 2}
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
    """1 dense + 2 expert layers, float32 compute: the system's logits, loss
    and every adapter leaf's gradient against the independent reference
    (float32 ``highest``; the gap is summation order)."""
    cfg = small_cfg()
    base, lora = weights(cfg)
    tok = tokens(cfg)
    x, y = tok[:, :-1], tok[:, 1:]
    bundle = bundle_for(cfg, base, 16)
    grad_fn = REF.make_model(cfg)
    with jax.default_matmul_precision("highest"):
        want_logits = grad_fn.forward(lora, base, x, None)
        want_g, want_ls, want_n = grad_fn(
            lora, base, {"x": x, "y": y, "mask": jnp.ones((2,))}, None)
    got_logits = bundle.apply(lora, x)
    assert rel(got_logits, want_logits) < 2e-5
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    (loss, aux), got_g = jax.value_and_grad(spec.loss, has_aux=True)(
        lora, {"x": x, "y": y, "mask": jnp.ones((2,))}, None)
    assert abs(float(aux["loss_sum"]) - float(want_ls)) < 1e-4 * float(want_ls)
    assert float(aux["count"]) == float(want_n) == 32.0
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = dict(jax.tree_util.tree_leaves_with_path(got_g))
    assert len(flat_w) == len(flat_g) == 2 * (5 * 3 + 3 * 3)
    for path, w in flat_w:
        assert float(jnp.abs(w).max()) > 0, path      # no blind leaf
        assert rel(flat_g[path], w) < 2e-4, jax.tree_util.keystr(path)


def test_adapter_tree_is_the_references_and_experts_stay_frozen():
    cfg = small_cfg()
    base, lora = weights(cfg)
    mine = lora_init(jax.random.PRNGKey(0), base, rank=cfg["lora_rank"])
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(lora))
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(lora)):
        assert a.shape == b.shape
    assert "router" not in mine["layer_1"]["moe"]
    assert set(mine["layer_1"]["moe"]) == {"shared"}
    assert set(mine["layer_0"]) == {"attn", "mlp"}


def test_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the parts that all ranks give for one expert
    layer, the shared expert counted once, add up to the uncut layer."""
    experts, per_rank = 12, 4
    whole = small_cfg(held=experts, first=0, experts=experts, layers=2)
    base, lora = weights(whole)
    x = tokens(whole)[:, :-1]
    full = bundle_for(whole, base, 16).apply(lora, x)

    def without_routed(b):
        b = jax.tree_util.tree_map(lambda a: a, b)
        m = dict(b["layer_1"]["moe"])
        for k in ("experts_gate", "experts_up", "experts_down"):
            m[k] = jnp.zeros_like(m[k][:1])
        b["layer_1"] = dict(b["layer_1"], moe=m)
        return b

    # layer 1 is the last layer: the final norm and head are not linear in
    # its output, so compare the layer's own output: probe it through a
    # model whose head sees the residual stream directly
    def layer_out(cfg, b):
        lc = system_cfg(cfg, 16)
        mod = CausalLM(lc)
        _, state = mod.apply({"params": b}, x, adapters=lora,
                             lora_scale=2.0, capture_intermediates=(
                                 lambda m, _: m.name == "layer_1"),
                             mutable=["intermediates", "moe_stats"])
        return state["intermediates"]["layer_1"]["__call__"][0][0]

    whole_out = layer_out(whole, base)
    shared_only = layer_out(dict(whole, n_routed_experts=1),
                            without_routed(base))
    total = shared_only
    for r in range(experts // per_rank):
        cut = dict(whole, n_routed_experts=per_rank, first_expert=r * per_rank)
        b = jax.tree_util.tree_map(lambda a: a, base)
        m = dict(b["layer_1"]["moe"])
        for k in ("experts_gate", "experts_up", "experts_down"):
            m[k] = m[k][r * per_rank:(r + 1) * per_rank]
        b["layer_1"] = dict(b["layer_1"], moe=m)
        total = total + (layer_out(cut, b) - shared_only)
    assert rel(total, whole_out) < 1e-5
    assert full.shape == (2, 16, whole["vocab_size"])


def dense_loop(x, gates, chosen, w, first):
    """The held experts' part as plain autodiff sees it: every held expert
    over every token, weighted by the gate of the slot that chose it."""
    y = jnp.zeros_like(x)
    for e in range(w[0].shape[0]):
        g = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
        y += (jax.nn.silu(x @ w[0][e]) * (x @ w[1][e])) @ w[2][e] * g[:, None]
    return y


def test_dropless_under_a_skewed_router():
    """Every slot of every token is held, one held expert and a second take
    every token, the other two split the rest unevenly: more rows than the
    compact buffers have, so the pass runs at the worst-case size; every
    held slot finds a row, the result still equals the dense loop's."""
    cfg = small_cfg(layers=2)
    base, _ = weights(cfg)
    lc = system_cfg(cfg, 32)
    first, held, t, k = lc.first_expert, lc.held, 64, lc.num_experts_per_tok
    flat = jax.random.normal(jax.random.PRNGKey(1), (t, cfg["hidden_size"]))
    logits = jnp.full((t, lc.n_routed_experts), -10.0)
    logits = logits.at[:, first].set(10.0).at[:, first + 1].set(9.0)
    logits = logits.at[:30, first + 2].set(5.0).at[30:, first + 3].set(5.0)
    gates, chosen = moe.route(logits, k, 2.5)
    p = moe.plan(chosen, first, held, 16)
    assert np.asarray(p.load).tolist() == [64, 64, 30, 34]
    full = moe.buffer_rows(t, k, held, 16)
    compact = moe.compact_rows(t, k, held, lc.n_routed_experts, 16)
    assert compact < int(p.ends[-1]) == 64 + 64 + 32 + 48 <= full
    assert int(p.slot_held.sum()) == int(p.load.sum())
    rows = np.asarray(p.slot_row)[np.asarray(p.slot_held)]
    assert len(set(rows.tolist())) == len(rows)          # one row a slot
    assert np.asarray(moe.place(p, full, 16).row_used).sum() == len(rows)
    # a buffer too small for the plan places what fits and no row twice
    assert np.asarray(moe.place(p, compact, 16).row_used).sum() == int(
        (rows < compact).sum())
    w = [jnp.asarray(base["layer_1"]["moe"][n]) for n in
         ("experts_gate", "experts_up", "experts_down")]
    got, stats = moe.routed_experts(flat, gates, chosen, *w, first,
                                    lc.n_routed_experts)
    assert rel(got, dense_loop(flat, gates, chosen, w, first)) < 1e-5
    assert float(stats["dropped"]) == 0 and float(stats["load_max"]) == 64
    assert float(stats["compact_steps"]) == 0


# t, h, width, held, first, k over 12 experts at row tiles of 16: the
# worst-case buffers have 176 rows, the compact ones 112
PASS = dict(t=64, h=32, width=16, held=3, first=2, k=2, experts=12, tile_m=16)


def pass_inputs(held_bias=0.0):
    c, key = PASS, jax.random.PRNGKey(5)
    x = jax.random.normal(key, (c["t"], c["h"]))
    logits = jax.random.normal(jax.random.fold_in(key, 1),
                               (c["t"], c["experts"]))
    logits = logits.at[:, c["first"]:c["first"] + c["held"]].add(held_bias)
    w = [jax.random.normal(jax.random.fold_in(key, 2 + i), s) * 0.2
         for i, s in enumerate([(c["held"], c["h"], c["width"])] * 2
                               + [(c["held"], c["width"], c["h"])])]
    return x, logits, w


def pass_sizes():
    c = PASS
    return (moe.compact_rows(c["t"], c["k"], c["held"], c["experts"],
                             c["tile_m"]),
            moe.buffer_rows(c["t"], c["k"], c["held"], c["tile_m"]))


@pytest.mark.parametrize("path,held_bias,n_experts", [
    ("compact", 0.0, 12), ("full", 8.0, 12), ("one_path", 0.0, 4)])
def test_routed_experts_gradients_match_a_dense_loop(path, held_bias,
                                                     n_experts):
    """Gradients through dispatch, the grouped products and combine, toward
    the tokens and toward the gates, against plain autodiff of the loop: a
    routing that fits the compact buffers, one that overflows them (every
    token's slots pulled to held experts), and a rank that holds 3 of 4
    experts, whose compact size is no smaller than the worst case."""
    c = PASS
    x, logits, w = pass_inputs(held_bias)
    logits = logits[:, :n_experts] if n_experts < 12 else logits
    k, first = c["k"], (0 if n_experts < 12 else c["first"])
    seen = {}

    def mine(x, logits):
        gates, chosen = moe.route(logits, k, 2.5)
        y, stats = moe.routed_experts(x, gates, chosen, *w, first, n_experts)
        return jnp.sum(jnp.sin(y)), stats

    def plain(x, logits):
        gates, chosen = moe.route(logits, k, 2.5)
        return jnp.sum(jnp.sin(dense_loop(x, gates, chosen, w, first)))

    jaxpr = jax.make_jaxpr(jax.grad(mine, argnums=(0, 1), has_aux=True))(
        x, logits)
    assert len(conds(jaxpr.jaxpr)) == (0 if path == "one_path" else 2)
    got, seen = jax.grad(mine, argnums=(0, 1), has_aux=True)(x, logits)
    want = jax.grad(plain, argnums=(0, 1))(x, logits)
    assert rel(got[0], want[0]) < 1e-5 and rel(got[1], want[1]) < 1e-5
    assert float(seen["compact_steps"]) == (1 if path == "compact" else 0)
    assert float(seen["dropped"]) == 0


@pytest.mark.parametrize("sizes", ["compact", "full", "one_path"])
def test_the_pass_is_the_same_to_the_last_bit_at_either_size(sizes):
    """One routing through the pass at the compact size, at the worst-case
    size under the conditional (a compact size of one tile does not fit it)
    and at the worst-case size with no conditional, each a jitted program
    as the train step is: output and both gradients equal bit for bit (same
    rows, same tile order, same slot order)."""
    c = PASS
    x, logits, w = pass_inputs()
    gates, chosen = moe.route(logits, c["k"], 2.5)
    p = moe.plan(chosen, c["first"], c["held"], c["tile_m"])
    compact, full = pass_sizes()
    assert int(p.ends[-1]) <= compact < full

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def run(compact, full, x, gates):
        y, vjp = jax.vjp(lambda x, gates: moe.expert_pass(
            compact, full, c["tile_m"], x, gates, *w, p), x, gates)
        return (y,) + vjp(jnp.cos(y))

    want = run(full + c["tile_m"], full + c["tile_m"], x, gates)
    got = run(*{"compact": (compact, full), "full": (c["tile_m"], full),
                "one_path": (full, full)}[sizes], x, gates)
    for g, v in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(v))
    assert rel(got[0], dense_loop(x, gates, chosen, w, c["first"])) < 1e-5


@pytest.mark.parametrize("extra,compact_steps", [(0, 1.0), (1, 0.0)])
def test_the_compact_size_is_taken_up_to_its_last_row(extra, compact_steps):
    """Loads of 48, 32, 32 fill the compact buffers' 112 rows exactly and
    take them; one more slot opens an eighth tile and takes the worst-case
    buffers. Nothing is dropped either way."""
    c = PASS
    x, _, w = pass_inputs()
    a, b, cc, away = c["first"], c["first"] + 1, c["first"] + 2, 0
    chosen = np.full((c["t"], c["k"]), away, np.int32)
    chosen[:48, 0] = a
    chosen[:32, 1], chosen[32:, 1] = b, cc
    chosen[48:48 + extra, 0] = b
    chosen = jnp.asarray(chosen)
    gates = jax.random.uniform(jax.random.PRNGKey(2), chosen.shape) + 0.5
    compact, _ = pass_sizes()
    p = moe.plan(chosen, c["first"], c["held"], c["tile_m"])
    assert int(p.ends[-1]) == compact + 16 * extra
    got, stats = moe.routed_experts(x, gates, chosen, *w, c["first"],
                                    c["experts"])
    assert rel(got, dense_loop(x, gates, chosen, w, c["first"])) < 1e-5
    assert float(stats["compact_steps"]) == compact_steps
    assert float(stats["dropped"]) == 0
    assert float(stats["slots_held"]) == 112 + extra


# ----------------------------------------------------- the lowered program ---

def conds(jaxpr):
    """Every ``cond`` of a jaxpr outside its Pallas kernels, outermost
    first (a ``cond`` inside another's branch is not looked for)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond":
            found.append(eqn)
        elif eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += conds(sub)
    return found


def row_buffers(jaxpr, cfg, into_conds=True):
    """Row counts of everything shaped like a row buffer that a jaxpr makes
    (``[R]``, ``[R, hidden]``, ``[R, expert width]``), its Pallas kernels'
    insides left out; without ``into_conds`` also its conditionals and
    what they hand on."""
    widths = ((), (cfg["hidden_size"],), (cfg["moe_intermediate_size"],))
    rows = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "cond" and not into_conds:
            continue
        rows |= {v.aval.shape[0] for v in eqn.outvars if getattr(
            v.aval, "shape", ()) and v.aval.shape[1:] in widths}
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            rows |= row_buffers(sub, cfg, into_conds)
    return rows


def grouped_calls(jaxpr):
    """How often each grouped product is called in a jaxpr, the branches of
    its conditionals included."""
    count = dict.fromkeys(moe.GROUPED_KERNEL_NAMES, 0)
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            count[eqn.params["name"]] += 1
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            for name, n in grouped_calls(sub).items():
                count[name] += n
    return count


@pytest.mark.parametrize("path,held_bias,n_experts,rebuilt,kept", [
    ("compact", 0.0, 12, 0, 1.0), ("full", 8.0, 12, 2, 0.0),
    ("one_path", 0.0, 4, 0, 1.0)])
def test_the_backward_pass_rebuilds_no_product_it_kept(path, held_bias,
                                                       n_experts, rebuilt,
                                                       kept):
    """The backward pass alone (``jax.vjp``'s pull-back, the residuals its
    constants), in the branch that runs: three ``dx`` products, and a
    forward product only where the pass took the worst-case size under the
    conditional (gate and up, rebuilt); the untaken branch of a compact
    pass is that rebuild. ``kept_steps`` says which it will be."""
    c = PASS
    x, logits, w = pass_inputs(held_bias)
    logits = logits[:, :n_experts] if n_experts < 12 else logits
    first = 0 if n_experts < 12 else c["first"]
    gates, chosen = moe.route(logits, c["k"], 2.5)
    y, pull, stats = jax.vjp(
        lambda x, gates: moe.routed_experts(x, gates, chosen, *w, first,
                                            n_experts), x, gates,
        has_aux=True)
    jaxpr = jax.make_jaxpr(pull)(jnp.ones_like(y)).jaxpr
    fwd_name, dx_name = moe.GROUPED_KERNEL_NAMES
    if path == "one_path":
        assert not conds(jaxpr)
        assert grouped_calls(jaxpr) == {fwd_name: 0, dx_name: 3}
    else:
        (cond,) = conds(jaxpr)
        worst, small = (grouped_calls(b.jaxpr)
                        for b in cond.params["branches"])
        assert worst == {fwd_name: 2, dx_name: 3}
        assert small == {fwd_name: 0, dx_name: 3}
        assert grouped_calls(jaxpr) == {fwd_name: 2, dx_name: 6}
        runs = small if path == "compact" else worst
        assert runs[fwd_name] == rebuilt
    assert float(stats["kept_steps"]) == kept
    assert float(stats["compact_steps"]) == (1 if path == "compact" else 0)


@pytest.mark.parametrize("sizes", ["compact", "full", "one_path"])
def test_the_pull_back_reads_no_unwritten_row(sizes):
    """A skewed routing: 40 slots on the first held expert, none on the
    second, 20 on the third, so 5 of the compact buffers' 7 tiles (and of
    the worst case's 11) are in use and the interpreted kernels leave the
    others' rows NaN, in the kept products too. ``dx`` and ``d_gates`` of
    the pull-back, from kept products and behind the rebuild, against plain
    autodiff of the dense loop: no unwritten row reaches a sum."""
    c = PASS
    x, _, w = pass_inputs()
    chosen = np.zeros((c["t"], c["k"]), np.int32)     # expert 0: not held
    chosen[:40, 0], chosen[10:30, 1] = c["first"], c["first"] + 2
    chosen = jnp.asarray(chosen)
    gates = jax.random.uniform(jax.random.PRNGKey(4), chosen.shape) + 0.5
    p = moe.plan(chosen, c["first"], c["held"], c["tile_m"])
    compact, full = pass_sizes()
    assert np.asarray(p.load).tolist() == [40, 0, 20]
    assert int(p.num_tiles[0]) == 5 < compact // c["tile_m"]
    _, a, _ = moe._pass_at(compact, c["tile_m"], x, gates, *w, p)
    assert np.isnan(np.asarray(a)[5 * c["tile_m"]:]).any()
    size = {"compact": (compact, full), "full": (c["tile_m"], full),
            "one_path": (full, full)}[sizes]

    def mine(x, gates):
        return jnp.sum(jnp.sin(moe.expert_pass(*size, c["tile_m"], x, gates,
                                               *w, p)))

    def plain(x, gates):
        return jnp.sum(jnp.sin(dense_loop(x, gates, chosen, w, c["first"])))

    got = jax.grad(mine, argnums=(0, 1))(x, gates)
    want = jax.grad(plain, argnums=(0, 1))(x, gates)
    assert np.isfinite(np.asarray(got[0])).all()
    assert np.isfinite(np.asarray(got[1])).all()
    assert rel(got[0], want[0]) < 1e-5 and rel(got[1], want[1]) < 1e-5
    assert not np.asarray(got[1])[~np.asarray(p.slot_held)].any()


def test_the_train_step_holds_one_conditional_a_layer_and_direction():
    """The small model's train step (2 rows x 16 tokens, 4 of 12 experts
    held: worst case 160 rows, compact 128): each of the 2 expert layers is
    a real conditional on an unbatched scalar in the forward and in the
    backward pass (a ``select`` over both branches would leave no ``cond``
    in the jaxpr), nothing of the worst-case size leaves a conditional,
    the forward one hands on exactly two ``[compact, width]`` arrays (the
    gate and up products the backward pass works from) and the backward
    one nothing of a row buffer's size, the compact branch makes nothing
    of the worst-case size, and nothing outside the conditionals makes an
    array of either size."""
    cfg = small_cfg()
    base, lora = weights(cfg)
    tok = tokens(cfg)
    batch = {"x": tok[:, :-1], "y": tok[:, 1:], "mask": jnp.ones((2,))}
    bundle = bundle_for(cfg, base, 16)
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    lc = bundle.cfg
    t, k = 32, lc.num_experts_per_tok
    tile_m = moe.tile_rows(t * k)
    full = moe.buffer_rows(t, k, lc.held, tile_m)
    compact = moe.compact_rows(t, k, lc.held, lc.n_routed_experts, tile_m)
    assert (compact, full) == (128, 160)
    step = jax.grad(lambda p: spec.loss(p, batch, None)[0])
    jaxpr = jax.make_jaxpr(step)(lora).jaxpr
    found = conds(jaxpr)
    assert len(found) == 2 * 2
    kept = [(compact, cfg["moe_intermediate_size"])] * 2
    for eqn, hands_on in zip(found, [kept, kept, [], []]):
        assert eqn.invars[0].aval.shape == ()            # the predicate
        assert [v.aval.shape for v in eqn.outvars
                if v.aval.shape[:1] in ((full,), (compact,))] == hands_on
        worst, small = (b.jaxpr for b in eqn.params["branches"])
        assert full in row_buffers(worst, cfg)
        assert compact in row_buffers(small, cfg)
        assert full not in row_buffers(small, cfg)
    assert not {full, compact} & row_buffers(jaxpr, cfg, into_conds=False)
    assert jax.jit(step).lower(lora).as_text().count("stablehlo.case") >= 4


# ------------------------------------------------------------ flash kernels ---

@pytest.mark.pallas
@pytest.mark.parametrize("scale", [None, 0.11])
def test_flash_kernels_at_unequal_head_sizes_match_dense(scale):
    """``d_qk = 24 != d_v = 16`` in interpret mode: forward and the three
    gradients against dense attention."""
    key = jax.random.PRNGKey(2)
    b, s, h, d_qk, d_v = 2, 256, 2, 24, 16
    q = jax.random.normal(key, (b, s, h, d_qk))
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, d_qk))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, d_v))
    c = jax.random.normal(jax.random.fold_in(key, 3), (b, s, h, d_v))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * c)

    flash = lambda q, k, v: flash_causal_attention(  # noqa: E731
        q, k, v, block_q=128, block_k=128, scale=scale)
    dense = lambda q, k, v: dense_causal_attention(  # noqa: E731
        q, k, v, scale=scale)
    assert flash(q, k, v).shape == (b, s, h, d_v)
    assert rel(flash(q, k, v), dense(q, k, v)) < 1e-5
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape and rel(g, w) < 1e-5


@pytest.mark.pallas
def test_latent_attention_through_flash_matches_dense():
    cfg = small_cfg(layers=1, first_k_dense_replace=1)
    base, lora = weights(cfg)
    x = tokens(cfg, rows=1, seq=128)[:, :-1]
    dense = bundle_for(cfg, base, 128).apply(lora, x)
    flash = bundle_for(cfg, base, 128, impl="flash").apply(lora, x)
    assert rel(flash, dense) < 1e-5


# ------------------------------------------------------------ bfloat16 base ---

def test_a_bf16_base_stays_bf16_through_the_bundle():
    """No float32 copy of a frozen kernel: the lowered train step has no
    float32 argument or constant of a frozen kernel's shape."""
    cfg = small_cfg()
    base, lora = weights(cfg, dtype=jnp.bfloat16)
    bundle = bundle_for(cfg, base, 16, dtype="bfloat16")
    assert all(a.dtype == jnp.bfloat16
               for a in jax.tree_util.tree_leaves(bundle.base_params))
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    tok = tokens(cfg)
    batch = {"x": tok[:, :-1], "y": tok[:, 1:], "mask": jnp.ones((2,))}
    lowered = jax.jit(jax.grad(lambda p: spec.loss(p, batch, None)[0])
                      ).lower(lora)
    text = lowered.as_text()
    frozen_shapes = {tuple(a.shape) for a in jax.tree_util.tree_leaves(base)
                     if a.ndim >= 2}
    adapter_shapes = {tuple(a.shape) for a in jax.tree_util.tree_leaves(lora)}
    import re
    for dims, dtype in re.findall(r"tensor<([0-9x]+)x(f32|bf16)>", text):
        shape = tuple(int(n) for n in dims.split("x"))
        if dtype == "f32" and shape in frozen_shapes - adapter_shapes:
            # a float32 value of a frozen kernel's shape may only be an
            # activation that happens to share it, never an argument or a
            # constant: look for it among those
            assert not re.search(
                rf"(%arg\d+: tensor<{dims}xf32>|constant.*tensor<{dims}xf32>)",
                text), shape
    assert "xbf16>" in text


def test_full_fine_tune_of_experts_is_refused():
    cfg = small_cfg()
    lc = system_cfg(cfg, 16)
    with pytest.raises(NotImplementedError, match="routed experts"):
        LLMBundle(CausalLM(lc), lc, None, 0, 16.0)


# ---------------------------------------------------- counters and refusals ---

def test_round_counters_reach_the_registry_from_the_round_program():
    """A federated LoRA round of the small model through ``TPUSimulator``:
    the ``fed_moe_*`` instruments are set from the round's own metrics (read
    at the next round's dispatch, or when the caller who has read the round's
    loss flushes them: never by a wait of their own); nothing is dropped."""
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
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    sim = TPUSimulator(args, fed, bundle, create_optimizer(args, spec), spec)
    hyper = TrainHyper(learning_rate=jnp.float32(0.05), epochs=1)
    dropped_before = REGISTRY.counter("fed_moe_dropped").value()
    m0 = sim.run_round(0, hyper)
    assert np.isfinite(float(m0["loss_sum"])) and float(m0["count"]) == 64.0
    # 2 expert layers x (2 silos x 2 steps) passes, 16 tokens x top-3 each
    assert float(m0["moe_layer_steps"]) == 8.0
    assert float(m0["moe_expert_steps"]) == 8.0 * cfg["n_routed_experts"]
    assert 0 < float(m0["moe_slots_held"]) <= 8 * 16 * 3
    assert 0 <= float(m0["moe_compact_steps"]) <= 8.0
    assert float(m0["moe_kept_steps"]) == float(m0["moe_compact_steps"])
    rounds_before = REGISTRY.counter("fed_moe_rounds_total").value()
    passes_before = REGISTRY.counter("fed_moe_layer_steps_total").value()
    compact_before = REGISTRY.counter("fed_moe_compact_steps_total").value()
    kept_before = REGISTRY.counter("fed_moe_kept_steps_total").value()
    m1 = sim.run_round(1, hyper)   # records round 0's sums, now ready
    assert REGISTRY.gauge("fed_moe_slots_held").value() == float(
        m0["moe_slots_held"])
    top = REGISTRY.gauge("fed_moe_load_max").value()
    mean = REGISTRY.gauge("fed_moe_load_mean").value()
    assert top >= mean > 0
    # the last round's sums: the caller who has read its loss flushes them
    float(m1["loss_sum"])
    sim.flush_program_counters()
    sim.flush_program_counters()                     # nothing held: no-op
    assert REGISTRY.counter("fed_moe_rounds_total").value() == rounds_before + 2
    assert REGISTRY.gauge("fed_moe_slots_held").value() == float(
        m1["moe_slots_held"])
    assert REGISTRY.counter("fed_moe_dropped").value() == dropped_before
    assert REGISTRY.counter("fed_moe_layer_steps_total").value() == \
        passes_before + 16
    assert REGISTRY.counter("fed_moe_compact_steps_total").value() == \
        compact_before + float(m0["moe_compact_steps"]) + float(
            m1["moe_compact_steps"])
    assert REGISTRY.counter("fed_moe_kept_steps_total").value() == \
        kept_before + float(m0["moe_kept_steps"]) + float(
            m1["moe_kept_steps"])
    # with the registry off nothing is held, so nothing is read back
    obs_metrics.set_enabled(False)
    try:
        sim.run_round(2, hyper)
        assert sim._program_counters is None
    finally:
        obs_metrics.set_enabled(True)
    # run() records every round after its own readback, a fused block's too
    sim.run(comm_round=2)
    assert REGISTRY.counter("fed_moe_rounds_total").value() == rounds_before + 4
    assert REGISTRY.counter("fed_moe_dropped").value() == dropped_before


def test_the_cache_path_of_latent_attention_refuses_clearly():
    cfg = small_cfg(layers=1)
    base, _ = weights(cfg)
    lc = system_cfg(cfg, 16)
    x = tokens(cfg)[:, :-1]
    view = [(jnp.zeros((2, 16, 4, 24)), jnp.zeros((2, 16, 4, 16)))]
    with pytest.raises(NotImplementedError, match="latent"):
        CausalLM(lc).apply({"params": base}, x, kv_view=view,
                           positions=jnp.broadcast_to(jnp.arange(16), (2, 16)))


def test_dense_count_refuses_latent_attention_and_experts():
    lc = system_cfg(small_cfg(), 16)
    with pytest.raises(NotImplementedError, match="benchmarks/flops"):
        lc.param_count()
    assert LLMConfig().param_count() > 0


@pytest.mark.parametrize("key,value", [("topk_method", "group_limited_greedy"),
                                       ("scoring_func", "softmax")])
def test_unbuilt_routing_variants_are_refused(key, value):
    with pytest.raises(NotImplementedError):
        system_cfg(small_cfg(**{key: value}), 16)


def test_yarn_frequencies_blend_between_plain_and_interpolated():
    from fedml_tpu.llm.model import rope_frequencies, yarn_mscale
    plain = np.asarray(rope_frequencies(64, 10000.0))
    yarn = np.asarray(rope_frequencies(64, 10000.0, YARN))
    np.testing.assert_allclose(yarn, np.asarray(
        REF.yarn_frequencies(64, 10000.0, YARN)), rtol=1e-6)
    assert np.allclose(yarn[:8], plain[:8])            # fast dims kept
    assert np.allclose(yarn[-4:], plain[-4:] / 32)     # slow dims stretched
    assert np.all(yarn <= plain * (1 + 1e-6)) and np.all(yarn >= plain / 32.001)
    assert abs(yarn_mscale(YARN, "mscale_all_dim") - 1.34657) < 1e-4
    assert yarn_mscale(None, "mscale_all_dim") == 1.0
