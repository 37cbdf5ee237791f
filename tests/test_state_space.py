"""Mamba-2 state-space layers, latent ``relu2`` experts beside a shared one
and attention without positions, one mixer a layer, through the federated
LoRA path, against the plain reference
(``benchmarks/reference/nemotron3_super_ep8_l11.py``: float32, imports
nothing of ``fedml_tpu``, the state-space layer as the token recurrence) at
small widths that keep every ratio of the published model: heads in groups
that share B and C, more experts than top-k, fewer held than experts, many
query heads a key-value head."""

from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.obs import REGISTRY
from fedml_tpu.llm import moe, state_space as ss
from fedml_tpu.llm.federated import LLMBundle, llm_config_from_hf
from fedml_tpu.llm.lora import lora_init
from fedml_tpu.llm.model import (MLP, Attention, CausalLM, LLMConfig, Mamba2,
                                 MoE)
from fedml_tpu.llm.trainer import CausalLMTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(REPO, "benchmarks", "reference",
                        "nemotron3_super_ep8_l11.py")
    spec = importlib.util.spec_from_file_location("ref_nemotron", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def small_cfg(held=4, first=4, experts=16, pattern="MEM*E", **over):
    cfg = {
        "model_type": "nemotron_h", "vocab_size": 96, "hidden_size": 32,
        "intermediate_size": 24, "num_hidden_layers": len(pattern),
        "hybrid_override_pattern": pattern, "num_attention_heads": 8,
        "num_key_value_heads": 2, "head_dim": 4, "mamba_num_heads": 8,
        "mamba_head_dim": 8, "expand": 2, "ssm_state_size": 16,
        "n_groups": 2, "conv_kernel": 4, "chunk_size": 16,
        "use_conv_bias": True, "mamba_hidden_act": "silu",
        "mlp_hidden_act": "relu2", "moe_latent_size": 16,
        "moe_intermediate_size": 24,
        "moe_shared_expert_intermediate_size": 40, "n_routed_experts": held,
        "published": {"n_routed_experts": experts}, "first_expert": first,
        "n_shared_experts": 1, "num_experts_per_tok": 6, "n_group": 1,
        "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 5,
        "layer_norm_epsilon": 1e-5, "partial_rotary_factor": 1,
        "rope_theta": 10000, "attention_bias": False, "use_bias": False,
        "mamba_proj_bias": False, "mlp_bias": False,
        "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
        "time_step_min": 0.001, "time_step_max": 0.1,
        "time_step_floor": 1e-4, "initializer_range": 0.2,
        "router_bias_range": 0.3, "lora_rank": 4, "lora_alpha": 8.0,
        "lora_b_std": 0.05, "reference_heads_per_group": 4,
        "reference_expert_rows": 24, "reference_ssm_segment": 8}
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


def tokens(cfg, rows=2, seq=24, seed=3):
    return jax.random.randint(jax.random.PRNGKey(seed), (rows, seq + 1), 0,
                              cfg["vocab_size"]).astype(jnp.int32)


def bundle_for(cfg, base, seq, **kw):
    lc = system_cfg(cfg, seq, **kw)
    return LLMBundle(CausalLM(lc), lc, base, cfg["lora_rank"],
                     cfg["lora_alpha"])


def rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


# ---------------------------------------------------- the recurrence alone ---

def draw(b, s, h, p, g, n, seed=0, dtype=jnp.float32):
    """Operands whose decays cover the published range: ``delta A`` from
    -1.6 to -0.001 a step, so a chunk's product underflows for some heads
    and stays near 1 for others."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, h, p)).astype(dtype)
    bm = (jax.random.normal(ks[1], (b, s, g, n)) * 0.5).astype(dtype)
    cm = (jax.random.normal(ks[2], (b, s, g, n)) * 0.5).astype(dtype)
    a = -jnp.exp(jnp.linspace(0.0, jnp.log(16.0), h))
    dt = jnp.exp(jax.random.uniform(ks[4], (b, s, h), minval=jnp.log(0.001),
                                    maxval=jnp.log(0.1)))
    return x, dt, a, bm, cm, jax.random.normal(ks[5], (h,))


def value_and_grads(fn, args, w):
    return jax.value_and_grad(lambda *a: jnp.sum(fn(*a) * w),
                              argnums=tuple(range(6)))(*args)


# (b, s, h, p, g, n, chunk): a row shorter than a chunk and no multiple of
# the tile; rows that end inside a chunk; whole chunks at the published one
SHAPES = [(2, 40, 4, 8, 2, 16, 128), (1, 100, 8, 16, 2, 16, 32),
          (2, 72, 4, 8, 1, 16, 16), (1, 256, 4, 64, 2, 128, 128)]


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_the_chunked_form_matches_the_token_recurrence(shape):
    """Output and every gradient (x, delta, A, B, C, D) of the ``dense``
    chunked form against the recurrence run token by token."""
    *dims, chunk = shape
    args = draw(*dims)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    assert float(jnp.min(args[1] * args[2])) < -1.0
    assert float(jnp.max(args[1] * args[2])) > -0.002
    with jax.default_matmul_precision("highest"):
        want, want_g = value_and_grads(ss.ssd_recurrence, args, w)
    got, got_g = value_and_grads(
        lambda *a: ss.ssd_scan(*a, impl="dense", chunk=chunk), args, w)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want)) + 1e-5
    assert rel(ss.ssd_scan(*args, chunk=chunk),
               ss.ssd_recurrence(*args)) < 2e-6
    for name, g, wg in zip("x dt a b c d".split(), got_g, want_g):
        assert rel(g, wg) < 2e-5, name


@pytest.mark.pallas
@pytest.mark.parametrize("shape", [(1, 100, 8, 64, 2, 128, 32),
                                   (2, 256, 4, 64, 2, 128, 128),
                                   (1, 64, 4, 128, 4, 128, 32)],
                         ids=lambda s: "x".join(map(str, s)))
def test_the_interpreted_kernels_match_the_dense_form(shape):
    """``ssd_fwd`` / ``ssd_bwd`` run the same chunk step: output and every
    gradient agree with the scan's to round-off (two heads of 64 channels
    share a block of lanes; one of 128 has its own)."""
    *dims, chunk = shape
    args = draw(*dims, seed=1)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    want, want_g = value_and_grads(
        lambda *a: ss.ssd_scan(*a, impl="dense", chunk=chunk), args, w)
    got, got_g = value_and_grads(
        lambda *a: ss.ssd_scan(*a, impl="flash", chunk=chunk), args, w)
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want)) + 1e-5
    for name, g, wg in zip("x dt a b c d".split(), got_g, want_g):
        assert rel(g, wg) < 1e-5, name


@pytest.mark.pallas
def test_a_step_of_fewer_heads_than_a_group_sums_its_parts(monkeypatch):
    """With fewer heads a grid step than a group has, each step writes its
    heads' part of dB and dC and the parts are summed."""
    args = draw(1, 64, 8, 64, 2, 128, seed=2)
    w = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    want, want_g = value_and_grads(
        lambda *a: ss.ssd_scan(*a, impl="dense", chunk=32), args, w)
    monkeypatch.setattr(ss, "HEADS_PER_STEP", 2)
    assert ss.heads_per_step(8, 2, 64) == 2
    for impl in ("dense", "flash"):
        got, got_g = value_and_grads(
            lambda *a: ss.ssd_scan(*a, impl=impl, chunk=32), args, w)
        assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
        for g, wg in zip(got_g, want_g):
            assert rel(g, wg) < 1e-5, impl


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_a_rows_state_starts_at_zero(impl):
    """Two rows of a batch do not mix, and a row's result does not depend
    on the row before it in the batch."""
    args = draw(2, 48, 4, 64, 2, 128, seed=3)
    both = ss.ssd_scan(*args, impl=impl, chunk=16)
    for r in range(2):
        alone = ss.ssd_scan(*(a[r:r + 1] if a.ndim > 1 else a for a in args),
                            impl=impl, chunk=16)
        assert rel(both[r:r + 1], alone) < 1e-6
    swapped = ss.ssd_scan(*(a[::-1] if a.ndim > 1 else a for a in args),
                          impl=impl, chunk=16)
    assert rel(swapped[::-1], both) < 1e-6


def test_a_masked_position_neither_writes_nor_decays():
    """``delta`` 0 at a position leaves the state as it was: the positions
    after it read what they would without it."""
    x, dt, a, bm, cm, d = draw(1, 32, 4, 8, 2, 16, seed=4)
    keep = jnp.ones((1, 32)).at[0, 10].set(0.0)
    got = ss.ssd_scan(x, dt * keep[..., None], a, bm, cm, d, chunk=16)
    cut = lambda t: jnp.concatenate([t[:, :10], t[:, 11:]], 1)  # noqa: E731
    want = ss.ssd_scan(cut(x), cut(dt), a, cut(bm), cut(cm), d, chunk=16)
    assert rel(cut(got), want) < 1e-5


def test_the_plan_gauges_are_set_when_a_call_is_traced():
    """The plan of a call at the benchmark's shape: chunks of 128, 16 of a
    group's heads a grid step."""
    args = draw(1, 4096, 128, 64, 8, 128)
    jax.eval_shape(lambda *a: ss.ssd_scan(*a, impl="dense"), *args)
    assert ss.chunk_size(4096) == 128
    assert ss.heads_per_step(128, 8, 64) == 16
    assert ss.chunk_size(40) == 48
    assert ss.heads_per_lane_block(64, 16) == 2
    assert ss.heads_per_lane_block(128, 4) == 1
    with pytest.raises(ValueError, match="128 grid"):
        ss.ssd_scan(*draw(1, 32, 4, 8, 2, 16), impl="flash")
    with pytest.raises(ValueError, match="groups"):
        ss.ssd_scan(*draw(1, 32, 4, 8, 3, 16))


# ---------------------------------------------- system against reference ---

def test_logits_loss_and_adapter_gradients_match_the_reference():
    """Two Mamba-2 layers, two latent expert layers and an attention layer
    without positions, float32: the system's logits, loss and every adapter
    leaf's gradient against the independent reference."""
    cfg = small_cfg()
    base, lora = weights(cfg)
    tok = tokens(cfg)
    x, y = tok[:, :-1], tok[:, 1:]
    bundle = bundle_for(cfg, base, 24)
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
    assert float(aux["count"]) == float(want_n) == 48.0
    assert float(aux["ssm_layer_steps"]) == 2.0
    assert float(aux["moe_layer_steps"]) == 2.0
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = dict(jax.tree_util.tree_leaves_with_path(got_g))
    assert len(flat_w) == len(flat_g) == 2 * (2 * 2 + 2 * 4 + 4)
    for path, w in flat_w:
        assert float(jnp.abs(w).max()) > 0, path      # no blind leaf
        assert rel(flat_g[path], w) < 2e-4, jax.tree_util.keystr(path)


@pytest.mark.pallas
def test_the_flash_path_of_the_stack_matches_the_dense_one():
    """``attention_impl`` ``flash`` runs the SSD kernels (interpreted here)
    in the state-space layers: head and state sizes on the 128 grid."""
    cfg = small_cfg(pattern="ME", mamba_num_heads=4, mamba_head_dim=64,
                    hidden_size=128, ssm_state_size=128, head_dim=16)
    base, lora = weights(cfg)
    x = tokens(cfg)[:, :-1]
    dense = bundle_for(cfg, base, 24).apply(lora, x)
    flash = bundle_for(cfg, base, 24, impl="flash").apply(lora, x)
    assert rel(flash, dense) < 1e-5


# ---------------------------------------- the passes around the kernels ---

def mamba_grads(seq, chunk, dtype, impl, masked, conv_bias, b=2, heads=4,
                p=64, g=2, n=128, d=64):
    """One ``Mamba2`` layer with rank-4 adapters on both products, its
    frozen parameters drawn as the benchmark's reference draws them ->
    (loss, gradients toward the parameters, the adapters and ``x``)."""
    lc = LLMConfig(hidden_size=d, ssm_heads=heads, ssm_head_dim=p,
                   ssm_state_size=n, ssm_groups=g, ssm_chunk=chunk,
                   ssm_conv_bias=conv_bias, dtype=dtype, rms_eps=1e-5,
                   attention_impl=impl)
    m = Mamba2(lc)
    ks = jax.random.split(jax.random.PRNGKey(5), 12)
    x = jax.random.normal(ks[0], (b, seq, d))
    params = dict(m.init(ks[1], x[:, :16], None)["params"])
    wide, inner = heads * p + 2 * g * n, heads * p
    params["conv_w"] = jax.random.normal(ks[2], (4, wide)) * 0.5
    if conv_bias:
        params["conv_b"] = jax.random.uniform(ks[3], (wide,), minval=-0.1,
                                              maxval=0.1)
    params["A_log"] = jnp.log(jax.random.uniform(ks[4], (heads,), minval=1,
                                                 maxval=16))
    dt = jnp.exp(jax.random.uniform(ks[5], (heads,), minval=np.log(1e-3),
                                    maxval=np.log(0.1)))
    params["dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    params["D"] = jax.random.normal(ks[6], (heads,))
    params["norm"] = {"scale": 1 + 0.1 * jax.random.normal(ks[7], (inner,))}
    side = lambda k, i, o: jax.random.normal(k, (i, o)) * 0.2  # noqa: E731
    lora = {"in_proj": {"lora_a": side(ks[8], d, 4),
                        "lora_b": side(ks[9], 4, inner + wide + heads)},
            "out_proj": {"lora_a": side(ks[10], inner, 4),
                         "lora_b": side(ks[11], 4, d)}}
    mask = None
    if masked:
        mask = jnp.ones((b, seq), jnp.int32).at[0, 5].set(0).at[
            1, seq // 2:seq // 2 + 3].set(0)
    w = jax.random.normal(jax.random.PRNGKey(99), (b, seq, d))

    def loss(params, lora, x):
        y, _ = m.apply({"params": params}, x.astype(lc.compute_dtype), None,
                       mask, adapter=lora, lora_scale=2.0)
        return jnp.sum(y.astype(jnp.float32) * w)

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        params, lora, x)


# (seq, chunk, masked, conv bias, blocks of the passes: rows before the
# kernels, rows and columns after them)
MAMBA_CASES = {
    # three row blocks a direction: the convolution's halo crosses two
    # block edges forward (the rows before) and backward (the rows after);
    # two column blocks after the kernels
    "halo": (96, 32, True, True, (32, 32, 128)),
    # a row shorter than one block: one chunk of 48 rows, no bias
    "short": (40, 128, False, False, None),
    # a row of 100 in chunks of 32: padded to four blocks
    "ragged": (100, 32, True, True, (32, 32, 128)),
}


@pytest.mark.pallas
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(MAMBA_CASES))
def test_the_fused_passes_match_the_modules_form(case, dtype, monkeypatch):
    """``flash`` runs the layer's element-wise work in the four passes
    (interpreted here) around the kernels: the loss and every gradient,
    toward both adapters, ``x`` and the frozen parameters, against the
    module's ``jax.numpy`` form around the same kernels' dense form. In
    float32 to round-off; in bfloat16 the passes round where the module
    does (x, B and C after the SiLU, the gated norm's output)."""
    seq, chunk, masked, conv_bias, blocks = MAMBA_CASES[case]
    if blocks:
        for name, v in zip(("_PRE_ROWS", "_POST_ROWS", "_POST_COLS"),
                           blocks):
            monkeypatch.setattr(ss, name, v)
    got, got_g = mamba_grads(seq, chunk, dtype, "flash", masked, conv_bias)
    want, want_g = mamba_grads(seq, chunk, dtype, "dense", masked,
                               conv_bias)
    tol = 1e-5 if dtype == "float32" else 5e-3
    assert abs(float(got) - float(want)) < tol * abs(float(want))
    flat = dict(jax.tree_util.tree_leaves_with_path(want_g))
    assert len(flat) == 13 - (not conv_bias)
    for path, g in jax.tree_util.tree_leaves_with_path(got_g):
        assert float(jnp.abs(flat[path]).max()) > 0, path
        assert rel(g, flat[path]) < tol, jax.tree_util.keystr(path)


def test_the_fused_gauge_says_which_path_was_traced():
    """The fused layer's lowered text holds its passes beside the kernels,
    that of a caller who made the kernels' operands itself the kernels
    alone; a product whose B columns do not fall on its own blocks is
    refused."""
    f32 = jnp.float32
    heads, head = 8, jax.ShapeDtypeStruct((8,), f32)
    zx = jax.ShapeDtypeStruct((1, 256, 512 + 1536 + 8), f32)

    def layer(zx, *params, **kw):
        return ss.ssm_layer(zx, None, *params, heads=heads, head_dim=64,
                            groups=4, state=128, **kw)

    params = (jax.ShapeDtypeStruct((4, 1536), f32),
              jax.ShapeDtypeStruct((1536,), f32), head, head, head,
              jax.ShapeDtypeStruct((512,), f32))
    fused = jax.jit(layer).lower(zx, *params).as_text(debug_info=True)
    assert ss.chunk_size(256) == 128
    plain = jax.jit(lambda *a: ss.ssd_scan(*a, impl="flash")).lower(
        *draw(1, 256, 8, 64, 4, 128)).as_text(debug_info=True)
    for name in ("ssm_pre_fwd", "ssm_post_fwd", ss.SSD_KERNEL_NAMES[0]):
        assert name in fused, name
    assert ss.SSD_KERNEL_NAMES[0] in plain
    assert not any(name in plain for name in ss.SSM_PASS_NAMES)
    with pytest.raises(ValueError, match="column blocks"):
        jax.eval_shape(lambda zx: ss.ssm_layer(
            zx, None, jnp.zeros((4, 1024)), None, jnp.zeros(2),
            jnp.zeros(2), jnp.zeros(2), jnp.zeros(256), heads=2,
            head_dim=128, groups=1, state=384),
            jax.ShapeDtypeStruct((1, 128, 256 + 1024 + 2), f32))


@pytest.mark.parametrize("fault", ["fault_no_decay", "fault_plain_relu"])
def test_the_planted_faults_are_seen_by_the_loss(fault):
    """What the benchmark's faults plant in the reference moves the small
    model's logits: a state that forgets nothing, ``relu`` for ``relu^2``."""
    cfg = small_cfg()
    base, lora = weights(cfg)
    x = tokens(cfg)[:, :-1]
    with jax.default_matmul_precision("highest"):
        sound = REF.make_model(cfg).forward(lora, base, x, None)
        faulty = REF.make_model(dict(cfg, **{fault: True})).forward(
            lora, base, x, None)
    assert rel(faulty, sound) > 1e-3


def test_adapter_tree_is_the_references_and_the_rest_stays_frozen():
    cfg = small_cfg()
    base, lora = weights(cfg)
    mine = lora_init(jax.random.PRNGKey(0), base, rank=cfg["lora_rank"])
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(lora))
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(lora)):
        assert a.shape == b.shape
    assert set(mine["layer_0"]["mixer"]) == {"in_proj", "out_proj"}
    assert set(mine["layer_1"]["mixer"]) == {"latent_down", "latent_up",
                                             "shared"}
    assert set(mine["layer_1"]["mixer"]["shared"]) == {"up", "down"}
    assert set(mine["layer_3"]["mixer"]) == {"q", "k", "v", "o"}
    # the system's own tree is the reference's
    lc = system_cfg(cfg, 24)
    params = CausalLM(lc).init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 8), jnp.int32))["params"]
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(base))
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(base)):
        assert a.shape == b.shape
    assert "experts_gate" not in params["layer_1"]["mixer"]
    assert "gate" not in params["layer_1"]["mixer"]["shared"]
    assert set(params["layer_0"]) == {"norm", "mixer"}


def test_the_ranks_routed_parts_and_the_shared_expert_once_add_up():
    """The guide's share test under bias-corrected top-k in a latent: the 8
    ranks' routed parts of one expert layer, each through its own ``W_up``
    (which is linear), and the shared expert counted once, add up to what
    the reference gives for the uncut layer."""
    experts, per_rank = 16, 2
    whole = small_cfg(held=experts, first=0, experts=experts, pattern="E")
    base, lora = weights(whole)
    bp, lp = base["layer_0"]["mixer"], lora["layer_0"]["mixer"]
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))
    with jax.default_matmul_precision("highest"):
        want = REF.make_model(whole).mixers["E"](h, bp, lp, None)
    lc = system_cfg(whole, 24)
    shared = MLP(lc, lc.shared_expert_size).apply(
        {"params": bp["shared"]}, h, adapter=lp["shared"], lora_scale=2.0)
    total = shared
    for r in range(experts // per_rank):
        cut = dict(whole, n_routed_experts=per_rank,
                   first_expert=r * per_rank)
        mine = dict(bp, **{k: bp[k][r * per_rank:(r + 1) * per_rank]
                           for k in ("experts_up", "experts_down")})
        part, _ = MoE(system_cfg(cut, 24)).apply(
            {"params": mine}, h, adapter=lp, lora_scale=2.0,
            mutable=["moe_stats"])
        total = total + (part - shared)
    assert rel(total, want) < 1e-5
    # a token's 6 slots all land on some rank: the parts are not nothing
    assert rel(shared, want) > 0.05


# the non-gated pass: t, latent, width, held, first, k over 12 experts at
# row tiles of 16 (worst-case buffers 176 rows, compact ones 112)
PASS = dict(t=64, h=32, width=16, held=3, first=2, k=2, experts=12)


def relu2_loop(x, gates, chosen, w_up, w_down, first):
    """The held non-gated experts' part, token by token and slot by slot."""
    out = []
    for t in range(x.shape[0]):
        y = jnp.zeros_like(x[t])
        for j in range(chosen.shape[1]):
            e = chosen[t, j] - first
            held = (e >= 0) & (e < w_up.shape[0])
            e = jnp.clip(e, 0, w_up.shape[0] - 1)
            y += jnp.where(held, gates[t, j], 0.0) * (
                jnp.square(jax.nn.relu(x[t] @ w_up[e])) @ w_down[e])
        out.append(y)
    return jnp.stack(out)


@pytest.mark.parametrize("path,held_bias,n_experts", [
    ("compact", 0.0, 12), ("full", 8.0, 12), ("one_path", 0.0, 4)])
def test_relu2_experts_match_a_per_token_loop(path, held_bias, n_experts):
    """The two-product pass (no gate kernel) forward, toward the tokens and
    toward the gates, through the compact branch, the worst-case branch
    (every token's slots pulled to held experts) and the one-path case."""
    c, key = PASS, jax.random.PRNGKey(5)
    x = jax.random.normal(key, (c["t"], c["h"]))
    logits = jax.random.normal(jax.random.fold_in(key, 1),
                               (c["t"], c["experts"]))
    logits = logits.at[:, c["first"]:c["first"] + c["held"]].add(held_bias)
    logits = logits[:, :n_experts]
    first = 0 if n_experts < 12 else c["first"]
    w_up = jax.random.normal(jax.random.fold_in(key, 2),
                             (c["held"], c["h"], c["width"])) * 0.2
    w_down = jax.random.normal(jax.random.fold_in(key, 3),
                               (c["held"], c["width"], c["h"])) * 0.2

    def mine(x, logits):
        gates, chosen = moe.route(logits, c["k"], 2.5)
        y, stats = moe.routed_experts(x, gates, chosen, None, w_up, w_down,
                                      first, n_experts)
        return jnp.sum(jnp.sin(y)), (y, stats)

    def plain(x, logits):
        gates, chosen = moe.route(logits, c["k"], 2.5)
        y = relu2_loop(x, gates, chosen, w_up, w_down, first)
        return jnp.sum(jnp.sin(y)), y

    (_, (y, seen)), got = jax.value_and_grad(
        mine, argnums=(0, 1), has_aux=True)(x, logits)
    (_, want_y), want = jax.value_and_grad(
        plain, argnums=(0, 1), has_aux=True)(x, logits)
    assert rel(y, want_y) < 1e-5
    assert rel(got[0], want[0]) < 1e-5 and rel(got[1], want[1]) < 1e-5
    assert float(seen["compact_steps"]) == (1 if path == "compact" else 0)
    assert float(seen["kept_steps"]) == (0 if path == "full" else 1)
    assert float(seen["dropped"]) == 0
    # two grouped products forward and two backward where the pass keeps
    # its up product, no gate product anywhere; under the conditional the
    # text holds both branches of both directions, and the worst-case
    # backward branch rebuilds the one up product (2 + 2 forward, 2 + 1 + 2
    # backward)
    text = str(jax.make_jaxpr(jax.grad(lambda a, b: mine(a, b)[0],
                                       argnums=(0, 1)))(x, logits))
    calls = text.count("moe_grouped_fwd"), text.count("moe_grouped_dx")
    assert calls == ((2, 2) if path == "one_path" else (5, 4))


# ------------------------------------------------ attention without rotary ---

def naive_attention(q, k, v):
    """Causal softmax a row, query head a reading key-value head a // rep."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    out = np.zeros((b, s, h, d))
    for bi in range(b):
        for a in range(h):
            for i in range(s):
                sc = q[bi, i, a] @ k[bi, :i + 1, a // rep].T / np.sqrt(d)
                pr = np.exp(sc - sc.max())
                out[bi, i, a] = (pr / pr.sum()) @ v[bi, :i + 1, a // rep]
    return out


def test_attention_without_rotary_is_a_plain_softmax_and_knows_no_position():
    """32/2-like grouped heads (16 query heads a key-value head), no
    position term: the module against a naive per-row softmax over its own
    projections, and unchanged when every position is shifted."""
    lc = LLMConfig(hidden_size=32, num_heads=16, num_kv_heads=1, head_size=4,
                   use_rope=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 12, 32))
    pos = jnp.broadcast_to(jnp.arange(12)[None], (2, 12))
    mod = Attention(lc)
    params = mod.init(jax.random.PRNGKey(1), x, pos)["params"]
    got, _ = mod.apply({"params": params}, x, pos)
    f64 = lambda a: np.asarray(a, np.float64)  # noqa: E731
    q, k, v = (np.einsum("bsh,hnd->bsnd", f64(x), f64(params[n]["kernel"]))
               for n in "qkv")
    want = naive_attention(q, k, v).reshape(2, 12, 64) @ f64(
        params["o"]["kernel"])
    assert rel(got, want) < 1e-5
    shifted, _ = mod.apply({"params": params}, x, pos + 1000)
    assert rel(shifted, got) == 0.0
    turning, _ = Attention(LLMConfig(
        hidden_size=32, num_heads=16, num_kv_heads=1, head_size=4)).apply(
        {"params": params}, x, pos)
    assert rel(turning, got) > 1e-3      # rotary on is another function


# ------------------------------------------------------------- the loader ---

def test_the_loader_reads_the_published_keys():
    cfg = small_cfg()
    lc = system_cfg(cfg, 24)
    assert lc.layers == ("ssm", "moe", "ssm", "full", "moe")
    assert (lc.ssm_heads, lc.ssm_head_dim, lc.ssm_state_size, lc.ssm_groups,
            lc.ssm_conv_kernel, lc.ssm_chunk, lc.ssm_conv_bias) == (
        8, 8, 16, 2, 4, 16, True)
    assert (lc.mlp_activation, lc.moe_latent_size, lc.shared_expert_size,
            lc.moe_intermediate_size) == ("relu2", 16, 40, 24)
    assert (lc.n_routed_experts, lc.held, lc.first_expert,
            lc.num_experts_per_tok, lc.n_shared_experts) == (16, 4, 4, 6, 1)
    assert lc.router_bias and lc.n_group == 1 and lc.norm_topk_prob
    assert lc.routed_scaling_factor == 5.0 and lc.rms_eps == 1e-5
    assert (lc.num_heads, lc.kv_heads, lc.head_dim) == (8, 2, 4)
    assert not lc.use_rope and lc.rotary_dim == 0
    assert not lc.tie_embeddings and not any("+" in k for k in lc.layers)
    silu = system_cfg(dict(cfg, mlp_hidden_act="silu"), 24)
    assert silu.mlp_activation == "swiglu"
    # the published file itself, at its published sizes
    import json
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "nemotron3_super_ep8_l11.json")) as f:
        pub = json.load(f)
    big = system_cfg(pub, 4096, dtype="bfloat16", impl="flash")
    assert (big.hidden_size, big.ssm_heads, big.ssm_head_dim,
            big.ssm_state_size, big.ssm_groups, big.ssm_conv_kernel,
            big.ssm_chunk) == (4096, 128, 64, 128, 8, 4, 128)
    assert (big.num_heads, big.kv_heads, big.head_dim) == (32, 2, 128)
    assert (big.n_routed_experts, big.held, big.first_expert,
            big.num_experts_per_tok, big.routed_scaling_factor) == (
        512, 64, 192, 22, 5.0)
    assert (big.moe_latent_size, big.moe_intermediate_size,
            big.shared_expert_size, big.mlp_activation) == (
        1024, 2688, 5376, "relu2")
    assert big.layers == ("ssm", "moe") * 4 + ("ssm", "full", "moe")
    assert big.vocab_size == 16384


@pytest.mark.parametrize("over,match", [
    ({"hybrid_override_pattern": "MEM*"}, "hybrid_override_pattern"),
    ({"hybrid_override_pattern": "MEM-E"}, "hybrid_override_pattern"),
    ({"hybrid_override_pattern": "MEMxE"}, "hybrid_override_pattern"),
    ({"mamba_hidden_act": "gelu"}, "mamba_hidden_act"),
    ({"mlp_hidden_act": "gelu"}, "mlp_hidden_act"),
    ({"use_bias": True}, "use_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"mlp_bias": True}, "mlp_bias"),
    ({"attention_bias": True}, "attention_bias"),
    ({"n_groups": 3}, "n_groups"),
    ({"expand": 3}, "expand"),
    ({"num_nextn_predict_layers": 1}, "multi-token"),
])
def test_what_is_not_built_is_refused(over, match):
    with pytest.raises(NotImplementedError, match=match):
        system_cfg(small_cfg(**over), 24)


def test_the_cache_path_of_a_state_space_layer_refuses_clearly():
    lc = system_cfg(small_cfg(), 24)
    x = jnp.zeros((1, 4, 32))
    pos = jnp.zeros((1, 4), jnp.int32)
    mod = Mamba2(lc)
    params = mod.init(jax.random.PRNGKey(0), x, pos)["params"]
    with pytest.raises(NotImplementedError, match="no cache path"):
        mod.apply({"params": params}, x, pos,
                  kv_view=(jnp.zeros((1, 8, 2, 4)),) * 2)


def test_dense_count_refuses_the_new_fields():
    with pytest.raises(NotImplementedError, match="layers"):
        system_cfg(small_cfg(), 24).param_count()
    with pytest.raises(NotImplementedError, match="mlp_activation"):
        LLMConfig(mlp_activation="relu2").param_count()


def test_a_relu2_mlp_has_two_kernels():
    lc = LLMConfig(hidden_size=16, intermediate_size=24,
                   mlp_activation="relu2")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 16))
    params = MLP(lc).init(jax.random.PRNGKey(1), x)["params"]
    assert set(params) == {"up", "down"}
    want = jnp.square(jax.nn.relu(x @ params["up"]["kernel"])) \
        @ params["down"]["kernel"]
    assert rel(MLP(lc).apply({"params": params}, x), want) < 1e-6


def test_round_counters_reach_the_registry_from_the_round_program():
    """A federated LoRA round of the small model through ``TPUSimulator``:
    ``fed_ssm_layer_steps_total`` counts the round program's own passes
    through state-space layers; a model without them names no such
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
    assert "ssm_layer_steps" in bundle.extra_metrics
    assert "kda_layer_steps" not in bundle.extra_metrics
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    sim = TPUSimulator(args, fed, bundle, create_optimizer(args, spec), spec)
    hyper = TrainHyper(learning_rate=jnp.float32(0.05), epochs=1)
    before = REGISTRY.counter("fed_ssm_layer_steps_total").value()
    m0 = sim.run_round(0, hyper)
    # 2 state-space layers x (2 silos x 2 steps)
    assert float(m0["ssm_layer_steps"]) == 8.0
    assert float(m0["moe_layer_steps"]) == 8.0
    float(m0["loss_sum"])
    sim.flush_program_counters()
    assert REGISTRY.counter("fed_ssm_layer_steps_total").value() \
        == before + 8.0
    assert REGISTRY.counter("fed_moe_dropped").value() == 0.0
    plain = LLMConfig()
    assert LLMBundle(CausalLM(plain), plain, None, 0, 1.0).extra_metrics == ()
