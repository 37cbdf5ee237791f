"""Layers of two attention kinds (Kimi delta attention 5:1 with gated latent
attention), group-limited bias-corrected routing and the chunked delta rule,
against the plain reference (``benchmarks/reference/ling3flash_ep8_l7.py``:
float32, the recurrence token by token, imports nothing of ``fedml_tpu``) at
small widths that keep the published model's ratios: a whole period of six
expert layers after one dense layer, 8 router groups of which 4 stay, a rank
that holds exactly one group."""

from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedml_tpu.core.obs import REGISTRY
from fedml_tpu.llm import linear_attention as la
from fedml_tpu.llm import moe
from fedml_tpu.llm.federated import LLMBundle, llm_config_from_hf
from fedml_tpu.llm.lora import lora_init
from fedml_tpu.llm.model import CausalLM
from fedml_tpu.llm.trainer import CausalLMTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    path = os.path.join(REPO, "benchmarks", "reference",
                        "ling3flash_ep8_l7.py")
    spec = importlib.util.spec_from_file_location("ref_ling3", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def small_cfg(held=4, first=12, experts=32, layers=7, **over):
    cfg = {
        "vocab_size": 96, "hidden_size": 48, "intermediate_size": 80,
        "num_hidden_layers": layers, "num_attention_heads": 4,
        "num_key_value_heads": 4, "head_dim": 16, "q_lora_rank": None,
        "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "layer_group_size": 6, "short_conv_kernel_size": 4,
        "kda_lower_bound": -5, "kda_safe_gate": True, "linear_silu": True,
        "no_kda_lora": True,
        "gated_attention_proj_granularity_type": "head_wise",
        "num_experts": held, "published": {"num_experts": experts},
        "first_expert": first, "num_experts_per_tok": 4,
        "moe_intermediate_size": 24,
        "moe_shared_expert_intermediate_size": 24, "num_shared_experts": 1,
        "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
        "norm_topk_prob": True, "score_function": "sigmoid",
        "moe_router_enable_expert_bias": True, "topk_method": "noaux_tc",
        "n_group": 8, "topk_group": 4, "router_bias_range": 0.05,
        "kda_decay_proj_scale": 0.1,
        "rms_norm_eps": 1e-6, "rope_theta": 6000000, "rope_scaling": None,
        "tie_word_embeddings": False, "initializer_range": 0.2,
        "lora_rank": 4, "lora_alpha": 8.0, "lora_b_std": 0.05,
        "reference_heads_per_group": 2, "reference_kda_segment": 8,
        "expert_swiglu_limit_list": [0] * 7 + [4] * 3,
        "share_expert_swiglu_limit_list": [0] * 7 + [5] * 3}
    cfg.update(over)
    return cfg


def system_cfg(cfg, seq, dtype="float32", impl="dense"):
    published = dict(cfg, num_experts=cfg["published"]["num_experts"])
    return llm_config_from_hf(
        published, max_seq_len=seq, dtype=dtype, attention_impl=impl,
        first_expert=cfg["first_expert"], experts_held=cfg["num_experts"])


def weights(cfg, seed=0):
    key = jax.random.PRNGKey(seed)
    base = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                  REF.init_frozen(key, cfg))
    return base, REF.init_trainable(jax.random.fold_in(key, 7), cfg)


def tokens(cfg, rows=2, seq=32, seed=3):
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
    """Layer 0 dense with KDA, expert layers 1-4 and 6 KDA, layer 5 latent:
    the system's logits, loss and every adapter leaf's gradient against the
    independent reference, whose KDA is the token recurrence (float32
    ``highest``; the gap is summation order and the chunked algebra)."""
    cfg = small_cfg()
    base, lora = weights(cfg)
    tok = tokens(cfg)
    x, y = tok[:, :-1], tok[:, 1:]
    bundle = bundle_for(cfg, base, 32)
    assert bundle.cfg.layers == ("linear+mlp",) + ("linear+moe",) * 4 + (
        "latent+moe", "linear+moe")
    grad_fn = REF.make_model(cfg)
    batch = {"x": x, "y": y, "mask": jnp.ones((2,))}
    with jax.default_matmul_precision("highest"):
        want_logits = grad_fn.forward(lora, base, x, None)
        want_g, want_ls, want_n = grad_fn(lora, base, batch, None)
        got_logits = bundle.apply(lora, x)
        spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
        (_, aux), got_g = jax.value_and_grad(spec.loss, has_aux=True)(
            lora, batch, None)
    assert rel(got_logits, want_logits) < 5e-5
    assert abs(float(aux["loss_sum"]) - float(want_ls)) < 1e-4 * float(want_ls)
    assert float(aux["count"]) == float(want_n) == 64.0
    assert float(aux["kda_layer_steps"]) == 6.0
    assert float(aux["moe_layer_steps"]) == 6.0
    assert 0 < float(aux["moe_tokens_here"]) <= 6 * 64
    flat_w = jax.tree_util.tree_leaves_with_path(want_g)
    flat_g = dict(jax.tree_util.tree_leaves_with_path(got_g))
    # 6 KDA layers x 5 targets, 1 latent x 4, 1 dense and 6 shared x 3
    assert len(flat_w) == len(flat_g) == 2 * (6 * 5 + 4 + 7 * 3)
    for path, w in flat_w:
        assert float(jnp.abs(w).max()) > 0, path      # no blind leaf
        assert rel(flat_g[path], w) < 5e-4, jax.tree_util.keystr(path)


def test_the_reference_computes_an_overflowing_expert_over_every_token():
    """An expert that drew more tokens than ``reference_expert_rows`` runs
    over all of them: the same logits as with room for everyone."""
    cfg = small_cfg(layers=2)
    base, lora = weights(cfg)
    x = tokens(cfg)[:, :-1]
    with jax.default_matmul_precision("highest"):
        roomy = REF.make_model(dict(cfg, reference_expert_rows=64)).forward(
            lora, base, x, None)
        tight = REF.make_model(dict(cfg, reference_expert_rows=2)).forward(
            lora, base, x, None)
    assert rel(tight, roomy) < 1e-6


def test_adapter_tree_is_the_references():
    cfg = small_cfg()
    base, lora = weights(cfg)
    mine = lora_init(jax.random.PRNGKey(0), base, rank=cfg["lora_rank"])
    assert (jax.tree_util.tree_structure(mine)
            == jax.tree_util.tree_structure(lora))
    for a, b in zip(jax.tree_util.tree_leaves(mine),
                    jax.tree_util.tree_leaves(lora)):
        assert a.shape == b.shape
    assert set(mine["layer_1"]["attn"]) == set("qkvfo")      # KDA
    assert set(mine["layer_5"]["attn"]) == {"q", "kv_a", "kv_b", "o"}
    assert set(mine["layer_1"]["moe"]) == {"shared"}


# ----------------------------------------- the chunked delta rule itself ---

def kda_inputs(s, lo, hi, b=1, h=2, dk=128, dv=128, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + s), 5)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = jax.random.uniform(ks[3], (b, s, h, dk), minval=lo, maxval=hi)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    return q, k, v, g, beta


@pytest.mark.parametrize("impl", ["dense", "flash"])
@pytest.mark.parametrize("s,lo,hi", [
    (64, -5.0, 0.0),        # one chunk, decays all over the range
    (128, -5.0, -4.9),      # two chunks, every channel at the fast end
    (200, -0.01, 0.0),      # padded to four chunks, hardly any decay
    (48, -5.0, 0.0),        # a short row: one chunk of three sub-chunks
])
def test_chunked_kda_matches_the_recurrence(impl, s, lo, hi):
    """Both chunked forms (``jax.numpy`` under a scan; the Pallas kernels,
    interpreted here) against the token recurrence: outputs and the
    gradients toward q, k, v, the log-decay and beta."""
    args = kda_inputs(s, lo, hi)
    want = la.kda_recurrence(*args)
    w = jax.random.normal(jax.random.PRNGKey(7), want.shape)
    grads = lambda f: jax.grad(  # noqa: E731
        lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2, 3, 4))(*args)
    want_g = grads(la.kda_recurrence)
    got = la.kda_attention(*args, impl=impl)
    assert rel(got, want) < 1e-5
    for name, a, b in zip("q k v g beta".split(), grads(
            lambda *a: la.kda_attention(*a, impl=impl)), want_g):
        # a gradient that all but cancels (the decay's, where every
        # channel forgets within a token) is held to the largest one
        scale = max(float(jnp.linalg.norm(b)),
                    1e-2 * max(float(jnp.linalg.norm(x)) for x in want_g))
        assert float(jnp.linalg.norm(a - b)) / scale < 5e-5, name


def test_kda_in_bfloat16_stays_near_the_recurrence():
    """bfloat16 operands, as the timed path has them: the products round
    their operands, the state and the log-decays stay float32."""
    q, k, v, g, beta = kda_inputs(128, -5.0, 0.0)
    want = la.kda_recurrence(q, k, v, g, beta)
    bf = lambda a: a.astype(jnp.bfloat16)  # noqa: E731
    got = la.kda_attention(bf(q), bf(k), bf(v), g, beta, impl="flash")
    assert got.dtype == jnp.bfloat16
    assert rel(got.astype(jnp.float32), want) < 2e-2


def solve(a, rhs):
    """The chunk's solve as the forward kernel runs it: the inverse by
    doubling, then ``u`` from it."""
    return la._unit_lower_solve(a, la._unit_lower_inverse(a), rhs)


def solve_inputs(c, d=128):
    ks = jax.random.split(jax.random.PRNGKey(c), 3)
    a = 0.3 * jax.random.normal(ks[0], (c, c))
    return (jnp.tril(a, -1), jax.random.normal(ks[1], (c, d)),
            jax.random.normal(ks[2], (c, d)))


@pytest.mark.parametrize("c", [16, 32, 48, 64])
def test_the_chunks_solve_matches_solve_triangular(c):
    """``(I + a) u = rhs`` by doubling, and its pull-back (``custom_vjp``
    over the solve: no derivative of an inverse), against
    ``jax.scipy.linalg.solve_triangular`` and its autodiff: the value and
    both gradients, at every chunk size a row can have."""
    a, rhs, ct = solve_inputs(c)

    def plain(a, rhs):
        return jax.scipy.linalg.solve_triangular(
            jnp.eye(c) + jnp.tril(a, -1), rhs, lower=True,
            unit_diagonal=True)

    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(plain, a, rhs)
        want_a, want_rhs = pull(ct)
    got, pull = jax.vjp(solve, a, rhs)
    got_a, got_rhs = pull(ct)
    assert rel(got, want) < 2e-6
    assert rel(got_rhs, want_rhs) < 2e-6
    assert rel(got_a, want_a) < 2e-6


@pytest.mark.parametrize("c", [16, 32, 48, 64])
def test_the_solves_gradient_toward_a_is_strictly_lower(c):
    """Only ``a``'s strict lower triangle is free: the pull-back leaves the
    diagonal and everything above it at exactly zero, whatever comes in."""
    a, rhs, ct = solve_inputs(c)
    got_a = jax.vjp(solve, a, rhs)[1](ct)[0]
    assert float(jnp.abs(jnp.triu(got_a)).max()) == 0.0
    assert float(jnp.abs(got_a)[jnp.tril_indices(c, -1)].min()) > 0.0


def full_precision_products(jaxpr):
    """``dot_general``s at ``Precision.HIGHEST`` in a jaxpr and in every
    jaxpr its equations carry."""
    n = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            prec = eqn.params["precision"]
            prec = prec if isinstance(prec, tuple) else (prec,)
            n += jax.lax.Precision.HIGHEST in prec
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += full_precision_products(sub)
    return n


def test_the_chunk_step_makes_seven_full_precision_products():
    """At C = 64 in bfloat16 the solve is the step's only full-precision
    work: six doublings and ``u`` forward; in the backward kernel's body
    (the step pulled back from the inverse the forward kept) ``u`` and the
    pull-back's two, no doubling. A full-precision product is six MXU
    passes and a link of the step's dependent chain: one more has to show
    here."""
    c, d = 64, 128
    x = jnp.zeros((c, d), jnp.bfloat16)
    gc = jnp.zeros((c, d), jnp.float32)
    st = jnp.zeros((d, d), jnp.float32)
    cc = jnp.zeros((c, c), jnp.float32)
    fwd = jax.make_jaxpr(functools.partial(la._chunk, dtype=jnp.bfloat16))(
        x, x, x, x, gc, st)
    assert full_precision_products(fwd.jaxpr) == 7
    bwd = jax.make_jaxpr(
        functools.partial(la._chunk_grads, dtype=jnp.bfloat16))(
            x, x, x, x, gc, st, cc, cc.astype(jnp.bfloat16), x, st)
    assert full_precision_products(bwd.jaxpr) == 3


# ------------------- the backward pass reads what the forward pass kept ---

def chunk_operands(s, lo, dtype, masked, h=2, d=128, seed=0):
    """``_kda_chunks``' operands as ``kda_attention`` makes them (q, k,
    beta k, beta v in ``dtype``; the running log-decay within each chunk,
    float32) from log-decays over ``(lo, 0)``; ``masked``: the last third
    of the row neither writes nor decays, as a mask makes it. -> (operands,
    the chunk, a cotangent of the output)."""
    q, k, v, g, beta = kda_inputs(s, lo, 0.0, h=h, dk=d, dv=d, seed=seed)
    if masked:
        keep = (jnp.arange(s) < 2 * s // 3).astype(jnp.float32)[None, :,
                                                                  None]
        beta, g = beta * keep, g * keep[..., None]
    chunk = la.chunk_size(s)
    gc = jnp.cumsum(g.reshape(1, s // chunk, chunk, h, d), 2).reshape(
        g.shape)
    beta = beta[..., None]
    operands = tuple(a.astype(dtype) for a in (q, k, k * beta, v * beta))
    do = jax.random.normal(jax.random.PRNGKey(seed + 1), v.shape)
    return operands + (gc,), chunk, do.astype(dtype)


def rebuilt_backward(q, k, kb, vb, gc, do, chunk, unbounded):
    """``_kda_chunks``' backward pass as it was before the forward pass
    kept anything: every chunk's whole step made again (``A``, ``P``, the
    inverse's doublings) and pulled back, chunks last to first from the
    entering states."""
    dtype = q.dtype
    states = la._scan_fwd(q, k, kb, vb, gc, chunk, unbounded)[1]

    def one(q, k, kb, vb, gc, st, do, dst):
        args = tuple(a.astype(jnp.float32) for a in (q, k, kb, vb, gc, st))
        _, pull = jax.vjp(lambda *a: la._chunk(*a, dtype, unbounded)[:2],
                          *args)
        return pull((do.astype(jnp.float32), dst))

    step = jax.vmap(jax.vmap(one))

    def body(dst, xs):
        *grads, dst = step(*xs, dst)
        return dst, tuple(grads)

    xs = tuple(la._by_chunk(a, chunk) for a in (q, k, kb, vb, gc, do))
    grads = jax.lax.scan(body, jnp.zeros_like(states[0]),
                         xs[:5] + (states, xs[5]), reverse=True)[1]
    return tuple(la._from_chunks(g).astype(a.dtype)
                 for g, a in zip(grads, (q, k, kb, vb, gc)))


def check_the_kept_backward(s, lo, dtype, masked, unbounded):
    """``_kda_chunks``' five gradients: the ``dense`` form's equal to the
    rebuilt backward pass's bit for bit (the same operations on the same
    values, made once where they were made twice), the Pallas kernels'
    (interpreted) within float32 rounding of the ``dense`` form's, as they
    were before."""
    operands, chunk, do = chunk_operands(s, lo, dtype, masked)
    want = jax.jit(rebuilt_backward, static_argnums=(6, 7))(
        *operands, do, chunk, unbounded)
    got = {}
    for impl in ("dense", "flash"):
        pull = jax.jit(lambda *a: jax.vjp(  # noqa: B023
            lambda *x: la._kda_chunks(*x, chunk, impl, unbounded),
            *a[:5])[1](a[5]))
        got[impl] = pull(*operands, do)
    f32 = lambda a: np.asarray(a.astype(jnp.float32))  # noqa: E731
    for name, a, b, c in zip("q k kb vb gc".split(), got["dense"], want,
                             got["flash"]):
        assert a.dtype == b.dtype and np.array_equal(f32(a), f32(b)), name
        assert held_to(c, a, want) < 1e-6, name


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("s,masked", [
    (128, False),       # two chunks
    (192, True),        # three chunks, the last third masked
    (48, False),        # a short row: one chunk of three sub-chunks
])
def test_the_backward_reads_what_the_forward_kept_bit_for_bit(s, masked,
                                                              dtype):
    """The bounded gate's form (log-decays over (-5, 0)): the backward
    pass that reads the chunks' inverse and ``P`` from the forward pass
    against the one that made them again."""
    check_the_kept_backward(s, -5.0, dtype, masked, unbounded=False)


def test_the_forward_keeps_each_chunks_inverse_and_scores():
    """What the forward pass keeps is the chunk's own ``(I + A)^-1`` in
    float32 and ``P`` in the compute dtype, in both forms (the kernels'
    blocks put a step's two heads side by side)."""
    operands, chunk, _ = chunk_operands(128, -5.0, jnp.bfloat16, False)
    _, kept = la._kda_chunks_fwd(*operands, chunk, "dense", False)
    inv, p = kept[6:]
    assert inv.shape == p.shape == (2, 1, 2, chunk, chunk)
    assert inv.dtype == jnp.float32 and p.dtype == jnp.bfloat16
    q, k, kb, vb, gc = (la._by_chunk(a, chunk)[1, 0, 1] for a in operands)
    a_mat, p_mat = la._chunk_mats(*(a.astype(jnp.float32)
                                    for a in (q, k, kb, gc)),
                                  jnp.bfloat16, False)
    assert np.array_equal(np.asarray(inv[1, 0, 1]),
                          np.asarray(la._unit_lower_inverse(a_mat)))
    assert np.array_equal(np.asarray(p[1, 0, 1]),
                          np.asarray(p_mat.astype(jnp.bfloat16)))
    _, flat = la._kda_chunks_fwd(*operands, chunk, "flash", False)
    for mine, theirs in zip(flat[6:], kept[6:]):
        assert mine.shape == (1, 1, 2, chunk, 2 * chunk)
        by_head = jnp.stack([mine[0, 0, :, :, :chunk],
                             mine[0, 0, :, :, chunk:]], 1)
        assert held_to(by_head, theirs[:, 0], [theirs]) < 1e-6


def test_a_masked_key_neither_writes_nor_decays():
    cfg = small_cfg(layers=1)
    base, _ = weights(cfg)
    lc = system_cfg(cfg, 32)
    x = tokens(cfg)[:, :-1]
    mask = jnp.ones((2, 32)).at[:, 20:].set(0)
    whole = CausalLM(lc).apply({"params": base}, x, attn_mask=mask)
    short = CausalLM(lc).apply({"params": base}, x[:, :20])
    assert rel(whole[:, :20], short) < 1e-5


# --------------------------------- the fused passes around the kernels ---

def short_conv(x, w):
    """Causal depthwise convolution over the last ``K`` positions, no bias:
    ``y_t = sum_i w[i] * x_{t - (K - 1) + i}``. x [b, s, c], w [K, c]."""
    kk = w.shape[0]
    s = x.shape[1]
    xp = jnp.pad(x, [(0, 0), (kk - 1, 0), (0, 0)])
    return sum(xp[:, i:i + s] * w[i].astype(x.dtype) for i in range(kk))


def _l2_normalised(a):
    f = a.astype(jnp.float32)
    return (f * jax.lax.rsqrt(jnp.sum(f * f, -1, keepdims=True) + 1e-6)
            ).astype(a.dtype)


def module_as_it_was(ys, beta_logits, gate_logits, conv, a_log, dt_bias,
                     o_scale, attn_mask=None, *, heads, lower, eps, impl):
    """``LinearAttention`` between its products as XLA had it before the
    fused passes (``llm/model.py`` at PR 36, line for line): the definition
    :func:`la.kda_layer` is held to, with the same kernels under it."""
    b, s, hd = ys["q"].shape
    d = hd // heads
    by_head = lambda a: a.reshape(b, s, heads, d)  # noqa: E731
    q, k, v = (by_head(jax.nn.silu(short_conv(ys[n], w)))
               for n, w in zip("qkv", conv))
    q, k = (_l2_normalised(a) for a in (q, k))
    g = lower * jax.nn.sigmoid(
        jnp.exp(a_log.astype(jnp.float32))[:, None]
        * by_head(ys["f"].astype(jnp.float32) + dt_bias.astype(jnp.float32)))
    beta = jax.nn.sigmoid(beta_logits.astype(jnp.float32))
    if attn_mask is not None:
        keep = attn_mask.astype(jnp.float32)[:, :, None]
        g, beta = g * keep[..., None], beta * keep
    q = (q.astype(jnp.float32) * d ** -0.5).astype(q.dtype)
    out = la.kda_attention(q, k, v, g, beta, impl=impl)
    var = jnp.mean(jnp.square(out.astype(jnp.float32)), -1, keepdims=True)
    out = (out.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
           * o_scale).astype(out.dtype)
    gate = jax.nn.sigmoid(gate_logits.astype(jnp.float32))[..., None]
    return (out.astype(jnp.float32) * gate).astype(out.dtype).reshape(
        b, s, hd)


LAYER = dict(heads=2, lower=-5.0, eps=1e-6)
LEAVES = ("q", "k", "v", "f", "beta_logits", "gate_logits", "conv_q",
          "conv_k", "conv_v", "A_log", "dt_bias", "o_scale")


def layer_inputs(s, masked, dtype=jnp.float32, h=2, d=128):
    ks = jax.random.split(jax.random.PRNGKey(s), 13)
    hd = h * d
    ys = {n: jax.random.normal(ks[i], (1, s, hd)).astype(
        jnp.float32 if n == "f" else dtype) for i, n in enumerate("qkvf")}
    logits = [jax.random.normal(k, (1, s, h)).astype(dtype) for k in ks[4:6]]
    conv = [0.5 * jax.random.normal(k, (4, hd)) for k in ks[6:9]]
    frozen = (0.3 * jax.random.normal(ks[9], (h,)),
              0.3 * jax.random.normal(ks[10], (hd,)),
              1 + 0.1 * jax.random.normal(ks[11], (d,)))
    mask = jnp.ones((1, s)).at[:, 2 * s // 3:].set(0) if masked else None
    weight = jax.random.normal(ks[12], (1, s, hd))
    return (ys, *logits, conv, *frozen), mask, weight


def layer_value_and_grads(fn, impl, args, mask, weight):
    """-> (output, the gradients of ``sum(output * weight)`` toward every
    array and parameter of the layer, in ``LEAVES``' order)."""
    def loss(*a):
        y = fn(*a, mask, impl=impl, **LAYER)
        return jnp.sum(y.astype(jnp.float32) * weight), y

    (_, y), (d_ys, d_beta, d_gate, d_conv, *d_frozen) = jax.value_and_grad(
        loss, argnums=tuple(range(7)), has_aux=True)(*args)
    return y, [*(d_ys[n] for n in "qkvf"), d_beta, d_gate, *d_conv,
               *d_frozen]


def held_to(a, b, others):
    """``|a - b|`` over the larger of ``|b|`` and a hundredth of the
    largest norm among ``others`` (a gradient that all but cancels)."""
    norm = lambda x: float(jnp.linalg.norm(x.astype(jnp.float32)))  # noqa
    scale = max(norm(b), 1e-2 * max(norm(x) for x in others))
    return norm(a.astype(jnp.float32) - b.astype(jnp.float32)) / scale


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("s", [64, 200, 1024])
@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_the_fused_passes_match_the_modules_jax_numpy(impl, s, masked):
    """One chunk, a padded row and a row of four blocks (the convolution's
    rows and its transpose's cross block edges there), in float32: the
    output and the gradient toward every product, both logits and every
    frozen parameter."""
    args, mask, weight = layer_inputs(s, masked)
    want, want_g = layer_value_and_grads(module_as_it_was, impl, args, mask,
                                         weight)
    got, got_g = layer_value_and_grads(la.kda_layer, impl, args, mask,
                                       weight)
    if impl == "flash" and s == 1024:
        assert la._row_tile(s, 64, 256) == 256
    assert rel(got, want) < 1e-5
    for name, a, b in zip(LEAVES, got_g, want_g):
        assert held_to(a, b, want_g[:4]) < 2e-5, name


@pytest.mark.parametrize("masked", [False, True])
@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_the_fused_passes_in_bfloat16_are_nearer_float32(impl, masked):
    """bfloat16 products, as the timed path has them: the passes round
    nothing the module did not, so their output and gradients lie within
    the module's own distance to the float32 result (about half of it).
    The decay's two parameters take their gradient from the kernels' own
    float32 ``dgc`` in float32 arithmetic in both forms, a few numbers
    each summed over the whole row: the same precision, another draw."""
    args, mask, weight = layer_inputs(256, masked, jnp.bfloat16)
    exact = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), args)
    want, want_g = layer_value_and_grads(module_as_it_was, "dense", exact,
                                         mask, weight)
    was, was_g = layer_value_and_grads(module_as_it_was, impl, args, mask,
                                       weight)
    got, got_g = layer_value_and_grads(la.kda_layer, impl, args, mask,
                                       weight)
    assert got.dtype == jnp.bfloat16
    assert [g.dtype for g in got_g] == [g.dtype for g in was_g]
    assert rel(got.astype(jnp.float32), want) \
        <= rel(was.astype(jnp.float32), want)
    for name, a, b, c in zip(LEAVES, got_g, was_g, want_g):
        room = 2.5 if name in ("A_log", "dt_bias") else 1.0
        assert held_to(a, c, want_g[:4]) \
            <= room * held_to(b, c, want_g[:4]), name


def test_a_kda_layers_train_step_keeps_no_intermediate_of_the_module(capsys):
    """What the backward pass of a one-layer KDA model's LoRA step holds at
    the layer's width ``[rows, s, heads * d]``: the four products' outputs
    (``_kda_pre``'s residuals), the kernels' five operands, the kernels'
    output (``_kda_post``'s) and the float32 copy of the layer's result
    that the output product's adapter reads. The module kept 23 at PR 36:
    the convolutions' and SiLUs' outputs, float32 copies, both norms'.
    Beside them the kernels keep two ``[n, b, h, C, C]`` matrices a chunk
    (its solve's inverse and ``P``), which the backward pass reads."""
    cfg = small_cfg(layers=1)
    base, lora = weights(cfg)
    tok = tokens(cfg)
    bundle = bundle_for(cfg, base, 32)
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    batch = {"x": tok[:, :-1], "y": tok[:, 1:], "mask": jnp.ones((2,))}
    from jax.ad_checkpoint import print_saved_residuals
    print_saved_residuals(lambda p: spec.loss(p, batch, None)[0], lora)
    lines = capsys.readouterr().out.splitlines()
    shaped = lambda *shapes: [  # noqa: E731
        line for line in lines if line.split(" ", 1)[0] in shapes]
    wide = shaped("f32[2,32,64]", "f32[2,32,4,16]")
    by_site = lambda name: sum(name in line for line in wide)  # noqa: E731
    assert len(wide) == 11, "\n".join(wide)
    assert by_site("(_add_lora)") == 4 and by_site("kda_layer") == 6
    # one chunk of 32 positions, 2 rows, 4 heads
    assert len(shaped("f32[1,2,4,32,32]")) == 2, "\n".join(lines)
    # and the passes' own residuals are their arguments, nothing made
    args, mask, _ = layer_inputs(64, False)
    ys, beta_logits, gate_logits, conv, a_log, dt_bias, o_scale = args
    plan = la._Pass(2, 64, 64, -5.0, 1e-6, "dense", False)
    operands = (*(ys[n] for n in "qkvf"), beta_logits, None, tuple(conv),
                a_log, dt_bias)
    out, kept = la._kda_pre_fwd(plan, *operands)
    assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(kept),
                                      jax.tree_util.tree_leaves(operands)))
    o = out[3]
    _, kept = la._kda_post_fwd(plan, o, gate_logits, o_scale)
    assert kept[0] is o and kept[1] is gate_logits and kept[2] is o_scale


# ------------------------------------------------------------- routing ---

def test_plain_routing_is_bit_equal_to_what_it_was():
    """Without a bias and a group limit ``route`` is the program it was:
    sigmoid, top-k, normalise, scale."""
    logits = jax.random.normal(jax.random.PRNGKey(2), (64, 32)) * 2
    gates, chosen = moe.route(logits, 4, 2.5)
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    vals, idx = jax.lax.top_k(s, 4)
    want = vals / (jnp.sum(vals, -1, keepdims=True) + 1e-20) * 2.5
    assert np.array_equal(np.asarray(gates), np.asarray(want))
    assert np.array_equal(np.asarray(chosen), np.asarray(idx))
    again, same = moe.route(logits, 4, 2.5, True, None, 1, 1)
    assert np.array_equal(np.asarray(again), np.asarray(gates))
    assert np.array_equal(np.asarray(same), np.asarray(chosen))


def test_group_limited_routing_by_hand():
    """8 groups of 4: a token keeps the 4 groups whose two best ``s + b``
    sum highest and takes its top-4 among them by ``s + b``; the weights
    come from ``s`` alone. Checked against a per-token loop in numpy and
    against the reference's ``route``."""
    key = jax.random.PRNGKey(4)
    logits = jax.random.normal(key, (48, 32)) * 2
    bias = jax.random.uniform(jax.random.fold_in(key, 1), (32,),
                              minval=-0.3, maxval=0.3)
    gates, chosen = moe.route(logits, 4, 2.5, True, bias, 8, 4)
    s = np.asarray(jax.nn.sigmoid(logits), np.float64)
    c = s + np.asarray(bias, np.float64)
    changed = 0
    for t in range(48):
        groups = c[t].reshape(8, 4)
        score = np.sort(groups, -1)[:, -2:].sum(-1)
        keep = np.argsort(-score)[:4]
        allowed = np.full(32, -np.inf)
        for grp in keep:
            allowed[grp * 4:(grp + 1) * 4] = c[t, grp * 4:(grp + 1) * 4]
        want = np.argsort(-allowed)[:4]
        assert sorted(want.tolist()) == sorted(np.asarray(chosen[t]).tolist())
        w = s[t, np.asarray(chosen[t])]
        np.testing.assert_allclose(np.asarray(gates[t]),
                                   2.5 * w / w.sum(), rtol=1e-5)
        changed += sorted(want.tolist()) != sorted(
            np.argsort(-s[t])[:4].tolist())
    assert changed > 10     # the limit and the bias do change choices
    ref_gates, ref_idx = REF.route(
        logits, bias, {"num_experts_per_tok": 4, "topk_method": "noaux_tc",
                       "n_group": 8, "topk_group": 4,
                       "routed_scaling_factor": 2.5})
    assert np.array_equal(np.asarray(ref_idx), np.asarray(chosen))
    np.testing.assert_allclose(np.asarray(ref_gates), np.asarray(gates),
                               rtol=1e-6)


def test_shares_add_up_to_the_uncut_layer_under_a_group_limit():
    """The guide's share test for this model: the parts the 8 ranks give for
    one expert layer (each holds one router group), the shared expert
    counted once, add up to the uncut layer."""
    experts, per_rank = 32, 4
    whole = small_cfg(held=experts, first=0, layers=2)
    base, lora = weights(whole)
    x = tokens(whole)[:, :-1]

    def layer_out(cfg, b):
        mod = CausalLM(system_cfg(cfg, 32))
        _, state = mod.apply({"params": b}, x, adapters=lora,
                             lora_scale=2.0, capture_intermediates=(
                                 lambda m, _: m.name == "layer_1"),
                             mutable=["intermediates", "moe_stats",
                                      "kda_stats"])
        return state["intermediates"]["layer_1"]["__call__"][0][0]

    def held(b, lo, hi):
        b = dict(b)
        m = dict(b["layer_1"]["moe"])
        for k in ("experts_gate", "experts_up", "experts_down"):
            m[k] = m[k][lo:hi] if hi > lo else jnp.zeros_like(m[k][:1])
        b["layer_1"] = dict(b["layer_1"], moe=m)
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


# ---------------------------------------------------- counters and refusals ---

def test_round_counters_reach_the_registry_from_the_round_program():
    """A federated LoRA round of the small model through ``TPUSimulator``:
    ``fed_kda_layer_steps_total`` and ``fed_moe_tokens_here_total`` come
    from the round's own metrics, flushed by the caller who has read the
    round's loss; the layer's chunk, the residuals its forward keeps and
    the fused passes it runs."""
    import fedml_tpu
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.types import ClientData, TrainHyper
    from fedml_tpu.data.containers import FederatedDataset
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    cfg = small_cfg()
    base, lora = weights(cfg)
    args = fedml_tpu.init(Arguments(
        backend="tpu", precision="float32", client_num_in_total=2,
        client_num_per_round=2, batch_size=1, epochs=1, learning_rate=0.05,
        client_optimizer="sgd", federated_optimizer="FedAvg",
        comm_round=100, frequency_of_the_test=0, random_seed=3,
        dataset="llm", model="causal_lm", llm_max_seq_len=32,
        lora_rank=cfg["lora_rank"], lora_alpha=cfg["lora_alpha"]))
    tok = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (2, 2, 1, 33),
                                        0, cfg["vocab_size"]), np.int32)
    train = ClientData(x=jnp.asarray(tok[..., :-1]),
                       y=jnp.asarray(tok[..., 1:]),
                       mask=jnp.ones((2, 2, 1), jnp.float32),
                       num_samples=jnp.asarray([2.0, 2.0]))
    fed = FederatedDataset(
        train=train, test={"x": train.x[0, :1], "y": train.y[0, :1],
                           "mask": train.mask[0, :1]},
        num_classes=cfg["vocab_size"], input_shape=(32,), num_clients=2,
        client_num_samples=np.asarray([2, 2]), task="llm",
        provenance="synthetic")
    bundle = bundle_for(cfg, base, 32)
    assert bundle.extra_metrics[-2:] == ("moe_tokens_here", "kda_layer_steps")
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    sim = TPUSimulator(args, fed, bundle, create_optimizer(args, spec), spec)
    sim.params = jax.device_put(lora, sim.repl_sharding)
    hyper = TrainHyper(learning_rate=jnp.float32(0.05), epochs=1)
    kda_before = REGISTRY.counter("fed_kda_layer_steps_total").value()
    here_before = REGISTRY.counter("fed_moe_tokens_here_total").value()
    m0 = sim.run_round(0, hyper)
    # 6 KDA layers and 6 expert layers x (2 silos x 2 steps)
    assert float(m0["kda_layer_steps"]) == 24.0
    assert float(m0["moe_layer_steps"]) == 24.0
    assert 0 < float(m0["moe_tokens_here"]) <= 24 * 32
    assert float(m0["moe_tokens_here"]) <= float(m0["moe_slots_held"])
    float(m0["loss_sum"])
    sim.flush_program_counters()
    assert REGISTRY.counter("fed_kda_layer_steps_total").value() == \
        kda_before + 24
    assert REGISTRY.counter("fed_moe_tokens_here_total").value() == \
        here_before + float(m0["moe_tokens_here"])
    assert la.chunk_size(32) == 32
    # the backward pass reads each chunk's inverse and P from the forward:
    # a row of 32 at 4 heads, [32, 32] twice in float32
    row = jax.ShapeDtypeStruct((1, 32, 4, 16), jnp.float32)
    assert kept_bytes(row, 32) == 4 * 32 * 32 * 8
    assert kept_bytes(jax.ShapeDtypeStruct((1, 64, 2, 128), jnp.float32),
                      64) == 2 * 64 * 64 * 8
    # the layer's element-wise work runs through the fused passes; a caller
    # that hands the kernels their operands itself runs none of them
    (ys, beta, gates, conv, *frozen), _, _ = layer_inputs(64, False)
    fused = jax.jit(lambda ys, b, g: la.kda_layer(
        ys, b, g, conv, *frozen, heads=2, lower=-5.0, eps=1e-6,
        impl="flash")).lower(ys, beta, gates).as_text(debug_info=True)
    plain = jax.jit(functools.partial(la.kda_attention, impl="flash")).lower(
        *kda_inputs(64, -1.0, 0.0)).as_text(debug_info=True)
    for name in ("kda_pre_fwd", "kda_post_fwd"):
        assert name in fused and name not in plain
    assert not any(name in plain for name in la.KDA_PASS_NAMES)
    assert la.chunk_size(4096) == 64 and la.chunk_size(40) == 48


def kept_bytes(row, chunk):
    """Bytes of the residuals the chunked forward keeps of every chunk's
    ``[C, C]`` matrices (its inverse and P) for the backward pass, of a row
    of q, k, v and log-decays shaped ``row``."""
    _, res = jax.eval_shape(functools.partial(
        la._kda_chunks_fwd, chunk=chunk, impl="dense", unbounded=False),
        row, row, row, row, row)
    mats = [a for a in res if a.shape[-2:] == (chunk, chunk)]
    assert [a.dtype for a in mats] == [jnp.float32, row.dtype]
    return sum(a.size * a.dtype.itemsize for a in mats)


def test_the_cache_path_of_linear_attention_refuses_clearly():
    cfg = small_cfg(layers=1)
    base, _ = weights(cfg)
    lc = system_cfg(cfg, 32)
    x = tokens(cfg)[:, :-1]
    view = [(jnp.zeros((2, 32, 4, 16)), jnp.zeros((2, 32, 4, 16)))]
    with pytest.raises(NotImplementedError, match="linear attention"):
        CausalLM(lc).apply({"params": base}, x, kv_view=view,
                           positions=jnp.broadcast_to(jnp.arange(32), (2, 32)))


@pytest.mark.parametrize("over,match", [
    ({"expert_swiglu_limit_list": [0] * 6 + [4]}, "swiglu_limit"),
    ({"share_expert_swiglu_limit_list": [5] * 7}, "swiglu_limit"),
    ({"num_nextn_predict_layers": 1}, "multi-token"),
    ({"kda_safe_gate": False}, "bounded gate"),
    ({"topk_method": "group_limited_greedy"}, "topk_method"),
    ({"kda_lower_bound": -8}, "kda_lower_bound"),
    ({"kda_lower_bound": 0}, "kda_lower_bound"),
    ({"short_conv_kernel_size": 2}, "short_conv_kernel_size"),
])
def test_what_is_not_built_is_refused(over, match):
    with pytest.raises(NotImplementedError, match=match):
        system_cfg(small_cfg(**over), 32)


def test_a_limit_list_that_is_zero_in_the_layers_held_is_carried():
    lc = system_cfg(small_cfg(), 32)      # nonzero from layer 7 on: not held
    assert lc.num_layers == 7 and lc.n_group == 8 and lc.router_bias
    assert lc.linear_head_dim == 16 and lc.attn_output_gate
    assert lc.q_lora_rank == 0 and lc.n_shared_experts == 1
