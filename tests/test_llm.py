"""LLM pillar tests: model, attention variants, LoRA, sharding, federated
LoRA parity (VERDICT round-1 item 2; reference ``train/llm/`` +
``spotlight_prj/unitedllm/``)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from fedml_tpu.arguments import Arguments
from fedml_tpu.llm import (
    CausalLM, LLMBundle, LLMConfig, init_llm, lora_init, lora_merge,
    lora_param_count, CausalLMTrainer, build_llm, run_federated_llm,
)
from fedml_tpu.llm.attention import (
    dense_causal_attention, flash_causal_attention, ring_causal_attention,
    ring_axis,
)

# the LoRA tests below run in tier-1 (small_lm, float32, a few seconds);
# everything else in this file is the full gate's
slow = pytest.mark.slow

CFG = LLMConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                num_layers=2, num_heads=4, max_seq_len=32)


@pytest.fixture(scope="module")
def small_lm():
    return init_llm(CFG, jax.random.PRNGKey(0))


@slow
def test_forward_shape_and_causality(small_lm):
    model, params = small_lm
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    logits = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 16, 64)
    assert logits.dtype == jnp.float32
    # causality: changing a future token must not affect earlier logits
    tokens2 = tokens.at[:, 10].set((tokens[:, 10] + 1) % 64)
    logits2 = model.apply({"params": params}, tokens2)
    np.testing.assert_allclose(logits[:, :10], logits2[:, :10], atol=1e-5)
    assert not np.allclose(logits[:, 10:], logits2[:, 10:])


@slow
def test_flash_matches_dense():
    rng = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (2, 16, 2, 8))
               for i in range(3))
    dense = dense_causal_attention(q, k, v)
    flash = flash_causal_attention(q, k, v, 8, 8)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(flash),
                               atol=1e-5)
    # backward is the Pallas dQ/dKdV kernel pair — parity for ALL inputs
    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * jnp.cos(
            jnp.arange(q.shape[-1], dtype=jnp.float32))).sum()
    gd = jax.grad(loss(dense_causal_attention), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(lambda q, k, v: flash_causal_attention(q, k, v, 8, 8)),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gd, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@slow
def test_flash_key_padding_mask():
    """Flash supports key-padding masks in both directions; masked keys get
    zero probability (fwd parity vs dense) and zero dK/dV rows."""
    rng = jax.random.PRNGKey(1)
    b, s, h, d = 2, 32, 2, 8
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (b, s, h, d))
               for i in range(3))
    mask = (jax.random.uniform(rng, (b, s)) > 0.3).astype(jnp.float32)
    mask = mask.at[:, 0].set(1.0)  # row 0 live so no query sees zero keys
    dense = dense_causal_attention(q, k, v, attn_mask=mask)
    flash = flash_causal_attention(q, k, v, 8, 8, attn_mask=mask)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(flash),
                               atol=1e-5)
    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()
    gd = jax.grad(loss(lambda q, k, v: dense_causal_attention(
        q, k, v, attn_mask=mask)), argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(loss(lambda q, k, v: flash_causal_attention(
        q, k, v, 8, 8, attn_mask=mask)), argnums=(0, 1, 2))(q, k, v)
    for a, bb in zip(gd, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb), atol=1e-4)
    # masked keys contribute nothing: their dK/dV rows are exactly zero
    dk, dv = np.asarray(gf[1]), np.asarray(gf[2])
    dead = np.asarray(mask) == 0
    assert np.all(dk[dead] == 0) and np.all(dv[dead] == 0)


@slow
def test_flash_all_masked_row_is_zero():
    """A query row whose every visible key is masked (mid-sequence key
    mask covering its own diagonal) must output exactly zero — not an
    unmasked average of V (ADVICE r3: exp(NEG_INF - NEG_INF) = 1)."""
    rng = jax.random.PRNGKey(2)
    b, s, h, d = 1, 16, 1, 8
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (b, s, h, d))
               for i in range(3))
    mask = jnp.ones((b, s), jnp.float32).at[:, :4].set(0.0)
    out = flash_causal_attention(q, k, v, 8, 8, attn_mask=mask)
    # queries 0..3 see only keys 0..q (all masked) -> exact zeros
    assert np.all(np.asarray(out)[:, :4] == 0.0)
    # live rows still match dense
    dense = dense_causal_attention(q, k, v, attn_mask=mask)
    np.testing.assert_allclose(np.asarray(dense)[:, 4:],
                               np.asarray(out)[:, 4:], atol=1e-5)
    # same contract for ring attention (mask rotates with K/V)
    from jax.sharding import PartitionSpec as P
    from fedml_tpu.core.mesh import build_mesh
    mesh = build_mesh({"sp": 4}, devices=jax.devices()[:4])
    ring = jax.shard_map(
        lambda q, k, v, m: ring_causal_attention(q, k, v, "sp", 4,
                                                 attn_mask=m),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3 + (P(None, "sp"),),
        out_specs=P(None, "sp"), check_vma=False)(q, k, v, mask)
    assert np.all(np.asarray(ring)[:, :4] == 0.0)
    np.testing.assert_allclose(np.asarray(dense)[:, 4:],
                               np.asarray(ring)[:, 4:], atol=1e-5)


@slow
def test_nonaligned_seq_len_pads_to_lane_multiple():
    """s=100 (not a multiple of 128) must be handled by pad+slice, matching
    dense exactly on the real rows (ADVICE r3: 125-row blocks are not
    lane-aligned on hardware)."""
    rng = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (2, 100, 2, 8))
               for i in range(3))
    dense = dense_causal_attention(q, k, v)
    flash = flash_causal_attention(q, k, v)
    assert flash.shape == dense.shape
    np.testing.assert_allclose(np.asarray(dense), np.asarray(flash),
                               atol=1e-5)


@slow
def test_ring_matches_dense_multidevice():
    from jax.sharding import PartitionSpec as P
    from fedml_tpu.core.mesh import build_mesh

    mesh = build_mesh({"sp": 4}, devices=jax.devices()[:4])
    rng = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (2, 32, 2, 8))
               for i in range(3))
    dense = dense_causal_attention(q, k, v)

    ring = jax.shard_map(
        lambda q, k, v: ring_causal_attention(q, k, v, "sp", 4),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3,
        out_specs=P(None, "sp"), check_vma=False)(q, k, v)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                               atol=1e-5)


@slow
def test_ring_gradients_match_dense():
    """Ring attention must be TRAINABLE: gradients through the ppermute
    accumulation (sequence-parallel backward) match the dense single-
    device gradients — the property a long-context fine-tune relies on."""
    from jax.sharding import PartitionSpec as P
    from fedml_tpu.core.mesh import build_mesh

    mesh = build_mesh({"sp": 4}, devices=jax.devices()[:4])
    rng = jax.random.PRNGKey(5)
    q, k, v = (jax.random.normal(jax.random.fold_in(rng, i), (2, 32, 2, 8))
               for i in range(3))
    mask = (jax.random.uniform(rng, (2, 32)) > 0.25).astype(jnp.float32)
    mask = mask.at[:, 0].set(1.0)

    def loss_dense(q, k, v):
        out = dense_causal_attention(q, k, v, attn_mask=mask)
        return (out.astype(jnp.float32) ** 2).sum()

    ring_fn = jax.shard_map(
        lambda q, k, v, m: ring_causal_attention(q, k, v, "sp", 4,
                                                 attn_mask=m),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3 + (P(None, "sp"),),
        out_specs=P(None, "sp"), check_vma=False)

    def loss_ring(q, k, v):
        return (ring_fn(q, k, v, mask).astype(jnp.float32) ** 2).sum()

    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gd, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4)


@slow
def test_ring_bwd_residuals_stay_linear_in_s():
    """Training-memory contract for ring attention (VERDICT r4 item 3),
    mirroring test_chip_compile's flash memory contract: the fold is
    rematerialized, so the backward must NOT stack the per-step
    [s_loc, s_loc] probability block across the axis_size ring steps —
    compiled temp memory stays well under the full [s, s] score matrix
    (the un-remat'd form measures ~3x over this bound at s=4096 and the
    gap grows with s)."""
    from jax.sharding import PartitionSpec as P
    from fedml_tpu.core.mesh import build_mesh

    s, d, sp = 4096, 64, 4
    mesh = build_mesh({"sp": sp}, devices=jax.devices()[:sp])
    q = jnp.zeros((1, s, 1, d), jnp.float32)

    def loss(q, k, v):
        out = jax.shard_map(
            lambda a, b, c: ring_causal_attention(a, b, c, "sp", sp),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)(q, k, v)
        return out.sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).compile()
    mem = compiled.memory_analysis()
    if mem is None:
        pytest.skip("backend reports no memory analysis")
    scores_bytes = s * s * 4
    assert mem.temp_size_in_bytes < scores_bytes // 2, (
        f"ring bwd temp {mem.temp_size_in_bytes} vs full scores "
        f"{scores_bytes} — remat contract broken")
    # more shards -> smaller per-device block -> less temp memory: the
    # property that lets context scale with chip count
    mesh8 = build_mesh({"sp": 8}, devices=jax.devices()[:8])

    def loss8(q, k, v):
        out = jax.shard_map(
            lambda a, b, c: ring_causal_attention(a, b, c, "sp", 8),
            mesh=mesh8, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)(q, k, v)
        return out.sum()

    mem8 = jax.jit(jax.grad(loss8, argnums=(0, 1, 2))).lower(
        q, q, q).compile().memory_analysis()
    assert mem8.temp_size_in_bytes < mem.temp_size_in_bytes


@slow
def test_ring_forward_full_model():
    """Sequence-parallel forward of the whole decoder matches the dense
    single-device forward (global RoPE positions + causal mask)."""
    from fedml_tpu.core.mesh import build_mesh
    from fedml_tpu.llm.sharding import make_ring_forward

    cfg_ring = LLMConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                         num_layers=2, num_heads=4, max_seq_len=32,
                         attention_impl="ring")
    model_ring = CausalLM(cfg_ring)
    model_dense, params = init_llm(CFG, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 64)
    want = model_dense.apply({"params": params}, tokens)

    mesh = build_mesh({"sp": 4}, devices=jax.devices()[:4])
    fwd = make_ring_forward(
        lambda p, t, m: model_ring.apply({"params": p}, t, attn_mask=m),
        mesh)
    got = fwd(params, tokens)
    np.testing.assert_allclose(np.asarray(want), np.asarray(got), atol=2e-4)

    # key-padding: last 8 tokens of row 0 are pad. Ring must agree with the
    # dense forward on the real positions (padded-row logits are garbage in
    # both and excluded).
    mask = np.ones((2, 32), np.int32)
    mask[0, 24:] = 0
    want_m = model_dense.apply({"params": params}, tokens,
                               attn_mask=jnp.asarray(mask))
    got_m = fwd(params, tokens, jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(want_m)[mask.astype(bool)],
                               np.asarray(got_m)[mask.astype(bool)],
                               atol=2e-4)


def test_lora_zero_init_and_delta(small_lm):
    model, params = small_lm
    lora = lora_init(jax.random.PRNGKey(2), params, rank=4)
    assert lora_param_count(lora) > 0
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    base_out = model.apply({"params": params}, tokens)
    merged = lora_merge(params, lora)
    merged_out = model.apply({"params": merged}, tokens)
    np.testing.assert_allclose(np.asarray(base_out), np.asarray(merged_out),
                               atol=1e-6)  # b=0 → zero effect
    # non-zero b changes the output
    bumped = jax.tree_util.tree_map(lambda a: a + 0.1, lora)
    out2 = model.apply({"params": lora_merge(params, bumped)}, tokens)
    assert not np.allclose(np.asarray(base_out), np.asarray(out2))


def _lora_bundle(small_lm, rank):
    model, params = small_lm
    return LLMBundle(model, CFG, params, lora_rank=rank, lora_alpha=16.0)


def test_lora_training_reduces_loss(small_lm):
    bundle = _lora_bundle(small_lm, 4)
    spec = CausalLMTrainer(bundle.apply)
    x = jax.random.randint(jax.random.PRNGKey(3), (4, 16), 4, 64)
    lora = bundle.init(jax.random.PRNGKey(2), x)
    batch = {"x": x, "y": x, "mask": jnp.ones(4)}

    import optax
    opt = optax.adam(1e-2)
    state = opt.init(lora)
    loss0 = None

    @jax.jit
    def step(lora, state):
        (loss, _), g = jax.value_and_grad(spec.loss, has_aux=True)(
            lora, batch, jax.random.PRNGKey(0))
        up, state = opt.update(g, state, lora)
        return optax.apply_updates(lora, up), state, loss

    for i in range(20):
        lora, state, loss = step(lora, state)
        if loss0 is None:
            loss0 = float(loss)
    assert float(loss) < loss0 * 0.9


def _lora_losses(small_lm, rank=2):
    """The LoRA loss twice over one batch: through ``LLMBundle.apply`` (the
    factored side path training runs) and through ``lora_merge`` (the plain
    statement of the same mathematics)."""
    model, params = small_lm
    bundle = _lora_bundle(small_lm, rank)
    x = jax.random.randint(jax.random.PRNGKey(3), (2, 8), 4, 64)
    batch = {"x": x, "y": x, "mask": jnp.ones(2)}
    rng = jax.random.PRNGKey(0)
    factored = CausalLMTrainer(bundle.apply)
    merged = CausalLMTrainer(
        lambda lora, x, rng=None, train=False: model.apply(
            {"params": lora_merge(params, lora, bundle.lora_alpha)}, x))
    return (bundle, lambda lora: factored.loss(lora, batch, rng)[0],
            lambda lora: merged.loss(lora, batch, rng)[0])


@pytest.mark.parametrize("b_std", [0.0, 0.05])
def test_lora_factored_matches_merged(small_lm, b_std):
    bundle, factored, merged = _lora_losses(small_lm)
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        bundle.init(jax.random.PRNGKey(2), None))
    assert len(leaves) == 2 * 7 * CFG.num_layers
    lora = treedef.unflatten([
        leaf if path[-1].key == "lora_a" else b_std * jax.random.normal(
            jax.random.fold_in(jax.random.PRNGKey(4), i), leaf.shape)
        for i, (path, leaf) in enumerate(leaves)])
    lf, gf = jax.value_and_grad(factored)(lora)
    lm, gm = jax.value_and_grad(merged)(lora)
    np.testing.assert_allclose(float(lf), float(lm), atol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(gf),
                            jax.tree_util.tree_leaves(gm)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5,
                                   err_msg=jax.tree_util.keystr(path))
    # at b = 0 every lora_a has a zero gradient and lora_b a live one
    live = {jax.tree_util.keystr(p) for p, g in
            jax.tree_util.tree_leaves_with_path(gf) if np.abs(g).max() > 0}
    assert any("lora_b" in k for k in live)
    assert any("lora_a" in k for k in live) == (b_std > 0)


def _dot_result_shapes(jaxpr):
    """Result shapes of every ``dot_general`` in a jaxpr, sub-jaxprs
    (pjit, custom_vjp, remat, scan) included."""
    shapes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shapes += [tuple(v.aval.shape) for v in eqn.outvars]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            shapes += _dot_result_shapes(sub)
    return shapes


def test_lora_grad_takes_no_frozen_weight_gradient(small_lm):
    """The backward pass of the LoRA loss may produce no matrix of a frozen
    kernel's shape: that is the full weight gradient LoRA exists to avoid.
    The merged formulation does produce them, which checks the detector."""
    bundle, factored, merged = _lora_losses(small_lm)
    lora = bundle.init(jax.random.PRNGKey(2), None)
    frozen = set()
    for leaf in jax.tree_util.tree_leaves(bundle.base_params):
        if leaf.ndim >= 2:
            two_d = (leaf.shape[0], int(np.prod(leaf.shape[1:])))
            frozen |= {tuple(leaf.shape), two_d, two_d[::-1]}

    def weight_shaped(loss):
        dots = _dot_result_shapes(jax.make_jaxpr(jax.grad(loss))(lora).jaxpr)
        assert dots
        return [s for s in dots if s in frozen]

    assert weight_shaped(factored) == []
    assert len(weight_shaped(merged)) >= 7 * CFG.num_layers


@slow
def test_fsdp_tp_sharded_step():
    """Train step jitted over a fsdp×tensor mesh compiles, executes, and
    matches the unsharded step numerically."""
    from fedml_tpu.core.mesh import build_mesh
    from fedml_tpu.llm.sharding import (
        llm_param_specs, make_sharded_train_step, shard_llm_params)
    import optax

    cfg = LLMConfig(vocab_size=64, hidden_size=32, intermediate_size=64,
                    num_layers=2, num_heads=4, max_seq_len=16,
                    tie_embeddings=False)
    model, params = init_llm(cfg, jax.random.PRNGKey(0))
    spec = CausalLMTrainer(
        lambda p, x, rng=None, train=False: model.apply({"params": p}, x))
    x = jax.random.randint(jax.random.PRNGKey(3), (8, 16), 4, 64)
    batch = {"x": x, "y": x, "mask": jnp.ones(8)}
    opt = optax.sgd(0.1)

    # golden: unsharded
    (l0, _), g = jax.value_and_grad(spec.loss, has_aux=True)(
        params, batch, jax.random.PRNGKey(0))
    up, _ = opt.update(g, opt.init(params), params)
    want = jax.tree_util.tree_map(lambda p, u: p + u, params, up)

    mesh = build_mesh({"data": 2, "fsdp": 2, "tensor": 2},
                      devices=jax.devices()[:8])
    specs = llm_param_specs(params, mesh)
    with mesh:
        sharded = shard_llm_params(params, mesh)
        step = make_sharded_train_step(
            lambda p, b, r: spec.loss(p, b, r), opt, mesh, specs)
        new_params, _, loss = step(sharded, opt.init(sharded), batch,
                                   jax.random.PRNGKey(0))
    np.testing.assert_allclose(float(loss), float(l0), atol=1e-5)
    for wleaf, gleaf in zip(jax.tree_util.tree_leaves(want),
                            jax.tree_util.tree_leaves(new_params)):
        np.testing.assert_allclose(np.asarray(wleaf), np.asarray(gleaf),
                                   atol=1e-4)


@slow
def test_federated_lora_two_silos_parity():
    """2 silos with FedAvg over adapters: with full participation and equal
    shards, the federated run must track single-silo training on the union
    of the data (UnitedLLM round semantics)."""
    common = dict(
        dataset="llm_synth", model="causal_lm", comm_round=3, epochs=1,
        batch_size=8, learning_rate=5e-3, client_optimizer="adam",
        llm_corpus_size=64, llm_max_seq_len=48, llm_hidden_size=32,
        llm_num_layers=1, llm_num_heads=2, llm_intermediate_size=64,
        lora_rank=4, random_seed=7, frequency_of_the_test=10,
        training_type="simulation", backend="sp",
    )
    r2 = run_federated_llm(Arguments(
        client_num_in_total=2, client_num_per_round=2, **common))
    r1 = run_federated_llm(Arguments(
        client_num_in_total=1, client_num_per_round=1, **common))
    # both learn (loss drops below initial-ish level) and agree closely
    assert r2["final_test_loss"] < 6.0
    assert abs(r2["final_test_loss"] - r1["final_test_loss"]) < 0.35


@slow
def test_hf_llama_import_roundtrip():
    """Fabricated Llama-named torch state dict → flax params → forward."""
    import torch
    from fedml_tpu.llm.hf import convert_llama_state_dict

    cfg = LLMConfig(vocab_size=32, hidden_size=16, intermediate_size=32,
                    num_layers=1, num_heads=2, max_seq_len=8)
    h, i, v = 16, 32, 32
    sd = {
        "model.embed_tokens.weight": torch.randn(v, h),
        "model.norm.weight": torch.ones(h),
        "model.layers.0.input_layernorm.weight": torch.ones(h),
        "model.layers.0.post_attention_layernorm.weight": torch.ones(h),
        "model.layers.0.self_attn.q_proj.weight": torch.randn(h, h),
        "model.layers.0.self_attn.k_proj.weight": torch.randn(h, h),
        "model.layers.0.self_attn.v_proj.weight": torch.randn(h, h),
        "model.layers.0.self_attn.o_proj.weight": torch.randn(h, h),
        "model.layers.0.mlp.gate_proj.weight": torch.randn(i, h),
        "model.layers.0.mlp.up_proj.weight": torch.randn(i, h),
        "model.layers.0.mlp.down_proj.weight": torch.randn(h, i),
    }
    params = convert_llama_state_dict(sd, cfg)
    model = CausalLM(cfg)
    ref_init = model.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 4), jnp.int32))["params"]
    # identical treedef + shapes as a fresh init
    got = {tuple(p): l.shape for p, l in
           jax.tree_util.tree_flatten_with_path(params)[0]}
    want = {tuple(p): l.shape for p, l in
            jax.tree_util.tree_flatten_with_path(ref_init)[0]}
    assert {str(k): v for k, v in got.items()} == \
        {str(k): v for k, v in want.items()}
    logits = model.apply({"params": params},
                         jnp.zeros((1, 4), jnp.int32))
    assert logits.shape == (1, 4, 32)
    assert np.isfinite(np.asarray(logits)).all()
