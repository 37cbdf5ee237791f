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


# ------------------------------------------------------------------------
# The flash kernels' block plan and both of their variants (tier-1, the
# Pallas interpreter on the CPU): without a key mask and with s on the
# 128 grid the kernels carry no mask arithmetic; with a mask or a padded s
# every block runs the masked arithmetic; in both, only the blocks the
# diagonal crosses run the causal compare.

@pytest.mark.parametrize("s,block_q,block_k,want", [
    (4096, 512, 512, (28, 8, 28)),      # the axk1 cell: 28/36 interior
    (1024, 512, 512, (1, 2, 1)),        # the Mistral cell: 1/3
    (8192, 512, 512, (120, 16, 120)),   # chip_smoke's long context
    (1024, 256, 512, (2, 4, 2)),
    (1024, 512, 128, (4, 8, 4)),
])
def test_flash_block_plan_by_hand(s, block_q, block_k, want):
    from fedml_tpu.llm.attention import flash_block_plan
    plan = flash_block_plan(s, block_q, block_k)
    assert plan.counts() == want
    assert sum(want) == (s // block_q) * (s // block_k)
    # the k-major kernel walks the same blocks along the other axis
    assert plan.counts(k_major=True) == want
    # and block by block: a block is interior iff its last key <= its
    # first query, live iff its first key <= its last query
    for i in range(plan.n_q):
        n_full, n_live = plan.q_major(i)
        for j in range(plan.n_k):
            interior = (j + 1) * block_k - 1 <= i * block_q
            live = j * block_k <= (i + 1) * block_q - 1
            assert (j < n_full) == interior and (j < n_live) == live
            j0, j_full = plan.k_major(j)
            assert (i >= j_full) == interior and (i >= j0) == live


def _flash_case(s, d_qk=8, d_v=8, b=2, h=2, dtype=jnp.float32, seed=0):
    key = jax.random.PRNGKey(seed)
    q, k = (jax.random.normal(jax.random.fold_in(key, i),
                              (b, s, h, d_qk)).astype(dtype) for i in (0, 1))
    v, c = (jax.random.normal(jax.random.fold_in(key, i),
                              (b, s, h, d_v)).astype(dtype) for i in (2, 3))
    return q, k, v, c.astype(jnp.float32)


def _out_and_grads(fn, q, k, v, c):
    def loss(q, k, v):
        out = fn(q, k, v)
        return jnp.sum(out.astype(jnp.float32) * c), out
    (_, out), grads = jax.value_and_grad(loss, argnums=(0, 1, 2),
                                         has_aux=True)(q, k, v)
    return (out,) + grads


def _worst_rel(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    assert np.isfinite(got).all()
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


FLASH_CASES = {
    # name: (s, block_q, block_k, key mask?, d_qk, d_v, scale)
    "unmasked_on_grid": (256, 128, 128, False, 8, 8, None),
    "key_mask": (256, 128, 128, True, 8, 8, None),
    "padded_s1000": (1000, 512, 512, False, 8, 8, None),
    "block_q_2x_block_k": (512, 256, 128, False, 8, 8, None),
    "block_k_2x_block_q": (512, 128, 256, False, 8, 8, None),
    "block_k_2x_block_q_key_mask": (512, 128, 256, True, 8, 8, None),
    "latent_192_128_scaled": (256, 128, 128, False, 192, 128, 0.11),
}


@pytest.mark.pallas
@pytest.mark.parametrize("case", sorted(FLASH_CASES))
def test_flash_variants_match_dense(case):
    """Output and all three gradients against dense attention at float32,
    to the 1e-5 the older flash tests hold."""
    s, block_q, block_k, masked, d_qk, d_v, scale = FLASH_CASES[case]
    b = 1 if s >= 1000 or d_qk > 8 else 2
    q, k, v, c = _flash_case(s, d_qk, d_v, b=b)
    mask = None
    if masked:
        mask = (jax.random.uniform(jax.random.PRNGKey(7), (b, s)) > 0.3
                ).astype(jnp.float32).at[:, 0].set(1.0)
    flash = lambda q, k, v: flash_causal_attention(  # noqa: E731
        q, k, v, block_q, block_k, attn_mask=mask, scale=scale)
    dense = lambda q, k, v: dense_causal_attention(  # noqa: E731
        q, k, v, attn_mask=mask, scale=scale)
    for got, want in zip(_out_and_grads(flash, q, k, v, c),
                         _out_and_grads(dense, q, k, v, c)):
        assert got.shape == want.shape
        assert _worst_rel(got, want) < 1e-5
    if masked:      # masked keys take no gradient at all
        _, _, dk, dv = _out_and_grads(flash, q, k, v, c)
        dead = np.asarray(mask) == 0
        assert np.all(np.asarray(dk)[dead] == 0)
        assert np.all(np.asarray(dv)[dead] == 0)


@pytest.mark.pallas
def test_flash_bf16_operands_stay_within_bf16_of_the_float32_result():
    """bfloat16 q, k, v go to the products as they lie (float32 statistics
    and accumulators; ``p`` and ``dS`` rounded once to bf16 for their
    second product). Against DENSE attention of the same values at float32
    the worst element of the output and of each gradient stays under 1e-2
    of the largest: bf16 keeps 8 bits, 4e-3 a rounding. The interpreter
    reads 0.0027, 0.0037, 0.0049, 0.0028 (out, dq, dk, dv) here; the
    parent's kernels, whose float32 products the interpreter does not
    round as the MXU does, read 0.0022, 0.0023, 0.0039, 0.0025. On the
    v5e both round alike: kernels alone at [1,4096,64,192/128], L2 error
    against float32 dense 0.00349, 0.00386, 0.00418, 0.00369 now and
    0.00348, 0.00386, 0.00421, 0.00371 before (PERF.md, PR 31)."""
    q, k, v, c = _flash_case(512, 64, 64, b=1, dtype=jnp.bfloat16, seed=3)
    flash = lambda q, k, v: flash_causal_attention(  # noqa: E731
        q, k, v, 256, 256)
    got = _out_and_grads(flash, q, k, v, c)
    assert all(g.dtype == jnp.bfloat16 for g in got)
    f32 = [a.astype(jnp.float32) for a in (q, k, v)]
    want = _out_and_grads(dense_causal_attention, *f32, c)
    for g, w in zip(got, want):
        assert _worst_rel(g, w) < 1e-2


@pytest.mark.pallas
@pytest.mark.parametrize("s,masked,share,flag", [
    (1024, False, 1 / 3, 0.0), (4096, False, 28 / 36, 0.0),
    (1024, True, 1 / 3, 1.0), (1000, False, 1 / 3, 1.0)])
def test_flash_plan_gauges_are_set_when_a_call_is_traced(s, masked, share,
                                                         flag):
    """``fed_flash_interior_block_share`` and ``fed_flash_key_mask`` say
    which variant a traced call got (a padded s carries a key mask); with
    the registry's hooks off nothing is recorded."""
    from fedml_tpu.core.obs import REGISTRY, metrics
    q = jax.ShapeDtypeStruct((1, s, 1, 8), jnp.float32)
    mask = jnp.ones((1, s), jnp.float32) if masked else None
    trace = lambda: jax.eval_shape(  # noqa: E731
        lambda q, k, v: flash_causal_attention(q, k, v, attn_mask=mask),
        q, q, q)
    names = ("fed_flash_interior_block_share", "fed_flash_key_mask")
    was = metrics.is_enabled()
    try:
        metrics.set_enabled(True)
        trace()
        got = [REGISTRY.gauge(n).value() for n in names]
        assert got == pytest.approx([share, flag])
        for n in names:
            REGISTRY.gauge(n).set(-1.0)
        metrics.set_enabled(False)
        trace()
        assert [REGISTRY.gauge(n).value() for n in names] == [-1.0, -1.0]
    finally:
        metrics.set_enabled(was)


@pytest.mark.pallas
def test_flash_all_masked_rows_read_zero_with_a_finite_gradient():
    """A query row whose every visible key is masked (the guarantee lives
    in the masked variant alone: without a key mask every row sees key 0)
    reads exactly zero, passes no gradient on, and the live rows still
    match dense attention."""
    s = 256
    q, k, v, c = _flash_case(s, b=1, h=1)
    mask = jnp.ones((1, s), jnp.float32).at[:, :130].set(0.0)
    flash = lambda q, k, v: flash_causal_attention(  # noqa: E731
        q, k, v, 128, 128, attn_mask=mask)
    dense = lambda q, k, v: dense_causal_attention(  # noqa: E731
        q, k, v, attn_mask=mask)
    out, dq, dk, dv = _out_and_grads(flash, q, k, v, c)
    assert np.all(np.asarray(out)[:, :130] == 0.0)
    for g in (dq, dk, dv):
        assert np.isfinite(np.asarray(g)).all()
    assert np.all(np.asarray(dq)[:, :130] == 0.0)
    assert np.all(np.asarray(dk)[:, :130] == 0.0)
    # dense attention spreads an all-masked row evenly over every key, so
    # it is asked with those rows' cotangent at zero
    want = _out_and_grads(dense, q, k, v, c.at[:, :130].set(0.0))
    assert _worst_rel(out[:, 130:], want[0][:, 130:]) < 1e-5
    assert _worst_rel(dq[:, 130:], want[1][:, 130:]) < 1e-5
    assert _worst_rel(dk[:, 130:], want[2][:, 130:]) < 1e-5
    assert _worst_rel(dv[:, 130:], want[3][:, 130:]) < 1e-5


def test_flash_bundles_reads_loops_out_of_a_bundle_dump(tmp_path):
    """``scripts/flash_bundles.py`` counts a kernel's loops in libtpu's
    final-bundles text: a back edge is a loop, its length the bundles it
    spans, nested loops counted inside the outer one (a hand-made dump in
    the dump's own syntax; the compile that writes a real one is run by
    hand, it takes a quarter of a minute)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts"))
    import flash_bundles
    dump = tmp_path / "k-71-final_bundles.txt"
    dump.write_text("\n".join([
        "     0   :  { %s1_s0 = inlined_call_operand.vmem [shape: bf16[8]] }",
        "   0x1 LB: > { %s7_s26 = sadd.s32 1, %s4_s25 }",
        "   0x2 LB: >> { %v5_v1 = vld [vmem:[#allocation2_spill] sm:$0xff]"
        "  ;;  %9 = vmatmul.bf16.gmra.mxu0 %v5_v1 }",
        "   0x3   : >> { %v6_v2 = vpop.f32.mrf.mxu0  ;;  %11 = vst"
        " [vmem:[#allocation3_spill] sm:$0xff] %v6_v2 }",
        "   0x4   : >> { %12 = sbr.rel (!%p3_p4) target bundleno = 2 (0x2),"
        " region = 7 }",
        "   0x5   : > { %v8_v3 = vsel %vm1_vm0, %v6_v2, 0.0 }",
        "   0x6   : > { %13 = sbr.rel (!%p2_p2) target bundleno = 1 (0x1),"
        " region = 3 }",
    ]))
    (outer_lo, outer_hi, outer), (lo, hi, inner) = flash_bundles.loops(
        str(dump))
    assert (outer_lo, outer_hi, lo, hi) == (1, 6, 2, 4)
    assert {k: inner[k] for k in ("vld", "vmatmul", "vpop", "vst")} == {
        "vld": 1, "vmatmul": 1, "vpop": 1, "vst": 1}
    assert outer["vmatmul"] == 1 and outer["vsel"] == 1 and inner["vsel"] == 0


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
