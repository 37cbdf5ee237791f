"""AOT pre-flight: every Pallas kernel in the tree must compile for the
chip it was written for, checked here on the CPU.

The installed libtpu can compile for a TPU v5e without one:
``jax.experimental.topologies`` describes a ``v5e:2x2`` host, and
``core.kernels.compile_for_tpu`` makes the kernels lower for Mosaic
although the default backend is the CPU. A Mosaic refusal therefore fails
tier-1 instead of costing chip time. A missing topology is a failure, not
a skip: without it nothing here says anything about the chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from fedml_tpu.core import kernels
from fedml_tpu.core.kernels.conv_block import fused_block
from fedml_tpu.llm import moe
from fedml_tpu.llm.attention import (FLASH_KERNEL_NAMES, WINDOW_KERNEL_NAMES,
                                     flash_causal_attention)
from fedml_tpu.llm.linear_attention import (KDA_KERNEL_NAMES, KDA_PASS_NAMES,
                                            kda_attention, kda_layer)
from fedml_tpu.llm.sparse_attention import (SPARSE_KERNEL_NAMES,
                                            select_keys, sparse_attend,
                                            word_count)
from fedml_tpu.llm.state_space import (SSD_KERNEL_NAMES, SSM_PASS_NAMES,
                                       ssd_scan, ssm_layer)

pytestmark = pytest.mark.pallas


@pytest.fixture(scope="module")
def v5e():
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    assert len(topo.devices) == 4
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return topo.devices[0]


def _compile(fn, device, *avals):
    s = SingleDeviceSharding(device)
    with kernels.compile_for_tpu():
        return jax.jit(fn, in_shardings=s, out_shardings=s).lower(
            *avals).compile()


def _flash_train(q, k, v):
    return jax.value_and_grad(
        lambda q, k, v: flash_causal_attention(q, k, v).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("shape", [(8, 1024, 8, 128), (1, 8192, 8, 128)])
def test_flash_fwd_bwd_compiles_for_v5e(v5e, shape):
    """The two chip_smoke shapes: the bench_llm_mfu step and the long
    context one, where K/V residency needs the raised VMEM limit."""
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    compiled = _compile(_flash_train, v5e, q, q, q)
    # forward, dQ and dK/dV kernels, compiled by Mosaic — not interpreted
    assert compiled.as_text().count("tpu_custom_call") == 3


def test_flash_unequal_head_sizes_compile_for_v5e(v5e):
    """Latent attention's shape in the benchmark: 64 heads of d_qk 192
    (not a multiple of the 128 lanes) and d_v 128 at 4,096 positions; the
    same three kernels, found in the HLO under their names."""
    q = jax.ShapeDtypeStruct((1, 4096, 64, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 4096, 64, 128), jnp.bfloat16)
    text = _compile(
        lambda q, k, v: jax.value_and_grad(
            lambda q, k, v: flash_causal_attention(
                q, k, v, scale=0.13).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v), v5e, q, q, v).as_text()
    assert text.count("tpu_custom_call") == 3
    for name in FLASH_KERNEL_NAMES:
        assert name in text


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_window_kernels_with_a_sink_compile_for_v5e(v5e, dtype):
    """The window cell's shape: 64 query heads of 192 / 128 on 8 key-value
    heads that the kernels take as they are (a grid step holds one of them
    and its group of 8), 4,096 positions, a window of 128 and a sink: three
    kernels under their own names, and the sink's gradient beside them,
    from what the backward already keeps."""
    q = jax.ShapeDtypeStruct((1, 4096, 64, 192), dtype)
    k = jax.ShapeDtypeStruct((1, 4096, 8, 192), dtype)
    v = jax.ShapeDtypeStruct((1, 4096, 8, 128), dtype)
    sink = jax.ShapeDtypeStruct((64,), jnp.float32)

    def train(q, k, v, sink):
        return jax.value_and_grad(
            lambda q, k, v, sink: flash_causal_attention(
                q, k, v, window=128, sink=sink).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3))(q, k, v, sink)

    text = _compile(train, v5e, q, k, v, sink).as_text()
    assert text.count("tpu_custom_call") == 3
    for name in WINDOW_KERNEL_NAMES:
        assert name in text


def _mosaic_kernels(text):
    """The Mosaic modules of a lowered text, printed without locations (a
    module's bytecode carries the file's path and line numbers)."""
    import base64
    import re

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    out = []
    for body in re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text):
        ctx = jax_mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            out.append(ir.Module.parse(base64.b64decode(body)).operation
                       .get_asm(enable_debug_info=False))
    return out


def test_the_causal_kernels_are_the_ones_before_the_window(v5e):
    """A call without a window or a sink lowers to the kernels it did at
    the parent of PR 34 (commit bbeebdd, this container's jax 0.9.0), to the
    last instruction: the three Mosaic modules at the latent-attention
    cells' shape, locations stripped. A change that means to alter the
    causal kernels brings its new hashes."""
    import hashlib

    q = jax.ShapeDtypeStruct((1, 4096, 64, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 4096, 64, 128), jnp.bfloat16)
    s = SingleDeviceSharding(v5e)
    with kernels.compile_for_tpu():
        text = jax.jit(lambda q, k, v: jax.value_and_grad(
            lambda q, k, v: flash_causal_attention(
                q, k, v, scale=0.13).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v), in_shardings=s,
            out_shardings=s).lower(q, q, v).as_text()
    got = [hashlib.sha256(m.encode()).hexdigest()[:16]
           for m in _mosaic_kernels(text)]
    assert got == ["3d52f89ca3d428ba", "3758e89e94394271",
                   "6c1ea5df628634eb"], got


def test_flash_with_a_key_mask_compiles_for_v5e(v5e):
    """The other variant of the three kernels: a call with ``attn_mask``
    carries the mask operand and compares positions in every block. Both
    variants are known to lower through Mosaic before a chip is asked."""
    q = jax.ShapeDtypeStruct((2, 1024, 8, 128), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((2, 1024), jnp.float32)
    text = _compile(
        lambda q, k, v, mask: jax.value_and_grad(
            lambda q, k, v: flash_causal_attention(
                q, k, v, attn_mask=mask).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(q, k, v), v5e, q, q, q, mask).as_text()
    assert text.count("tpu_custom_call") == 3
    for name in FLASH_KERNEL_NAMES:
        assert name in text


@pytest.mark.parametrize("transpose", [False, True])
def test_grouped_matmul_compiles_for_v5e(v5e, transpose):
    """The expert layer's grouped product at the benchmark's widths (12
    held experts of 7168 x 2048, the worst-case row buffer of one step),
    forward and activation gradient."""
    rows, tile_m = moe.buffer_rows(4096, 8, 12, 256), 256
    x = jax.ShapeDtypeStruct((rows, 2048 if transpose else 7168),
                             jnp.bfloat16)
    w = jax.ShapeDtypeStruct((12, 7168, 2048), jnp.bfloat16)
    tg = jax.ShapeDtypeStruct((rows // tile_m,), jnp.int32)
    nt = jax.ShapeDtypeStruct((1,), jnp.int32)
    text = _compile(lambda x, w, tg, nt: moe._gmm(x, w, tg, nt, tile_m,
                                                  transpose),
                    v5e, x, w, tg, nt).as_text()
    assert text.count("tpu_custom_call") == 1
    assert moe.GROUPED_KERNEL_NAMES[int(transpose)] in text


def test_expert_pass_keeps_its_conditional_on_the_v5e(v5e):
    """The expert layer at the benchmark's shapes (4,096 tokens top-8 of
    192, 12 held: worst case 35,840 rows, compact 7,168), forward and
    backward, the gates coming from the tokens through a router as the
    model's do: the compiled program holds one real conditional a direction
    (not a select over both sizes), every grouped product of both sizes in
    them (3 forward at either size; backward 3 ``dx`` from the kept gate
    and up products at the compact size, those two rebuilt and 3 ``dx`` at
    the worst-case size), and nothing of the worst-case size outside their
    branches. Its temporaries: 1,305,763,840 bytes as this was written
    (1,498,645,504 while the backward pass rebuilt the whole pass and
    gathered its result for the gates' gradient); held to that plus the
    59 MB of the two kept ``[7168, 2048]`` arrays, so that one
    ``[35840, 2048]`` array (147 MB) kept alive between the passes fails
    here and not on the chip."""
    t, h, width, held, k, experts = 4096, 7168, 2048, 12, 8, 192
    assert moe.buffer_rows(t, k, held, 256) == 35840
    assert moe.compact_rows(t, k, held, experts, 256) == 7168
    x = jax.ShapeDtypeStruct((t, h), jnp.bfloat16)
    router = jax.ShapeDtypeStruct((h, experts), jnp.float32)
    w_in = jax.ShapeDtypeStruct((held, h, width), jnp.bfloat16)
    w_out = jax.ShapeDtypeStruct((held, width, h), jnp.bfloat16)

    def step(x, router, w_gate, w_up, w_down):
        def loss(x):
            gates, chosen = moe.route(x.astype(jnp.float32) @ router, k, 2.5)
            y, stats = moe.routed_experts(x, gates, chosen, w_gate, w_up,
                                          w_down, 60, experts)
            return jnp.sum(jnp.sin(y)), stats
        return jax.grad(loss, has_aux=True)(x)

    compiled = _compile(step, v5e, x, router, w_in, w_in, w_out)
    text = compiled.as_text()
    assert text.count(" conditional(") == 2
    assert text.count("tpu_custom_call") == 2 * 3 + 3 + (2 + 3)
    entry = text[text.index("\nENTRY "):]
    assert "[35840" not in entry and "[35840" in text
    kept = 2 * 7168 * width * 2
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= 1_305_763_840 + kept


@pytest.mark.parametrize("dtype,heads", [(jnp.bfloat16, 32),
                                         (jnp.float32, 2)])
def test_kda_kernels_compile_for_v5e(v5e, dtype, heads):
    """The linear-attention layer's shape in the benchmark (32 heads of
    128 at 4,096 positions, bfloat16) and float32 operands: the forward
    kernel and the backward one, whose body is ``jax.vjp`` of the chunk
    step, compiled by Mosaic and found in the HLO under their names."""
    qk = jax.ShapeDtypeStruct((1, 4096, heads, 128), dtype)
    g = jax.ShapeDtypeStruct((1, 4096, heads, 128), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, 4096, heads), jnp.float32)
    text = _compile(
        lambda *a: jax.value_and_grad(
            lambda *a: kda_attention(*a, impl="flash").astype(
                jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))(*a),
        v5e, qk, qk, qk, g, beta).as_text()
    assert text.count("tpu_custom_call") == 2
    for name in KDA_KERNEL_NAMES:
        assert name in text, name


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_ssd_kernels_compile_for_v5e(v5e, dtype):
    """The state-space layer's shape in the benchmark (128 heads of 64
    channels, state 128, 8 groups, 4,096 positions in chunks of 128),
    bfloat16 and float32 operands: the forward kernel and the backward
    one, whose body is ``jax.vjp`` of the chunk step, compiled by Mosaic
    and found in the HLO under their names."""
    f32 = jnp.float32
    x = jax.ShapeDtypeStruct((1, 4096, 128, 64), dtype)
    dt = jax.ShapeDtypeStruct((1, 4096, 128), f32)
    head = jax.ShapeDtypeStruct((128,), f32)
    bc = jax.ShapeDtypeStruct((1, 4096, 8, 128), dtype)
    text = _compile(
        lambda *a: jax.value_and_grad(
            lambda *a: ssd_scan(*a, impl="flash").astype(f32).sum(),
            argnums=(0, 1, 3, 4))(*a),
        v5e, x, dt, head, bc, bc, head).as_text()
    assert text.count("tpu_custom_call") == 2
    for name in SSD_KERNEL_NAMES:
        assert name in text, name


def _ssm_layer_step(masked):
    def step(zx, conv_w, conv_b, a_log, skip, dt_bias, scale, mask):
        return jax.value_and_grad(
            lambda zx: ssm_layer(
                zx, mask if masked else None, conv_w, conv_b, a_log, skip,
                dt_bias, scale, heads=128, head_dim=64, groups=8, state=128,
                eps=1e-5).astype(jnp.float32).sum())(zx)
    return step


def _ssm_layer_avals(dtype, s=4096):
    wide, head = 8192 + 2 * 1024, jax.ShapeDtypeStruct((128,), dtype)
    return (jax.ShapeDtypeStruct((1, s, 8192 + wide + 128), dtype),
            jax.ShapeDtypeStruct((4, wide), dtype),
            jax.ShapeDtypeStruct((wide,), dtype), head, head, head,
            jax.ShapeDtypeStruct((8192,), dtype),
            jax.ShapeDtypeStruct((1, s), jnp.int32))


@pytest.mark.parametrize("dtype,masked", [
    (jnp.bfloat16, False), (jnp.bfloat16, True), (jnp.float32, False)])
def test_ssm_passes_compile_for_v5e(v5e, dtype, masked):
    """The fused passes around the SSD kernels at the benchmark's shape
    (the ``in_proj`` product ``[1, 4096, 18560]``: 128 heads of 64, 8
    groups of state 128), with a mask, and in float32: four more Mosaic
    kernels beside the two, under their names, and the product's cotangent
    written in place (no concatenation of its three parts)."""
    text = _compile(_ssm_layer_step(masked), v5e,
                    *_ssm_layer_avals(dtype)).as_text()
    assert text.count("tpu_custom_call") == 6
    for name in SSD_KERNEL_NAMES + SSM_PASS_NAMES:
        assert name in text, name
    assert "concatenate" not in text


def test_only_the_ssd_kernels_read_as_ssd_kernels(v5e):
    """``ssd_kernels_roofline`` finds its two kernels by a substring of an
    instruction's name: of the layer's six custom calls it must take
    ``ssd_fwd`` and ``ssd_bwd`` and none of the passes."""
    import importlib.util
    import os
    import re

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "metrics",
        "ssd_kernels_roofline.py")
    spec = importlib.util.spec_from_file_location("ssd_roofline", path)
    metric = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(metric)
    text = _compile(_ssm_layer_step(False), v5e,
                    *_ssm_layer_avals(jnp.bfloat16)).as_text()
    names = re.findall(r"^\s*%?([\w.\-]+) = .*custom_call_target="
                       r"\"tpu_custom_call\"", text, re.M)
    assert len(names) == 6, names
    kinds = {name: metric.kind_of(name) for name in names}
    assert sorted(k for k in kinds.values() if k) == ["bwd", "fwd"], kinds
    for name, kind in kinds.items():
        mine = [n for n in SSD_KERNEL_NAMES + SSM_PASS_NAMES if n in name]
        assert mine == (["ssd_" + kind] if kind else mine[:1]) and mine, kinds


def test_the_non_gated_expert_pass_compiles_for_v5e(v5e):
    """The latent expert layer at the benchmark's shapes (4,096 tokens
    top-22 of 512, 64 held at 1,024 -> 2,688 -> 1,024: worst case 106,496
    rows, compact 38,912), forward and backward: two grouped products a
    direction at the compact size (``up`` and ``down``; their two ``dx``
    from the kept up product), 2 + (1 + 2) at the worst-case one."""
    t, lat, width, held, k, experts = 4096, 1024, 2688, 64, 22, 512
    assert moe.buffer_rows(t, k, held, 256) == 106496
    assert moe.compact_rows(t, k, held, experts, 256) == 38912
    x = jax.ShapeDtypeStruct((t, lat), jnp.bfloat16)
    router = jax.ShapeDtypeStruct((lat, experts), jnp.float32)
    w_up = jax.ShapeDtypeStruct((held, lat, width), jnp.bfloat16)
    w_down = jax.ShapeDtypeStruct((held, width, lat), jnp.bfloat16)

    def step(x, router, w_up, w_down):
        def loss(x):
            gates, chosen = moe.route(x.astype(jnp.float32) @ router, k, 5.0)
            y, stats = moe.routed_experts(x, gates, chosen, None, w_up,
                                          w_down, 192, experts)
            return jnp.sum(jnp.sin(y)), stats
        return jax.grad(loss, has_aux=True)(x)

    text = _compile(step, v5e, x, router, w_up, w_down).as_text()
    assert text.count(" conditional(") == 2
    assert text.count("tpu_custom_call") == 2 * 2 + 2 + (1 + 2)
    entry = text[text.index("\nENTRY "):]
    assert "[106496" not in entry and "[106496" in text


def _kda_layer_step(heads, masked):
    def step(ys, beta_logits, gate_logits, conv, a_log, dt_bias, o_scale,
             mask):
        return jax.value_and_grad(
            lambda ys, b, g: kda_layer(
                ys, b, g, conv, a_log, dt_bias, o_scale,
                mask if masked else None, heads=heads, lower=-5.0, eps=1e-6,
                impl="flash").astype(jnp.float32).sum(),
            argnums=(0, 1, 2))(ys, beta_logits, gate_logits)
    return step


def _kda_layer_avals(dtype, heads, s=4096, d=128):
    wide = lambda dt: jax.ShapeDtypeStruct((1, s, heads * d), dt)  # noqa
    f32 = jnp.float32
    per_head = jax.ShapeDtypeStruct((1, s, heads), dtype)
    return ({**{n: wide(dtype) for n in "qkv"}, "f": wide(f32)}, per_head,
            per_head, [jax.ShapeDtypeStruct((4, heads * d), f32)] * 3,
            jax.ShapeDtypeStruct((heads,), f32),
            jax.ShapeDtypeStruct((heads * d,), f32),
            jax.ShapeDtypeStruct((d,), f32),
            jax.ShapeDtypeStruct((1, s), f32))


@pytest.mark.parametrize("dtype,heads,masked", [
    (jnp.bfloat16, 32, False), (jnp.bfloat16, 32, True),
    (jnp.float32, 2, False)])
def test_kda_passes_compile_for_v5e(v5e, dtype, heads, masked):
    """The fused passes around the KDA kernels at the benchmark's shape
    (``[1, 4096, 32 x 128]``, bfloat16 products and a float32 decay
    product), with a key mask, and in float32: four more Mosaic kernels
    beside the two, under their names."""
    text = _compile(_kda_layer_step(heads, masked), v5e,
                    *_kda_layer_avals(dtype, heads)).as_text()
    assert text.count("tpu_custom_call") == 6
    for name in KDA_KERNEL_NAMES + KDA_PASS_NAMES:
        assert name in text, name


def test_only_the_kda_kernels_read_as_kda_kernels(v5e):
    """``kda_kernels_roofline`` finds its two kernels by a substring of an
    instruction's name: of the layer's six custom calls it must take
    ``kda_fwd`` and ``kda_bwd`` and none of the passes."""
    import importlib.util
    import os
    import re

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "metrics",
        "kda_kernels_roofline.py")
    spec = importlib.util.spec_from_file_location("kda_roofline", path)
    metric = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(metric)
    text = _compile(_kda_layer_step(32, False), v5e,
                    *_kda_layer_avals(jnp.bfloat16, 32)).as_text()
    names = re.findall(r"^\s*%?([\w.\-]+) = .*custom_call_target="
                       r"\"tpu_custom_call\"", text, re.M)
    assert len(names) == 6, names
    kinds = {name: metric.kind_of(name) for name in names}
    assert sorted(k for k in kinds.values() if k) == ["bwd", "fwd"], kinds
    for name, kind in kinds.items():
        mine = [n for n in KDA_KERNEL_NAMES + KDA_PASS_NAMES if n in name]
        assert mine == (["kda_" + kind] if kind else mine[:1]) and mine, kinds


def _unbounded_layer_step(masked):
    def step(ys, beta_logits, gate_logits, conv, a_log, dt_bias, o_scale,
             mask):
        def loss(ys, b, g):
            y, counts = kda_layer(
                ys, b, g, conv, a_log, dt_bias, o_scale,
                mask if masked else None, heads=32, lower=None, eps=1e-5,
                impl="flash")
            return y.astype(jnp.float32).sum(), counts
        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            ys, beta_logits, gate_logits)
    return step


@pytest.mark.parametrize("masked", [False, True])
def test_the_unbounded_kda_layer_compiles_for_v5e(v5e, masked):
    """The unbounded-gate cell's KDA layer (``[1, 4096, 32 x 128]``,
    bfloat16 products, the softplus gate, a gate a channel as a ``[1, 4096,
    4096]`` product): the kernels with each sub-chunk's block against itself
    made element by element, the passes with the counts of steep decays
    and the channel gate, six Mosaic kernels under their names."""
    avals = list(_kda_layer_avals(jnp.bfloat16, 32))
    avals[2] = jax.ShapeDtypeStruct((1, 4096, 32 * 128), jnp.bfloat16)
    text = _compile(_unbounded_layer_step(masked), v5e, *avals).as_text()
    assert text.count("tpu_custom_call") == 6
    for name in KDA_KERNEL_NAMES + KDA_PASS_NAMES:
        assert name in text, name


def _sparse_step(qi, ki, w, q, k, v):
    """The Keye cell's sparse attention layer between its products: the
    selection, then the attention forward and its gradients."""
    words = select_keys(qi, ki, w, 2048, impl="flash")
    return jax.value_and_grad(
        lambda q, k, v: sparse_attend(q, k, v, words, 2048,
                                      impl="flash").astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)


def _sparse_avals(s=16384):
    bf = jnp.bfloat16
    return (jax.ShapeDtypeStruct((1, s, 16, 64), bf),
            jax.ShapeDtypeStruct((1, s, 64), bf),
            jax.ShapeDtypeStruct((1, s, 16), jnp.float32),
            jax.ShapeDtypeStruct((1, s, 32, 128), bf),
            jax.ShapeDtypeStruct((1, s, 4, 128), bf),
            jax.ShapeDtypeStruct((1, s, 4, 128), bf))


def test_sparse_kernels_compile_for_v5e(v5e):
    """The Keye cell's shape (16,384 positions, 32 query heads on 4 of 128,
    16 index heads of 64, 2,048 keys a query, bfloat16): the index kernel
    with its row of 16,384 sortable keys in VMEM, and the attention
    kernels forward and backward, compiled by Mosaic under their names;
    the selection leaves as 32 MiB of bits and no score-sized buffer is
    made."""
    compiled = _compile(_sparse_step, v5e, *_sparse_avals())
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 4
    for name in SPARSE_KERNEL_NAMES:
        assert name in text, name
    assert word_count(16384) == 512
    assert "s32[1,16384,512]" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 16384 * 16384 * 4


def test_only_the_sparse_kernels_read_as_sparse_kernels(v5e):
    """``sparse_kernels_roofline`` finds its four kernels by a substring of
    an instruction's name, and ``flash_kernels_roofline`` takes none of
    them."""
    import importlib.util
    import os
    import re

    def metric(name):
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "metrics",
            name + ".py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    sparse, flash = (metric("sparse_kernels_roofline"),
                     metric("flash_kernels_roofline"))
    text = _compile(_sparse_step, v5e, *_sparse_avals(1024)).as_text()
    names = re.findall(r"^\s*%?([\w.\-]+) = .*custom_call_target="
                       r"\"tpu_custom_call\"", text, re.M)
    assert len(names) == 4, names
    assert sorted(map(sparse.kind_of, names)) == ["dkv", "dq", "fwd",
                                                  "index"]
    assert not any(map(flash.kind_of, names))


def test_flash_bwd_never_materializes_scores(v5e):
    """Training-memory contract: at s=4096 the compiled fwd+bwd must not
    allocate an [s, s] f32 buffer (64 MiB); flash peak temp stays under a
    quarter of that."""
    s, d = 4096, 64
    q = jax.ShapeDtypeStruct((1, s, 1, d), jnp.bfloat16)
    mem = _compile(_flash_train, v5e, q, q, q).memory_analysis()
    scores_bytes = s * s * 4
    assert mem.temp_size_in_bytes < scores_bytes // 4, (
        f"temp {mem.temp_size_in_bytes} vs scores {scores_bytes}")


# ResNet-56's three stages at the flagship batch, and the strided
# transitions between them (the larger one costs 5 s: full gate only)
@pytest.mark.parametrize("hw,cin,c,strides", [
    (32, 16, 16, 1), (16, 32, 32, 1), (8, 64, 64, 1), (16, 32, 64, 2),
    pytest.param(32, 16, 32, 2, marks=pytest.mark.slow)])
def test_conv_block_compiles_for_v5e(v5e, hw, cin, c, strides):
    shapes = {"w1": (3, 3, cin, c), "w2": (3, 3, c, c)}
    for g in ("g1", "g2") + (("gp",) if strides == 2 else ()):
        shapes[g + "_scale"] = shapes[g + "_bias"] = (c,)
    if strides == 2:
        shapes["wp"] = (1, 1, cin, c)
    p = {k: jax.ShapeDtypeStruct(v, jnp.bfloat16) for k, v in shapes.items()}
    x = jax.ShapeDtypeStruct((32, hw, hw, cin), jnp.bfloat16)
    compiled = _compile(
        lambda x, p: fused_block(x, p, strides=strides), v5e, x, p)
    assert "tpu_custom_call" in compiled.as_text()
