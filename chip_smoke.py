#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that fedml_tpu still starts on the chip.

    python3 chip_smoke.py              # on a machine with a TPU
    python3 chip_smoke.py --rehearse   # toy sizes on the CPU, never a pass

One process drives the system's main paths once, through the entry points a
user calls, at the widest sizes the repo's own records use (ResNet-56 / 64
clients / batch 32; causal LM h1024 / L8 / seq 1024 and 8192), with weights
and data generated from seeds:

  round engine   fedml_tpu.run_simulation: single rounds, one fused 8-round
                 dispatch, two evals
  llm train      build_llm -> TPUSimulator federated LoRA rounds with the
                 Pallas flash kernels (Mosaic custom call asserted in the
                 lowered round program), then fwd+bwd at seq 8192
  serving        CausalLMPredictor(mode="batch") -> DecodeScheduler answers
                 concurrent generate calls; steady state compiles nothing
  kernels        flash vs dense attention and fused vs reference conv block,
                 values and gradients
  four chips     (only when four devices are visible) the same rounds over
                 {client: 4}, the {fsdp 2, tensor 2} train step, the sp=4
                 ring forward; every device must hold data

Any failing check raises, and the process exits non-zero without a result
line. The result — the LAST line of stdout — is printed only when
``jax.devices()[0].platform == "tpu"`` and every phase passed:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--rehearse`` runs every phase at toy size on whatever backend JAX finds so
the script can be debugged without a chip; it prints no result line and
exits 3. Timings printed by either mode are smoke observations of one run,
not benchmark numbers.
"""

from __future__ import annotations

import argparse
import concurrent.futures as cf
import functools
import importlib.metadata
import json
import math
import os
import statistics
import sys
import time

FULL = {
    "resnet": dict(model="resnet56", clients=64, batch=32,
                   synthetic_size=50_000, test_size=1000, max_total=0),
    "llm": dict(hidden=1024, inter=2816, layers=8, heads=8, seq=1024,
                batch=8, rounds=3, long_seq=8192, long_vocab=8192,
                long_steps=4),
    "serve": dict(slots=8, max_new=16,
                  prompt_chars=(3, 40, 150, 400, 700, 12)),
    "flash_shapes": ((8, 1024, 8, 128), (1, 8192, 2, 128)),
    "kda_shape": (1, 2048, 4, 128),
    # the KDA cells' layer (both gate forms): b, s, heads, channels a head
    "kda_cell_shape": (1, 4096, 32, 128),
    "kda_softplus_shape": (1, 4096, 32, 128),
    # b, s, heads, channels a head, groups, state size
    "ssd_shape": (1, 4096, 128, 64, 8, 128),
    # b, s, query heads, key-value heads, d_qk, d_v, window
    "window_shape": (1, 4096, 64, 8, 192, 128, 128),
    # b, s, query heads, key-value heads, head size, index heads, index
    # head size, keys a query; the rows of the gradient check
    "sparse_shape": (1, 16384, 32, 4, 128, 16, 64, 2048),
    "sparse_grad_seq": 4096,
    "conv_batch": 32,
    "ring_seq": 8192,
}
TOY = {
    "resnet": dict(model="resnet20", clients=4, batch=4, synthetic_size=64,
                   test_size=32, max_total=64),
    "llm": dict(hidden=64, inter=128, layers=2, heads=2, seq=128, batch=2,
                rounds=3, long_seq=256, long_vocab=512, long_steps=4),
    "serve": dict(slots=4, max_new=4, prompt_chars=(3, 20, 60, 9)),
    "flash_shapes": ((2, 128, 2, 32),),
    "kda_shape": (1, 128, 2, 128),
    "kda_cell_shape": (1, 128, 2, 128),
    "kda_softplus_shape": (1, 128, 2, 128),
    "ssd_shape": (1, 160, 4, 64, 2, 128),
    "window_shape": (1, 256, 4, 2, 24, 16, 40),
    "sparse_shape": (1, 384, 4, 2, 128, 4, 64, 100),
    "sparse_grad_seq": 256,
    "conv_batch": 8,
    "ring_seq": 256,
}

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_compile = {"n": 0, "s": 0.0, "hits": 0}


def say(**rec):
    print(json.dumps(rec), flush=True)


def on_chip():
    import jax
    return jax.default_backend() == "tpu"


def chip_timings(**obs):
    """Device timings are observations of the chip: a rehearsal on the CPU
    reports none, so no CPU number ever carries a device metric's name."""
    return obs if on_chip() else {}


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


def cache_entries():
    import jax
    d = jax.config.jax_compilation_cache_dir
    if not d or not os.path.isdir(d):
        return d, 0
    return d, sum(not f.endswith("-atime") for f in os.listdir(d))


def bytes_in_use_mib():
    """Per device; every device a phase spread over must hold something.
    The CPU backend keeps no memory statistics (rehearsal: None)."""
    import jax
    stats = [d.memory_stats() for d in jax.devices()]
    if not all(stats):
        return None
    in_use = [round(s["bytes_in_use"] / 2 ** 20, 1) for s in stats]
    check(all(b > 0 for b in in_use), f"bytes_in_use per device: {in_use}")
    return in_use


def run_phase(name, fn, *args):
    """Time one phase; its checks raise, so a failure ends the run."""
    from fedml_tpu.core.obs.profiler import sample_hbm_peak_gb
    c0 = dict(_compile)
    t0 = time.perf_counter()
    obs = fn(*args) or {}
    say(phase=name, ok=True, wall_s=round(time.perf_counter() - t0, 2),
        compiles=_compile["n"] - c0["n"],
        compile_s=round(_compile["s"] - c0["s"], 2),
        cache_hits=_compile["hits"] - c0["hits"],
        peak_hbm_gib=sample_hbm_peak_gb(), **obs)


def round_trips(fn, *xs, n):
    """Seconds per call of jitted ``fn``, each ended by block_until_ready,
    after one warm-up call."""
    import jax
    f = jax.jit(fn)
    jax.block_until_ready(f(*xs))
    trips = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*xs))
        trips.append(time.perf_counter() - t0)
    return trips


# ---------------------------------------------------------------- phases ----

def phase_dispatch():
    """Round trip of an empty jitted call ended by block_until_ready."""
    import jax.numpy as jnp
    trips = round_trips(lambda x: x, jnp.zeros((), jnp.float32), n=200)
    return chip_timings(
        empty_dispatch_round_trip_ms=round(1e3 * statistics.median(trips), 4),
        empty_dispatch_max_ms=round(1e3 * max(trips), 4))


def phase_round_engine(sz):
    """ResNet rounds through the public one-liner: round 0 alone with an
    eval, rounds 1-8 as ONE fused dispatch, round 9 alone with an eval."""
    import jax
    import numpy as np

    import fedml_tpu
    from fedml_tpu.core.obs.metrics import REGISTRY

    rounds_by = REGISTRY.counter("fed_dispatch_rounds_total",
                                 labels=("dispatch",))
    before = {k: rounds_by.value(dispatch=k) for k in ("round",
                                                       "rounds_fused")}
    r = sz["resnet"]
    result = fedml_tpu.run_simulation(
        backend="tpu", dataset="synthetic_cifar10", model=r["model"],
        precision="bfloat16", client_num_in_total=r["clients"],
        client_num_per_round=r["clients"], batch_size=r["batch"],
        comm_round=10, frequency_of_the_test=9, epochs=1,
        learning_rate=0.1, random_seed=0,
        synthetic_size=r["synthetic_size"],
        synthetic_test_size=r["test_size"],
        max_total_samples=r["max_total"])
    hist = result["history"]
    check(len(hist) == 10, f"expected 10 round records, got {len(hist)}")
    losses = [h["train_loss"] for h in hist]
    check(all(np.isfinite(losses)), f"non-finite train loss: {losses}")
    evals = [h for h in hist if "test_acc" in h]
    check(len(evals) == 2 and all(np.isfinite(h["test_loss"])
                                  for h in evals),
          f"expected two finite evals, got {evals}")
    single = rounds_by.value(dispatch="round") - before["round"]
    fused = rounds_by.value(dispatch="rounds_fused") - before["rounds_fused"]
    check(single == 2 and fused == 8,
          f"dispatch mix: {single} single rounds, {fused} fused rounds")
    leaf = jax.tree_util.tree_leaves(result["params"])[0]
    n_dev = len(jax.devices())
    check(len(leaf.sharding.device_set) == n_dev,
          f"params live on {len(leaf.sharding.device_set)} of {n_dev} "
          "devices")
    return {"model": r["model"], "clients": r["clients"],
            "mesh": {"client": n_dev},
            "bytes_in_use_mib": bytes_in_use_mib(),
            "train_loss_first_last": [round(losses[0], 4),
                                      round(losses[-1], 4)],
            "final_test_acc": result["final_test_acc"],
            **chip_timings(run_wall_s=round(result["wall_time_s"], 2))}


def _llm_args(sz, **over):
    from fedml_tpu.arguments import Arguments
    m = sz["llm"]
    kw = dict(
        dataset="llm", model="causal_lm", precision="bfloat16",
        client_num_in_total=2, client_num_per_round=2,
        comm_round=m["rounds"], epochs=1, batch_size=m["batch"],
        learning_rate=0.05, federated_optimizer="fedavg",
        frequency_of_the_test=10_000, random_seed=0,
        llm_corpus_fallback="shakespeare", llm_hidden_size=m["hidden"],
        llm_intermediate_size=m["inter"], llm_num_layers=m["layers"],
        llm_num_heads=m["heads"], llm_max_seq_len=m["seq"], lora_rank=8)
    kw.update(over)
    return Arguments(**kw)


def phase_llm_lora_rounds(sz, keep):
    """Federated LoRA rounds: build_llm -> TPUSimulator, attention left to
    its platform default. On the chip that default must be the flash
    kernels, COMPILED: the lowered round program carries the Mosaic call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.core import kernels
    from fedml_tpu.core.algframe.types import TrainHyper
    from fedml_tpu.llm.federated import build_llm
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    lowered = {}

    args = _llm_args(sz)
    fed, bundle, spec, _ = build_llm(args)
    impl = bundle.cfg.attention_impl
    if on_chip():
        check(impl == "flash", f"attention impl on the chip is {impl!r}")
        check(not kernels.interpret(), "Pallas kernels would be interpreted")
    sim = TPUSimulator(args, fed, bundle, create_optimizer(args, spec), spec)
    traced = sim._traced

    def lowering_traced(name, n_rounds, fn, *fn_args, **kw):
        """Keeps the StableHLO of each program, lowered with the
        arguments of its first dispatch (before it donates them)."""
        if name not in lowered:
            lowered[name] = fn.lower(*fn_args).as_text()
        return traced(name, n_rounds, fn, *fn_args, **kw)

    sim._traced = lowering_traced
    hyper = TrainHyper(learning_rate=jnp.float32(args.learning_rate),
                       epochs=1)
    losses, count = [], 0.0
    for r in range(sz["llm"]["rounds"]):
        m = sim.run_round(r, hyper)
        count = float(m["count"])
        losses.append(float(m["loss_sum"]) / max(count, 1.0))
    check(count > 0, "no tokens trained")
    check(all(np.isfinite(losses)), f"non-finite LoRA loss: {losses}")
    check(losses[-1] < losses[0], f"LoRA loss did not fall: {losses}")
    mosaic_calls = lowered["round"].count("tpu_custom_call")
    if on_chip():
        check(mosaic_calls > 0,
              "no Mosaic custom call in the lowered round program")
    keep["bundle"], keep["adapters"] = bundle, sim.params
    return {"attention_impl": impl, "mosaic_custom_calls": mosaic_calls,
            "tokens_per_round": count,
            "loss_per_round": [round(x, 4) for x in losses]}


def phase_llm_long_context(sz):
    """Full fine-tune SGD steps at bs 1 x the long sequence (the flash
    kernels' raised VMEM limit matters here), timed two ways: ended by
    block_until_ready, and ended by a scalar readback as a round's driver
    does."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from fedml_tpu.llm.federated import llm_config_from_args
    from fedml_tpu.llm.model import init_llm
    from fedml_tpu.llm.trainer import CausalLMTrainer

    m = sz["llm"]
    cfg = llm_config_from_args(_llm_args(
        sz, llm_max_seq_len=m["long_seq"], llm_vocab_size=m["long_vocab"]))
    if on_chip():
        check(cfg.attention_impl == "flash", cfg.attention_impl)
    model, params = init_llm(cfg, jax.random.PRNGKey(0))
    spec = CausalLMTrainer(
        lambda p, x, rng=None, train=False: model.apply(
            {"params": p}, x, train=train))
    tok = jax.random.randint(jax.random.PRNGKey(1), (1, m["long_seq"] + 1),
                             0, cfg.vocab_size)
    batch = {"x": tok[:, :-1], "y": tok[:, 1:],
             "mask": jnp.ones((1,), jnp.float32)}
    tx = optax.sgd(1e-2)

    @jax.jit
    def step(params, batch):
        (loss, _), grads = jax.value_and_grad(spec.loss, has_aux=True)(
            params, batch, None)
        updates, _ = tx.update(grads, tx.init(params), params)
        return optax.apply_updates(params, updates), loss

    t0 = time.perf_counter()
    params, loss = step(params, batch)
    losses = [float(loss)]
    first_s = time.perf_counter() - t0
    ready, readback = [], []
    for i in range(2 * m["long_steps"]):
        t0 = time.perf_counter()
        params, loss = step(params, batch)
        if i % 2:
            jax.block_until_ready((params, loss))
            ready.append(time.perf_counter() - t0)
        else:   # a scalar read back from the new params
            float(jax.tree_util.tree_leaves(params)[0].sum())
            readback.append(time.perf_counter() - t0)
            jax.block_until_ready(params)   # drain before the next step
        losses.append(float(loss))
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    dt = statistics.median(ready)
    return {"seq": m["long_seq"], "attention_impl": cfg.attention_impl,
            "loss_first_last": [round(losses[0], 4), round(losses[-1], 4)],
            **chip_timings(
                first_step_s=round(first_s, 2),
                step_s_block_until_ready=round(dt, 5),
                step_s_scalar_readback=round(statistics.median(readback), 5),
                sync_agree_ratio=round(statistics.median(readback) / dt, 3),
                tokens_per_s=round(m["long_seq"] / dt, 0))}


def phase_serving(sz, keep):
    """The LoRA adapters just trained, served by the continuous-batching
    engine: concurrent generate calls of different prompt lengths."""
    from fedml_tpu.core import mlops
    from fedml_tpu.serving.llm_template import CausalLMPredictor

    s = sz["serve"]
    mlops.install_compile_counter()
    predictor = CausalLMPredictor(
        keep["bundle"], keep["adapters"], mode="batch",
        batch_opts={"slots": s["slots"], "watchdog_s": 600.0,
                    "request_timeout_s": 900.0})
    try:
        text = "To be, or not to be, that is the question: " * 20
        prompts = [text[:n] for n in s["prompt_chars"]]
        t0 = time.perf_counter()
        warm = predictor.generate(prompts[1], max_new_tokens=s["max_new"],
                                  temperature=0.0, seed=1)
        warm_s = time.perf_counter() - t0
        c0 = mlops.compile_count()
        t0 = time.perf_counter()
        with cf.ThreadPoolExecutor(len(prompts)) as pool:
            futs = [pool.submit(predictor.generate, p,
                                max_new_tokens=s["max_new"],
                                temperature=0.0, seed=1) for p in prompts]
            outs = [f.result(timeout=900) for f in futs]
        batch_s = time.perf_counter() - t0
        recompiles = mlops.compile_count() - c0
        steps = predictor.engine.scheduler.steps_run
    finally:
        predictor.close()
    for p, o in zip(prompts, outs):
        check(o["prompt_tokens"] == len(p.encode()) + 2,  # BOS .. SEP
              f"prompt of {len(p)} chars counted {o['prompt_tokens']}")
        check(o["finish_reason"] in ("length", "stop"), o["finish_reason"])
        check(o["completion_tokens"] == s["max_new"]
              or o["finish_reason"] == "stop",
              f"{o['completion_tokens']} tokens, {o['finish_reason']}")
    check(sum(o["completion_tokens"] for o in outs) > 0, "no tokens decoded")
    check(outs[1]["text"] == warm["text"],
          "same prompt, same seed, different text")
    check(recompiles == 0,
          f"{recompiles} compiles after the first request warmed the engine")
    return {"requests": len(outs),
            "prompt_tokens": [o["prompt_tokens"] for o in outs],
            "completion_tokens": [o["completion_tokens"] for o in outs],
            "decode_steps": steps, "steady_state_compiles": recompiles,
            **chip_timings(first_request_s=round(warm_s, 2),
                           concurrent_batch_s=round(batch_s, 3))}


def phase_kernels(sz):
    """Kept kernels against their references, values and gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.core.kernels.conv_block import fused_block, reference_block
    from fedml_tpu.llm.attention import (dense_causal_attention,
                                         flash_causal_attention)

    def grads_of(fn, *xs):
        """Gradients under ONE fixed random cotangent, so both sides are
        asked the same question whatever their forward rounding."""
        out = jax.eval_shape(fn, *xs)
        ct = jax.random.normal(jax.random.PRNGKey(9), out.shape, jnp.float32)
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * ct),
            argnums=tuple(range(len(xs)))))(*xs)

    def ms(fn, *xs):
        return round(1e3 * statistics.median(round_trips(fn, *xs, n=20)), 3)

    def worst(got, want):
        got, want = (np.asarray(a, np.float32) for a in (got, want))
        check(np.isfinite(got).all(), "non-finite kernel output")
        return float(np.max(np.abs(got - want)) / (np.max(np.abs(want))
                                                   + 1e-6))

    out = {}
    for shape in sz["flash_shapes"]:
        q, k, v = (jax.random.normal(jax.random.PRNGKey(i), shape,
                                     jnp.bfloat16) for i in range(3))
        errs = [worst(jax.jit(flash_causal_attention)(q, k, v),
                      jax.jit(dense_causal_attention)(q, k, v))]
        errs += [worst(g, w) for g, w in zip(
            grads_of(flash_causal_attention, q, k, v),
            grads_of(dense_causal_attention, q, k, v))]
        check(max(errs) < 0.03, f"flash vs dense at {shape}: {errs}")
        out["flash_rel_err_" + "x".join(map(str, shape))] = round(
            max(errs), 5)

    # the ResNet-56 stages: 32x32x16, 16x16x32, 8x8x64 and the two strided
    # transitions between them
    for hw, cin, c, strides in ((32, 16, 16, 1), (16, 32, 32, 1),
                                (8, 64, 64, 1), (32, 16, 32, 2),
                                (16, 32, 64, 2)):
        ks = jax.random.split(jax.random.PRNGKey(hw + c), 8)
        p = {"w1": jax.random.normal(ks[0], (3, 3, cin, c)) * 0.1,
             "w2": jax.random.normal(ks[1], (3, 3, c, c)) * 0.1}
        for i, g in enumerate(("g1", "g2")):
            p[g + "_scale"] = 1 + 0.1 * jax.random.normal(ks[2 + i], (c,))
            p[g + "_bias"] = 0.1 * jax.random.normal(ks[4 + i], (c,))
        if strides == 2:
            p["wp"] = jax.random.normal(ks[6], (1, 1, cin, c)) * 0.1
            p["gp_scale"] = jnp.ones((c,))
            p["gp_bias"] = jnp.zeros((c,))
        # the model's bf16 path casts params and activations alike
        p = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p)
        x = jax.random.normal(ks[7], (sz["conv_batch"], hw, hw, cin),
                              jnp.bfloat16)
        fused = lambda x, p: fused_block(x, p, strides=strides)  # noqa
        ref = lambda x, p: reference_block(x, p, strides=strides)  # noqa
        errs = [worst(jax.jit(fused)(x, p), jax.jit(ref)(x, p))]
        gx, _ = grads_of(fused, x, p)
        wx, _ = grads_of(ref, x, p)
        errs.append(worst(gx, wx))
        check(max(errs) < 0.03,
              f"fused vs reference block at {hw}x{hw}x{cin}->{c}: {errs}")
        out[f"conv_rel_err_{hw}x{hw}x{cin}to{c}"] = round(max(errs), 5)
        if (hw, c) == (32, 16) and on_chip():   # the widest activations
            out["conv_32x32x16_fwd_ms_fused_vs_reference"] = [
                ms(fused, x, p), ms(ref, x, p)]
    return out


def _kda_operands(shape, lo, seed):
    """bfloat16 q, k (unit rows, q at ``d ** -0.5``) and v, float32
    log-decays all over ``(lo, 0)``, beta, and a float32 cotangent of the
    output; with the pulled-back gradients' function and a relative
    distance that refuses a non-finite result."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    b, s, h, d = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)  # noqa
    q = (unit(jax.random.normal(ks[0], (b, s, h, d))) * d ** -0.5
         ).astype(jnp.bfloat16)
    k = unit(jax.random.normal(ks[1], (b, s, h, d))).astype(jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.bfloat16)
    g = jax.random.uniform(ks[3], (b, s, h, d), minval=lo, maxval=0.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    ct = jax.random.normal(ks[5], (b, s, h, d), jnp.float32)

    def grads(fn):
        return jax.grad(
            lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * ct),
            argnums=(0, 1, 2, 3, 4))

    def gap(a, w):
        a, w = (np.asarray(x, np.float32) for x in (a, w))
        check(np.isfinite(a).all(), "non-finite KDA result")
        return float(np.linalg.norm(a - w) / (np.linalg.norm(w) + 1e-6))

    return (q, k, v, g, beta), grads, gap


def phase_kda(sz):
    """The chunked delta rule (the Pallas kernels on a chip, interpreted
    elsewhere) forward and backward against the token recurrence, on
    bfloat16 operands with log-decays all over (-5, 0); then at the KDA
    cells' shape against the same step under the ``dense`` scan (the
    recurrence's pull-back would hold every token's state), and on a chip
    the host-timed ms a call of the kernels with their XLA glue, forward
    and forward + backward."""
    import jax

    from fedml_tpu.llm.linear_attention import kda_attention, kda_recurrence

    xs, grads, gap = _kda_operands(sz["kda_shape"], -5.0, 11)
    kernels = functools.partial(kda_attention, impl="flash")
    errs = [gap(jax.jit(kernels)(*xs), jax.jit(kda_recurrence)(*xs))]
    errs += [gap(a, w) for a, w in zip(jax.jit(grads(kernels))(*xs),
                                       jax.jit(grads(kda_recurrence))(*xs))]
    check(max(errs) < 0.06, f"KDA kernels vs the recurrence: {errs}")
    out = {"kda_rel_err_" + "x".join(map(str, sz["kda_shape"])):
           round(max(errs), 5)}
    if on_chip():
        out["kda_fwd_bwd_ms_kernels_vs_recurrence"] = [
            round(1e3 * statistics.median(round_trips(grads(f), *xs, n=5)), 3)
            for f in (kernels, kda_recurrence)]
    shape = "x".join(map(str, sz["kda_cell_shape"]))
    xs, grads, gap = _kda_operands(sz["kda_cell_shape"], -5.0, 12)
    dense = functools.partial(kda_attention, impl="dense")
    errs = [gap(jax.jit(kernels)(*xs), jax.jit(dense)(*xs))]
    errs += [gap(a, w) for a, w in zip(jax.jit(grads(kernels))(*xs),
                                       jax.jit(grads(dense))(*xs))]
    check(max(errs) < 0.02, f"KDA kernels vs the dense step: {errs}")
    out["kda_rel_err_" + shape] = round(max(errs), 6)
    if on_chip():
        out["kda_ms_fwd_and_fwd_bwd_" + shape] = [
            round(1e3 * statistics.median(round_trips(f, *xs, n=7)), 3)
            for f in (kernels, grads(kernels))]
    return out


def phase_kda_softplus(sz):
    """The chunk step of the unbounded gate (each sub-chunk's block against
    itself element by element) in the Pallas kernels (on a chip; interpreted
    elsewhere) against the same step under the ``dense`` scan, forward and
    the gradients toward q, k, v, the log-decay and beta, on bfloat16
    operands with log-decays all over (-20, 0), at the unbounded-gate
    cell's shape. On a chip also the host-timed ms a call of the kernels
    with their XLA glue, forward and forward + backward, exact form against
    the bounded form's factorised one at the same shapes, whose result is
    wrong at these decays: its distance to the dense step is reported."""
    import jax

    from fedml_tpu.llm.linear_attention import kda_attention

    b, s, h, d = sz["kda_softplus_shape"]
    xs, grads, gap = _kda_operands((b, s, h, d), -20.0, 13)

    def form(impl, unbounded=True):
        return functools.partial(kda_attention, impl=impl,
                                 unbounded=unbounded)

    kernels, dense = form("flash"), form("dense")
    errs = [gap(jax.jit(kernels)(*xs), jax.jit(dense)(*xs))]
    errs += [gap(a, w) for a, w in zip(jax.jit(grads(kernels))(*xs),
                                       jax.jit(grads(dense))(*xs))]
    check(max(errs) < 0.02, f"unbounded KDA kernels vs the dense step: {errs}")
    bounded = form("flash", False)
    out = {"kda_softplus_rel_err_" + "x".join(map(str, (b, s, h, d))):
           round(max(errs), 6),
           # what the bounded form's factorised blocks give at these decays
           "kda_bounded_form_rel_err": round(
               gap(jax.jit(bounded)(*xs), jax.jit(dense)(*xs)), 6)}
    if on_chip():
        for name, fn in (("exact", kernels), ("bounded", bounded)):
            out[f"kda_{name}_ms_fwd_and_fwd_bwd"] = [
                round(1e3 * statistics.median(round_trips(f, *xs, n=7)), 3)
                for f in (fn, grads(fn))]
    return out


def phase_ssd(sz):
    """The state-space kernels (Pallas on a chip, interpreted elsewhere) at
    the state-space cell's shape, forward and the gradients toward x, the
    step sizes, B and C against the dense form (the same chunk step under
    a scan), on bfloat16 operands whose decays ``delta A`` cover -1.6 to
    -0.001 a step; by the norms of the output and of every gradient."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.llm.state_space import ssd_scan

    b, s, h, p, g, n = sz["ssd_shape"]
    ks = jax.random.split(jax.random.PRNGKey(17), 6)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.bfloat16)
    bm = (0.5 * jax.random.normal(ks[1], (b, s, g, n))).astype(jnp.bfloat16)
    cm = (0.5 * jax.random.normal(ks[2], (b, s, g, n))).astype(jnp.bfloat16)
    a = -jnp.exp(jnp.linspace(0.0, math.log(16.0), h))
    dt = jnp.exp(jax.random.uniform(ks[3], (b, s, h), minval=math.log(1e-3),
                                    maxval=math.log(0.1)))
    ct = jax.random.normal(ks[4], (b, s, h, p), jnp.float32)
    xs = (x, dt, a, bm, cm, jnp.ones((h,)))

    def both(impl):
        def fn(*xs):
            def loss(*xs):
                out = ssd_scan(*xs, impl=impl)
                return jnp.sum(out.astype(jnp.float32) * ct), out
            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 3, 4), has_aux=True)(*xs)
            return (out,) + grads
        return jax.jit(fn)

    def gap(a, w):
        a, w = (np.asarray(t, np.float32) for t in (a, w))
        check(np.isfinite(a).all(), "non-finite state-space result")
        return float(np.linalg.norm(a - w) / (np.linalg.norm(w) + 1e-6))

    got, want = both("flash")(*xs), both("dense")(*xs)
    errs = [gap(a, w) for a, w in zip(got, want)]
    check(max(errs) < 0.02, f"SSD kernels vs the dense form: {errs}")
    out = {"ssd_rel_err_" + "x".join(map(str, sz["ssd_shape"])):
           round(max(errs), 5),
           "ssd_norms_out_dx_ddt_db_dc": [
               round(float(jnp.linalg.norm(t.astype(jnp.float32))), 3)
               for t in got]}
    if on_chip():
        fwd = jax.jit(functools.partial(ssd_scan, impl="flash"))
        ms = [1e3 * statistics.median(round_trips(f, *xs, n=5))
              for f in (fwd, both("flash"))]
        out["ssd_ms_fwd_and_fwd_bwd"] = [round(t, 3) for t in ms]
    out.update(_ssm_passes(sz, gap))
    return out


def _ssm_passes(sz, gap):
    """The four passes around the kernels (``ssm_layer``) against XLA's
    form of the same work around the same kernels, at the same shape with
    a mask: the output and the ``in_proj`` product's cotangent, bfloat16
    as the cell holds it; the convolution and step sizes drawn as the
    benchmark's reference draws them."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.llm.state_space import ssm_layer

    b, s, h, p, g, n = sz["ssd_shape"]
    inner, wide = h * p, h * p + 2 * g * n
    ks = jax.random.split(jax.random.PRNGKey(23), 8)
    bf = jnp.bfloat16
    zx = jax.random.normal(ks[0], (b, s, inner + wide + h), bf)
    dt = jnp.exp(jax.random.uniform(ks[1], (h,), minval=math.log(1e-3),
                                    maxval=math.log(0.1)))
    params = ((0.5 * jax.random.normal(ks[2], (4, wide))).astype(bf),
              jax.random.uniform(ks[3], (wide,), minval=-0.1,
                                 maxval=0.1).astype(bf),
              jnp.log(jax.random.uniform(ks[4], (h,), minval=1.0,
                                         maxval=16.0)).astype(bf),
              jnp.ones((h,), bf),
              (dt + jnp.log(-jnp.expm1(-dt))).astype(bf),
              (1 + 0.1 * jax.random.normal(ks[5], (inner,))).astype(bf))
    mask = jnp.ones((b, s), jnp.int32).at[:, s // 3:s // 3 + 5].set(0)
    ct = jax.random.normal(ks[6], (b, s, inner), jnp.float32)

    def layer(fused):
        def fn(zx):
            def loss(zx):
                out = ssm_layer(zx, mask, *params, heads=h, head_dim=p,
                                groups=g, state=n, eps=1e-5, fused=fused)
                return jnp.sum(out.astype(jnp.float32) * ct), out
            (_, out), dzx = jax.value_and_grad(loss, has_aux=True)(zx)
            return out, dzx
        return jax.jit(fn)

    got, want = layer(True)(zx), layer(False)(zx)
    errs = [gap(a, w) for a, w in zip(got, want)]
    check(max(errs) < 0.01, f"SSM passes vs XLA's form: {errs}")
    out = {"ssm_passes_rel_err_out_dzx": [round(e, 6) for e in errs]}
    if on_chip():
        out["ssm_layer_fwd_bwd_ms_passes_vs_xla"] = [
            round(1e3 * statistics.median(round_trips(layer(f), zx, n=5)), 3)
            for f in (True, False)]
    return out


def phase_window(sz):
    """The window kernels with a sink (Pallas on a chip, interpreted
    elsewhere) at the window cell's shape, taking the 8 key-value heads as
    the model hands them (a grid step is one of them and its 8 query
    heads), forward and the four gradients against the dense path on
    repeated heads (one key-value head's query heads at a time: the dense
    scores of all 64 would not fit), on bfloat16 operands; beside them the
    time of the causal kernels at the same shape, on repeated heads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.llm.attention import causal_attention

    b, s, h, kv, d_qk, d_v, window = sz["window_shape"]
    rep = h // kv
    ks = jax.random.split(jax.random.PRNGKey(13), 5)
    q = jax.random.normal(ks[0], (b, s, h, d_qk), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, kv, d_qk), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, kv, d_v), jnp.bfloat16)
    sink = math.log(window) + jax.random.uniform(ks[3], (h,), minval=-1.0,
                                                 maxval=1.0)
    ct = jax.random.normal(ks[4], (b, s, h, d_v), jnp.float32)

    def attend(impl, **kw):
        grouped = impl == "flash" and kw

        def fn(q, k, v, sink, ct):
            def loss(q, k, v, sink):
                if not grouped:
                    k, v = (jnp.repeat(a, q.shape[2] // a.shape[2], axis=2)
                            for a in (k, v))
                out = causal_attention(q, k, v, impl=impl,
                                       sink=sink if kw else None, **kw)
                return jnp.sum(out.astype(jnp.float32) * ct), out
            (_, out), g = jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                             has_aux=True)(q, k, v, sink)
            return (out,) + g
        return jax.jit(fn)

    flash = "flash" if on_chip() else "dense"
    got = attend(flash, window=window)(q, k, v, sink, ct)
    dense = attend("dense", window=window)
    parts = [dense(q[:, :, g * rep:(g + 1) * rep], k[:, :, g:g + 1],
                   v[:, :, g:g + 1], sink[g * rep:(g + 1) * rep],
                   ct[:, :, g * rep:(g + 1) * rep]) for g in range(kv)]
    want = [jnp.concatenate([p[i] for p in parts], 2 if i < 4 else 0)
            for i in range(5)]

    def gap(a, w):
        a, w = (np.asarray(x, np.float32) for x in (a, w))
        check(np.isfinite(a).all(), "non-finite window-attention result")
        return float(np.linalg.norm(a - w) / (np.linalg.norm(w) + 1e-6))

    errs = [gap(a, w) for a, w in zip(got, want)]
    check(max(errs) < 0.03, f"window kernels vs the dense path: {errs}")
    out = {"window_rel_err_" + "x".join(map(str, sz["window_shape"])):
           round(max(errs), 5),
           "window_norms_out_dq_dk_dv_dsink": [
               round(float(jnp.linalg.norm(a.astype(jnp.float32))), 3)
               for a in got]}
    if on_chip():
        out["fwd_bwd_ms_window_vs_causal"] = [
            round(1e3 * statistics.median(round_trips(
                f, q, k, v, sink, ct, n=5)), 3)
            for f in (attend("flash", window=window), attend("flash"))]
    return out


def phase_four_chip_llm(sz):
    """The two LLM programs __graft_entry__._dryrun_llm_sharded runs at
    h32, here at full width: the {data 1, fsdp 2, tensor 2} train step and
    the sp=4 ring-attention forward. Parameters and activations must
    actually be spread over the four devices."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from fedml_tpu.core.mesh import build_mesh
    from fedml_tpu.llm import CausalLM, CausalLMTrainer, LLMConfig, init_llm
    from fedml_tpu.llm.sharding import (llm_param_specs, make_ring_forward,
                                        make_sharded_train_step,
                                        shard_llm_params)

    m = sz["llm"]
    cfg = LLMConfig(vocab_size=m["long_vocab"], hidden_size=m["hidden"],
                    intermediate_size=m["inter"], num_layers=m["layers"],
                    num_heads=m["heads"], max_seq_len=m["seq"],
                    dtype="bfloat16", tie_embeddings=False)
    mesh = build_mesh({"data": 1, "fsdp": 2, "tensor": 2})
    model, params = init_llm(cfg, jax.random.PRNGKey(0))
    spec = CausalLMTrainer(
        lambda p, x, rng=None, train=False: model.apply({"params": p}, x))
    tok = jax.random.randint(jax.random.PRNGKey(1),
                             (m["batch"], m["seq"] + 1), 0, cfg.vocab_size)
    batch = {"x": tok[:, :-1], "y": tok[:, 1:], "mask": jnp.ones(m["batch"])}
    opt = optax.sgd(1e-2)
    with mesh:
        sharded = shard_llm_params(params, mesh)
        step = make_sharded_train_step(
            lambda p, b, r: spec.loss(p, b, r), opt, mesh,
            llm_param_specs(params, mesh))
        state, losses = opt.init(sharded), []
        for _ in range(3):
            sharded, state, loss = step(sharded, state, batch,
                                        jax.random.PRNGKey(0))
            losses.append(float(loss))
    check(all(np.isfinite(losses)) and losses[-1] < losses[0],
          f"sharded step losses {losses}")
    gate = sharded["layer_0"]["mlp"]["gate"]["kernel"]
    shard_shape = gate.addressable_shards[0].data.shape
    check(len(gate.sharding.device_set) == 4
          and not gate.sharding.is_fully_replicated
          and np.prod(shard_shape) * 4 == np.prod(gate.shape),
          f"gate kernel {gate.shape} sharded as {shard_shape} over "
          f"{len(gate.sharding.device_set)} devices")

    ring_cfg = dataclasses.replace(cfg, attention_impl="ring",
                                   num_layers=1, max_seq_len=sz["ring_seq"])
    ring_model = CausalLM(ring_cfg)
    _, ring_params = init_llm(
        dataclasses.replace(ring_cfg, attention_impl="dense"),
        jax.random.PRNGKey(0))
    fwd = make_ring_forward(
        lambda p, t, mk: ring_model.apply({"params": p}, t, attn_mask=mk),
        build_mesh({"sp": 4}))
    logits = fwd(ring_params, jnp.zeros((1, sz["ring_seq"]), jnp.int32))
    logits.block_until_ready()
    check(logits.shape == (1, sz["ring_seq"], cfg.vocab_size), logits.shape)
    check(bool(jnp.isfinite(logits).all()), "non-finite ring logits")
    ring_shard = logits.addressable_shards[0].data.shape
    check(len(logits.sharding.device_set) == 4
          and ring_shard[1] * 4 == sz["ring_seq"],
          f"ring logits shard {ring_shard} over "
          f"{len(logits.sharding.device_set)} devices")
    return {"sharded_step_mesh": dict(mesh.shape),
            "sharded_step_losses": [round(x, 4) for x in losses],
            "gate_kernel_shard": list(shard_shape),
            "ring_logits_shard": list(ring_shard),
            "bytes_in_use_mib": bytes_in_use_mib()}


# ------------------------------------------------------------------ main ----

def phase_sparse(sz):
    """The sparse attention kernels (Pallas on a chip, interpreted
    elsewhere) at the Keye cell's shape, on bfloat16 operands: the attention
    kernels' output against the dense path on the first and last rows of
    one key-value head's group, on the index kernel's selection; at
    ``sparse_grad_seq`` positions (where the dense path's scores fit) that
    selection against the dense path's from the same inputs (the share of
    (query, key) bits apart: the two sum the same products in their own
    orders, so a near tie may fall either way) and the gradients against
    the dense path; and the kernels' times at the cell's shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.llm import sparse_attention as sa

    b, s, h, kv, d, ih, idim, topk = sz["sparse_shape"]
    flash = "flash" if on_chip() else "dense"

    def operands(s, seed):
        ks = jax.random.split(jax.random.PRNGKey(seed), 7)
        bf = jnp.bfloat16
        return (jax.random.normal(ks[0], (b, s, ih, idim), bf),
                jax.random.normal(ks[1], (b, s, idim), bf),
                jax.random.normal(ks[2], (b, s, ih), jnp.float32) * 0.03,
                jax.random.normal(ks[3], (b, s, h, d), bf),
                jax.random.normal(ks[4], (b, s, kv, d), bf),
                jax.random.normal(ks[5], (b, s, kv, d), bf),
                jax.random.normal(ks[6], (b, s, h, d), jnp.float32))

    qi, ki, w, q, k, v, ct = operands(s, 17)
    select = jax.jit(functools.partial(sa.select_keys, topk=topk,
                                       impl=flash))
    words = select(qi, ki, w)
    attend = jax.jit(lambda q, k, v, wd: sa.sparse_attend(
        q, k, v, wd, topk, impl=flash))
    got = attend(q, k, v, words)
    rep, rows = h // kv, min(512, s)
    keep = sa.unpack(words, s)

    def dense_rows(lo):
        """The first key-value head's group on rows ``lo .. lo + rows``."""
        qs = q[:, lo:lo + rows, :rep].astype(jnp.float32)
        scores = jnp.einsum("bqhd,bkd->bhqk", qs,
                            k[:, :, 0].astype(jnp.float32)) * d ** -0.5
        scores = jnp.where(keep[:, None, lo:lo + rows], scores, -1e30)
        probs = jax.nn.softmax(scores, -1)
        return jnp.einsum("bhqk,bkd->bqhd", probs,
                          v[:, :, 0].astype(jnp.float32))

    def gap(a, want):
        a, want = (np.asarray(x, np.float32) for x in (a, want))
        check(np.isfinite(a).all(), "non-finite sparse-attention result")
        return float(np.linalg.norm(a - want) / (np.linalg.norm(want)
                                                + 1e-6))

    errs = [gap(got[:, lo:lo + rows, :rep], jax.jit(dense_rows,
                                                     static_argnums=0)(lo))
            for lo in (0, s - rows)]
    gs = sz["sparse_grad_seq"]
    qi2, ki2, w2, q2, k2, v2, ct2 = operands(gs, 19)
    words2 = jax.jit(functools.partial(sa.select_keys, topk=topk,
                                       impl="dense"))(qi2, ki2, w2)
    apart = int(jnp.sum(jax.lax.population_count(select(qi2, ki2, w2)
                                                 ^ words2)))
    kept = sa.selected(gs, topk)
    check(apart <= 1e-3 * kept, f"index kernel vs the dense selection: "
          f"{apart} bits apart of {kept}")

    def grads(impl):
        def loss(q, k, v):
            out = sa.sparse_attend(q, k, v, words2, topk, impl=impl)
            return jnp.sum(out.astype(jnp.float32) * ct2)
        return jax.jit(jax.grad(loss, (0, 1, 2)))(q2, k2, v2)

    errs += [gap(a, want) for a, want in zip(grads(flash), grads("dense"))]
    check(max(errs) < 0.03, f"sparse kernels vs the dense path: {errs}")
    out = {"sparse_select_bits_apart": apart, "sparse_selected": kept,
           "sparse_rel_err_out_first_last_dq_dk_dv": [round(e, 5)
                                                      for e in errs]}
    if on_chip():
        def fwd_bwd(q, k, v, wd):
            return jax.grad(lambda q, k, v: jnp.sum(sa.sparse_attend(
                q, k, v, wd, topk, impl="flash").astype(jnp.float32)
                * ct), (0, 1, 2))(q, k, v)
        out["sparse_ms_select_fwd_fwd_bwd"] = [
            round(1e3 * statistics.median(round_trips(f, *xs, n=5)), 3)
            for f, xs in ((select, (qi, ki, w)), (attend, (q, k, v, words)),
                          (fwd_bwd, (q, k, v, words)))]
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on any backend; never prints a pass")
    ap.add_argument("--only", default="",
                    help="comma-separated phases to run alone (never "
                         "prints a pass): e.g. ssd,kda")
    opts = ap.parse_args()

    import jax

    import fedml_tpu  # noqa: F401  (places the compile cache at import)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if not on_chip() and not opts.rehearse:
        print(f"chip_smoke: no accelerator — JAX reports {device}; "
              "refusing to run the device phases on it", file=sys.stderr)
        return 1
    sz = TOY if opts.rehearse else FULL

    def on_duration(event, secs, **_):
        if event == _COMPILE_EVENT:
            _compile["n"] += 1
            _compile["s"] += secs

    def on_event(event, **_):
        if event == _CACHE_HIT_EVENT:
            _compile["hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    cache_dir, entries_before = cache_entries()
    say(phase="device", ok=True, device=device, rehearsal=opts.rehearse,
        versions={p: importlib.metadata.version(p)
                  for p in ("jax", "jaxlib", "libtpu", "flax")},
        compile_cache_dir=cache_dir, cache_entries_before=entries_before)

    keep = {}
    phases = [("empty_dispatch", phase_dispatch, ()),
              ("round_engine", phase_round_engine, (sz,)),
              ("llm_lora_rounds", phase_llm_lora_rounds, (sz, keep)),
              ("llm_long_context", phase_llm_long_context, (sz,)),
              ("serving", phase_serving, (sz, keep)),
              ("kernels", phase_kernels, (sz,)),
              ("kda", phase_kda, (sz,)),
              ("kda_softplus", phase_kda_softplus, (sz,)),
              ("ssd", phase_ssd, (sz,)),
              ("window", phase_window, (sz,)),
              ("sparse", phase_sparse, (sz,))]
    if len(devices) == 4:
        phases.append(("four_chip_llm", phase_four_chip_llm, (sz,)))
    only = set(filter(None, opts.only.split(",")))
    unknown = only - {name for name, _, _ in phases}
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")
    for name, fn, fn_args in phases:
        if name == "kernels":
            keep.clear()
        if not only or name in only:
            run_phase(name, fn, *fn_args)
    say(phase="compile_cache", ok=True, compile_cache_dir=cache_dir,
        cache_entries_before=entries_before,
        cache_entries_after=cache_entries()[1],
        compiles=_compile["n"], compile_s=round(_compile["s"], 2),
        cache_hits=_compile["hits"])

    if only:
        print(f"chip_smoke: ran {sorted(only)} alone on {device}; this is "
              "not a pass", file=sys.stderr)
        return 3
    if opts.rehearse:
        print("chip_smoke: rehearsal finished — every phase ran at toy "
              f"size on {device}; this is not a pass", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
