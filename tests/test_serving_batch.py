"""Continuous-batching LLM serving (ISSUE 9): paged-KV decode bit-parity
vs the full-forward step, compile-once across concurrency/adapter mix,
admit/evict determinism, multi-LoRA adapter isolation, tail truncation,
per-request seeds, gateway p50/p99, and the chat endpoint under
concurrent clients.

Tier-1 except the HTTP/replica/soak tests (slow-marked): the core
correctness claims — parity, compile-once, determinism, isolation — run
in the quick gate.
"""

import concurrent.futures as cf
import threading
import time

import numpy as np
import pytest

from fedml_tpu.arguments import Arguments
from fedml_tpu.core.obs import metrics as obs_metrics
from fedml_tpu.llm.federated import build_llm
from fedml_tpu.serving.llm_template import (CausalLMPredictor,
                                            ChatCompletionRunner)

pytestmark = pytest.mark.serving


def _args(**kw):
    base = dict(dataset="llm_synthetic", model="causal_lm",
                client_num_in_total=2, client_num_per_round=2,
                comm_round=1, epochs=1, batch_size=4, learning_rate=1e-3,
                random_seed=3, llm_hidden_size=32, llm_num_layers=2,
                llm_num_heads=2, llm_intermediate_size=64,
                llm_max_seq_len=64, lora_rank=4)
    base.update(kw)
    return Arguments(**base)


def _rand_adapter(template, seed):
    """A LoRA tree with NONZERO lora_b (lora_init zeroes b, which would
    make every adapter a no-op and isolation vacuous)."""
    import jax
    import jax.numpy as jnp
    leaves, treedef = jax.tree_util.tree_flatten(template)
    key = jax.random.PRNGKey(seed)
    out = []
    for i, l in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        out.append(0.3 * jax.random.normal(k, l.shape, jnp.float32))
    return jax.tree_util.tree_unflatten(treedef, out)


@pytest.fixture(scope="module")
def lora_setup():
    """LoRA artifact (bundle.base_params frozen, params = adapter tree):
    the single path applies it through ``bundle.apply``, the batch path
    from the adapter bank, both as factored side paths — parity across
    that split is the acceptance pin."""
    import jax
    args = _args()
    _, bundle, _, tok = build_llm(args)
    params = bundle.init(jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))
    return args, bundle, params, tok


@pytest.fixture(scope="module")
def predictors(lora_setup):
    args, bundle, params, tok = lora_setup
    single = CausalLMPredictor(bundle, params, tokenizer=tok)
    batched = CausalLMPredictor(
        bundle, params, tokenizer=tok, mode="batch",
        batch_opts={"slots": 4, "block_size": 16, "prefill_chunk": 8})
    yield single, batched
    batched.close()


@pytest.fixture(scope="module")
def full_ft_setup():
    """Full fine-tune artifact (lora_rank=0): params ARE the model."""
    import jax
    args = _args(lora_rank=0)
    _, bundle, _, tok = build_llm(args)
    params = bundle.init(jax.random.PRNGKey(1), np.zeros((1, 8), np.int32))
    return args, bundle, params, tok


# ------------------------------------------------------------- parity ----

class TestKVParity:
    """Acceptance pin: paged-KV decode is bit-identical to the original
    full-forward step on the same artifact (greedy)."""

    PROMPTS = ["add 2 3", "echo hello world", "x",
               "subtract 19 4 and then explain"]

    def test_greedy_bit_parity_lora_artifact(self, predictors):
        single, batched = predictors
        for prompt in self.PROMPTS:
            a = single.generate(prompt, max_new_tokens=12)
            b = batched.generate(prompt, max_new_tokens=12)
            assert a["text"] == b["text"], prompt
            assert a["finish_reason"] == b["finish_reason"]
            assert a["completion_tokens"] == b["completion_tokens"]

    def test_greedy_bit_parity_full_ft_artifact(self, full_ft_setup):
        args, bundle, params, tok = full_ft_setup
        single = CausalLMPredictor(bundle, params, tokenizer=tok)
        batched = CausalLMPredictor(
            bundle, params, tokenizer=tok, mode="batch",
            batch_opts={"slots": 2, "block_size": 16, "prefill_chunk": 8})
        try:
            for prompt in self.PROMPTS[:3]:
                assert (single.generate(prompt, max_new_tokens=10)["text"]
                        == batched.generate(prompt,
                                            max_new_tokens=10)["text"])
        finally:
            batched.close()

    def test_batching_never_changes_a_request(self, predictors):
        """A seeded request's output is invariant to what else is in
        flight: solo == submitted alongside 3 concurrent neighbours."""
        _, batched = predictors
        solo = batched.generate("add 4 5", max_new_tokens=10,
                                temperature=1.2, seed=77)
        with cf.ThreadPoolExecutor(4) as ex:
            futs = [ex.submit(batched.generate, "add 4 5",
                              max_new_tokens=10, temperature=1.2, seed=77)]
            futs += [ex.submit(batched.generate, f"noise {i} blah blah",
                               max_new_tokens=10, temperature=0.8, seed=i)
                     for i in range(3)]
            crowded = futs[0].result(timeout=120)
        assert crowded["text"] == solo["text"]

    def test_step_hands_the_device_copies_of_its_mirrors(self, lora_setup):
        """Why the parity above failed now and then on a loaded machine:
        on the CPU backend ``jnp.asarray`` aliases a numpy buffer that
        lies on a 64-byte boundary, ``step()`` advanced the position
        mirror in place as soon as the step program's tokens were read,
        and a write program still running then wrote its K/V one
        position late. Mirrors on such a boundary make it certain."""
        from fedml_tpu.serving.batch import DecodeScheduler

        def aligned(a):
            buf = np.zeros(a.nbytes + 64, np.uint8)
            off = (-buf.ctypes.data) % 64
            out = buf[off:off + a.nbytes].view(a.dtype).reshape(a.shape)
            out[...] = a
            return out

        _, bundle, _, tok = lora_setup
        sched = DecodeScheduler(bundle.module, bundle.cfg,
                                bundle.base_params, None, slots=2,
                                block_size=16, prefill_chunk=8)
        mirrors = ("_tables", "_pos", "_active", "_aidx", "_last",
                   "_temp", "_seed")
        for name in mirrors:
            setattr(sched, name, aligned(getattr(sched, name)))
        sched.admit([1] + tok.encode("x") + [3], max_new_tokens=8)
        handed = {}
        dispatch = sched._dispatch

        def spy(name, fn, *args):
            handed[name] = [a for a in args if getattr(a, "ndim", 0)
                            and a.size <= sched._tables.size]
            return dispatch(name, fn, *args)

        sched._dispatch = spy
        before = sched._pos.copy()
        sched.step()
        assert (sched._pos != before).any()     # the mirror moved on
        host = [getattr(sched, name) for name in mirrors]
        for program in ("llm_decode_step", "llm_decode_write"):
            assert handed[program], program
            for arr in handed[program]:
                view = np.asarray(arr)
                assert not any(np.shares_memory(view, h) for h in host)
        pos_d = handed["llm_decode_write"][1]
        np.testing.assert_array_equal(np.asarray(pos_d), before)

    def test_single_mode_knob_keeps_old_path(self, predictors):
        single, _ = predictors
        assert single._engine is None  # no batch machinery constructed
        with pytest.raises(ValueError, match="batch"):
            single.generate("hi", adapter="silo_0")


# ------------------------------------------------------- compile-once ----

class TestCompileOnce:
    def test_decode_compiles_once_across_concurrency_and_adapters(
            self, lora_setup, xla_compile_counter):
        """Occupancy 1→S, admits/evicts, adapter mix, temps, and bank
        growth after warmup: all DATA — zero recompiles."""
        import jax
        from fedml_tpu.serving.batch import AdapterBank, DecodeScheduler

        args, bundle, params, tok = lora_setup
        bank = AdapterBank(params, alpha=bundle.lora_alpha, capacity=8)
        bank.add("a", _rand_adapter(params, 10))
        bank.add("b", _rand_adapter(params, 11))
        sched = DecodeScheduler(bundle.module, bundle.cfg,
                                bundle.base_params, bank, slots=4,
                                block_size=16, prefill_chunk=8)
        ids = [1] + tok.encode("warm up prompt") + [3]
        # warmup: compile prefill + first-token sample + decode step
        slot, _ = sched.admit(ids, adapter_idx=1, temperature=0.7, seed=5,
                              max_new_tokens=4)
        sched.step()
        sched.release(slot)
        xla_compile_counter.reset()
        # bank growth after warmup: capacity padding keeps shapes fixed
        bank.add("c", _rand_adapter(params, 12))
        prompts = ["x", "add 2 3",
                   "a longer prompt spanning chunks"]
        for occupancy in (1, 2, 4):
            slots = [sched.admit([1] + tok.encode(prompts[i % 3]) + [3],
                                 adapter_idx=(i % 4),
                                 temperature=float(i % 2), seed=i,
                                 max_new_tokens=4)[0]
                     for i in range(occupancy)]
            for _ in range(3):
                sched.step()
            for s in slots:
                sched.release(s)
        assert xla_compile_counter.delta() == 0


# ------------------------------------------- admit/evict determinism ----

class TestAdmitEvictDeterminism:
    def _run_sequence(self, lora_setup):
        from fedml_tpu.serving.batch import DecodeScheduler
        args, bundle, params, tok = lora_setup
        sched = DecodeScheduler(bundle.module, bundle.cfg,
                                bundle.base_params, None, slots=3,
                                block_size=16, prefill_chunk=8)
        trace = []
        enc = lambda p: [1] + tok.encode(p) + [3]  # noqa: E731
        s0, t0 = sched.admit(enc("alpha"), seed=1, max_new_tokens=8)
        s1, t1 = sched.admit(enc("beta"), seed=2, max_new_tokens=8)
        trace += [("admit", s0, t0), ("admit", s1, t1)]
        trace.append(("step", tuple(sorted(sched.step().items()))))
        sched.release(s0)
        trace.append(("free", tuple(sched.free_slots())))
        s2, t2 = sched.admit(enc("gamma gamma"), seed=3, max_new_tokens=8)
        trace += [("admit", s2, t2)]
        trace.append(("step", tuple(sorted(sched.step().items()))))
        trace.append(("tables", sched._tables.tolist()))
        return trace

    def test_same_sequence_same_slots_same_tokens(self, lora_setup):
        assert (self._run_sequence(lora_setup)
                == self._run_sequence(lora_setup))

    def test_released_slot_is_reused_lowest_first(self, lora_setup):
        from fedml_tpu.serving.batch import DecodeScheduler
        args, bundle, params, tok = lora_setup
        sched = DecodeScheduler(bundle.module, bundle.cfg,
                                bundle.base_params, None, slots=2,
                                block_size=16, prefill_chunk=8)
        ids = [1] + tok.encode("hi") + [3]
        a, _ = sched.admit(ids, max_new_tokens=4)
        b, _ = sched.admit(ids, max_new_tokens=4)
        assert (a, b) == (0, 1)
        assert not sched.can_admit(len(ids), 4)  # slots full
        sched.release(a)
        c, _ = sched.admit(ids, max_new_tokens=4)
        assert c == 0  # freed slot comes back, deterministically


# ------------------------------------------------- adapter isolation ----

class TestAdapterIsolation:
    @pytest.fixture(scope="class")
    def banked(self, lora_setup):
        args, bundle, params, tok = lora_setup
        batched = CausalLMPredictor(
            bundle, params, tokenizer=tok, mode="batch",
            batch_opts={"slots": 4, "block_size": 16, "prefill_chunk": 8,
                        "max_adapters": 8})
        batched.adapter_bank.add("siloA", _rand_adapter(params, 20))
        batched.adapter_bank.add("siloB", _rand_adapter(params, 21))
        yield batched
        batched.close()

    def test_adapters_actually_differ(self, banked):
        outs = {name: banked.generate("add 2 3", max_new_tokens=10,
                                      adapter=name)["text"]
                for name in ("siloA", "siloB", "base")}
        assert len(set(outs.values())) == 3, outs

    def test_routed_request_never_sees_other_adapter(self, banked):
        """Concurrent mixed-adapter batch: every request's output equals
        its solo run — adapter A's weights never leak into B's slots."""
        solo = {n: banked.generate("echo zq", max_new_tokens=10,
                                   adapter=n)["text"]
                for n in ("siloA", "siloB", "base")}
        names = ["siloA", "siloB", "base", "siloA"]
        with cf.ThreadPoolExecutor(4) as ex:
            futs = [ex.submit(banked.generate, "echo zq",
                              max_new_tokens=10, adapter=n)
                    for n in names]
            outs = [f.result(timeout=120) for f in futs]
        for n, o in zip(names, outs):
            assert o["text"] == solo[n], n

    def test_unknown_adapter_raises_not_silently_serves(self, banked):
        with pytest.raises(KeyError, match="unknown adapter"):
            banked.generate("hi", adapter="nonexistent_silo")

    def test_base_adapter_is_reserved(self, banked):
        with pytest.raises(ValueError, match="reserved"):
            banked.adapter_bank.add("base", _rand_adapter(
                banked.params, 30))

    def test_bank_capacity_enforced(self, lora_setup):
        from fedml_tpu.serving.batch import AdapterBank
        _, bundle, params, _ = lora_setup
        bank = AdapterBank(params, capacity=2)
        bank.add("one", _rand_adapter(params, 1))
        with pytest.raises(RuntimeError, match="full"):
            bank.add("two", _rand_adapter(params, 2))

    def test_adapter_request_without_bank_raises(self, full_ft_setup):
        """Full fine-tune batch mode has no bank: a named adapter must
        error, never silently serve the base model as someone's
        personalization."""
        args, bundle, params, tok = full_ft_setup
        batched = CausalLMPredictor(
            bundle, params, tokenizer=tok, mode="batch",
            batch_opts={"slots": 2, "block_size": 16, "prefill_chunk": 8})
        try:
            with pytest.raises(ValueError, match="no adapter bank"):
                batched.generate("hi", adapter="silo_0")
        finally:
            batched.close()

    def test_lora_stack_select_and_zero(self, lora_setup):
        """The lora.py bank primitives: stack N adapters into one [A,...]
        pytree, gather per-slot trees back out, and the content-free
        identity adapter."""
        import jax
        import jax.numpy as jnp
        from fedml_tpu.llm.lora import (lora_select, lora_stack,
                                        lora_zero_like)
        _, _, params, _ = lora_setup
        adapters = [params, _rand_adapter(params, 70),
                    lora_zero_like(params)]
        stack = lora_stack(adapters)
        for leaf, src in zip(jax.tree_util.tree_leaves(stack),
                             jax.tree_util.tree_leaves(params)):
            assert leaf.shape == (3,) + src.shape
        # scalar select returns adapter i exactly
        sel = lora_select(stack, jnp.int32(1))
        for a, b in zip(jax.tree_util.tree_leaves(sel),
                        jax.tree_util.tree_leaves(adapters[1])):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        # batched select gathers per-slot trees with a leading [S] axis
        batched = lora_select(stack, jnp.asarray([2, 0], jnp.int32))
        for leaf in jax.tree_util.tree_leaves(batched):
            assert leaf.shape[0] == 2
            assert float(jnp.abs(leaf[0]).sum()) == 0.0  # the zero row
        with pytest.raises(ValueError):
            lora_stack([])


# -------------------------------------------------- adapter artifacts ----

class TestAdapterArtifacts:
    def test_export_load_bank_round_trip(self, lora_setup, tmp_path):
        import jax
        from fedml_tpu.llm.federated import (load_adapter_artifacts,
                                             save_adapter_artifacts)
        from fedml_tpu.serving.batch import AdapterBank
        _, bundle, params, _ = lora_setup
        adapters = {"global": params,
                    "silo_0": _rand_adapter(params, 40),
                    "silo/../1": _rand_adapter(params, 41)}  # hostile name
        manifest = save_adapter_artifacts(adapters, str(tmp_path),
                                          lora_rank=4, lora_alpha=16.0)
        assert manifest.endswith("manifest.json")
        loaded = load_adapter_artifacts(str(tmp_path))
        assert set(loaded) == set(adapters)
        for name in adapters:
            a = jax.tree_util.tree_leaves(adapters[name])
            b = jax.tree_util.tree_leaves(loaded[name])
            assert all(np.array_equal(x, np.asarray(y))
                       for x, y in zip(a, b))
        bank = AdapterBank.from_artifacts(str(tmp_path))
        assert bank.has("global") and bank.has("silo_0")
        assert bank.index("silo_0") > 0

    def test_full_manifest_leaves_room_for_served_artifact(
            self, lora_setup, tmp_path):
        """A manifest that exactly fills the requested capacity must
        still leave a row for the predictor's own 'default' adapter
        (the off-by-one that would crash full-fleet deployments)."""
        from fedml_tpu.llm.federated import save_adapter_artifacts
        from fedml_tpu.serving.batch import AdapterBank
        _, _, params, _ = lora_setup
        save_adapter_artifacts(
            {f"silo_{i}": _rand_adapter(params, 80 + i)
             for i in range(3)}, str(tmp_path))
        bank = AdapterBank.from_artifacts(str(tmp_path), capacity=4)
        bank.add("default", params)  # what _build_engine does

    def test_hostile_names_stay_inside_the_dir(self, lora_setup, tmp_path):
        from fedml_tpu.llm.federated import save_adapter_artifacts
        _, _, params, _ = lora_setup
        out = tmp_path / "bank"
        save_adapter_artifacts({"../escape": params}, str(out))
        files = {p.name for p in out.iterdir()}
        assert files == {"manifest.json", ".._escape.fmtpu"}
        assert not (tmp_path / "escape.fmtpu").exists()


# --------------------------------------------------- engine behaviour ----

class TestEngine:
    def test_eight_concurrent_clients_four_slots(self, predictors):
        """More clients than slots: iteration-level scheduling drains the
        queue; every request resolves with a coherent finish."""
        _, batched = predictors
        with cf.ThreadPoolExecutor(8) as ex:
            outs = list(ex.map(
                lambda i: batched.generate(f"add {i} {i}",
                                           max_new_tokens=8),
                range(8)))
        assert all(o["finish_reason"] in ("stop", "length") for o in outs)
        assert all(o["completion_tokens"] <= 8 for o in outs)
        # identical prompts got identical greedy answers regardless of
        # admission order
        same = [batched.generate("add 3 3", max_new_tokens=8)["text"]
                for _ in range(2)]
        assert same[0] == same[1]

    def test_deadline_eviction_finishes_with_deadline(self, lora_setup):
        """Satellite (ISSUE 11): a deadline eviction resolves with
        finish_reason "deadline" — clients can tell "budget spent"
        ("length") apart from "truncated by the server"."""
        _, bundle, params, tok = lora_setup
        batched = CausalLMPredictor(
            bundle, params, tokenizer=tok, mode="batch",
            batch_opts={"slots": 2, "block_size": 16, "prefill_chunk": 8})
        try:
            evicted = obs_metrics.REGISTRY.counter(
                "llm_requests_evicted_total",
                labels=("reason",)).value(reason="deadline")
            fut = batched._engine.submit(
                [1] + tok.encode("a long story about") + [3],
                max_new_tokens=60, temperature=0.5, seed=9,
                deadline_s=0.05)
            out = fut.result(timeout=30)
            assert out["finish_reason"] == "deadline"
            assert out["completion_tokens"] < 60
            after = obs_metrics.REGISTRY.counter(
                "llm_requests_evicted_total",
                labels=("reason",)).value(reason="deadline")
            assert after >= evicted  # counted unless it raced to finish
        finally:
            batched.close()

    def test_infeasible_request_fails_fast_not_wedged(self, lora_setup):
        """A request whose worst-case KV reservation exceeds the whole
        pool must fail at submit, not sit unadmittable at the queue head
        blocking everyone behind it."""
        _, bundle, params, tok = lora_setup
        batched = CausalLMPredictor(
            bundle, params, tokenizer=tok, mode="batch",
            batch_opts={"slots": 2, "block_size": 16, "prefill_chunk": 8,
                        "num_blocks": 2})  # pool: 32 token positions
        try:
            big = batched._engine.submit(
                [1] + tok.encode("a prompt needing many blocks") + [3],
                max_new_tokens=40)
            with pytest.raises(ValueError, match="KV blocks"):
                big.result(timeout=5)
            # the queue is not wedged: a feasible request still serves
            small = batched._engine.submit([1, 90, 3], max_new_tokens=4)
            assert small.result(timeout=30)["finish_reason"] in (
                "stop", "length")
        finally:
            batched.close()

    def test_export_misconfig_fails_before_training(self, lora_setup):
        """lora_rank=0 + llm_adapter_export_dir must raise BEFORE the
        federated run, not discard a finished run's result."""
        from fedml_tpu.llm.federated import run_federated_llm
        args = _args(lora_rank=0)
        args.llm_adapter_export_dir = "/tmp/never_written"
        with pytest.raises(ValueError, match="lora_rank"):
            run_federated_llm(args)

    def test_stopped_engine_rejects_submissions(self, lora_setup):
        _, bundle, params, tok = lora_setup
        batched = CausalLMPredictor(
            bundle, params, tokenizer=tok, mode="batch",
            batch_opts={"slots": 2, "block_size": 16, "prefill_chunk": 8})
        eng = batched._engine
        batched.close()
        with pytest.raises(RuntimeError, match="stopped"):
            eng.submit([1, 5, 3], max_new_tokens=4)

    def test_serving_metrics_flow_to_registry(self, predictors):
        _, batched = predictors
        batched.generate("metrics probe", max_new_tokens=4)
        snap = obs_metrics.REGISTRY.snapshot()
        assert "llm_tokens_per_s" in snap
        assert "llm_slot_occupancy" in snap
        assert snap["llm_requests_admitted_total"]["values"][0]["value"] > 0


# ------------------------------------------------ prompt truncation ----

class TestPromptTruncation:
    def test_overlong_prompt_keeps_tail_and_reserves_room(self, predictors):
        """Regression (satellite 1): the old code kept
        ``ids[: max_seq_len - 1]`` — the HEAD — silently dropping the most
        recent chat turns, and left no room for the completion."""
        single, _ = predictors
        prompt = ("OLD" * 40) + " RECENT TAIL"
        ids = single._encode_prompt(prompt, max_new_tokens=16)
        assert len(ids) <= single.max_seq_len - 16
        tail = bytes(t - 4 for t in ids[-10:-1]).decode("latin-1")
        assert "NT TAIL" in tail  # the byte-tokenizer offset is +4
        out = single.generate(prompt, max_new_tokens=16)
        assert out["prompt_tokens"] <= single.max_seq_len - 16
        assert out["completion_tokens"] >= 1

    def test_short_prompt_untouched(self, predictors):
        single, _ = predictors
        ids = single._encode_prompt("hi", max_new_tokens=16)
        assert bytes(t - 4 for t in ids[1:-1]).decode("latin-1") == "hi"

    def test_batch_path_accepts_overlong_prompt(self, predictors):
        _, batched = predictors
        out = batched.generate("Z" * 500, max_new_tokens=8)
        assert out["finish_reason"] in ("stop", "length")


# ------------------------------------------------------ seeding ----

class TestSeeds:
    def test_default_seed_varies_per_request(self, predictors):
        """Satellite 2: no-seed sampled requests must not share one PRNG
        stream (the old ``seed=0`` default gave every user the same
        'sample')."""
        single, _ = predictors
        outs = {single.generate("sample me", max_new_tokens=12,
                                temperature=2.0)["text"]
                for _ in range(4)}
        assert len(outs) > 1

    def test_explicit_seed_reproducible_both_modes(self, predictors):
        single, batched = predictors
        for p in (single, batched):
            a = p.generate("reproduce", max_new_tokens=10,
                           temperature=1.3, seed=42)
            b = p.generate("reproduce", max_new_tokens=10,
                           temperature=1.3, seed=42)
            assert a["text"] == b["text"]

    def test_predict_surface_seed_semantics(self, predictors):
        single, _ = predictors
        base = {"prompt": "surface", "max_new_tokens": 10,
                "temperature": 2.0}
        a = single.predict(dict(base, seed=7))
        b = single.predict(dict(base, seed=7))
        assert a["text"] == b["text"]
        outs = {single.predict(dict(base))["text"] for _ in range(4)}
        assert len(outs) > 1


# ----------------------------------------------- gateway tail latency ----

class TestGatewayTail:
    def test_metrics_expose_p50_p99_and_legacy_unpack(self):
        """The dedupe regression pin: gateway tail stats come from the
        ONE shared core/obs LatencyWindow, and the legacy ``(qps, mean)``
        tuple-unpack of metrics() still works."""
        from fedml_tpu.serving.autoscale import Gateway
        gw = Gateway.__new__(Gateway)
        gw.window_s = 60.0
        gw._lock = threading.Lock()
        gw._window = obs_metrics.LatencyWindow(window_s=60.0)
        now = time.time()
        for l in [0.01] * 98 + [0.5, 2.0]:
            gw._window.observe(l, ts=now)
        m = gw.metrics()
        assert m.p50 == 0.01
        assert m.p99 == 0.5           # nearest-rank tail the mean hides
        assert m.latency_s < 0.05     # mean is tiny
        qps, lat = m                  # legacy tuple unpack still works
        assert (qps, lat) == (m.qps, m.latency_s)
        assert m.signal("p99") == m.p99

    def test_gateway_window_is_the_shared_implementation(self):
        """One source of truth: a live Gateway's window IS the core/obs
        LatencyWindow (no parallel percentile code path to drift)."""
        from fedml_tpu.serving.autoscale import Gateway

        class _RS:
            def ports(self):
                return []
        gw = Gateway(_RS(), window_s=3.0)
        assert isinstance(gw._window, obs_metrics.LatencyWindow)
        assert gw._window.window_s == 3.0
        assert gw.metrics().count == 0

    def test_autoscaler_feeds_declared_latency_signal(self):
        from fedml_tpu.serving.autoscale import (Autoscaler,
                                                 GatewayMetrics)

        class _RS:
            def health_check(self):
                return 0

            def scale_to(self, n):
                return n

            def __len__(self):
                return 1

        class _GW:
            replica_set = _RS()

            def metrics(self):
                return GatewayMetrics(qps=10.0, latency_s=0.02, p50=0.01,
                                      p99=1.0, count=100)

        seen = {}

        class _Policy:
            latency_signal = "p99"

            def desired_replicas(self, qps, latency_s, current):
                seen["lat"] = latency_s
                return current

        Autoscaler(_GW(), _Policy()).step()
        assert seen["lat"] == 1.0  # p99, not the 0.02 mean

    def test_lookback_policy_tail_guard(self):
        from fedml_tpu.serving.autoscale import LookbackPolicy
        p = LookbackPolicy(target_qps_per_replica=10.0, window=5,
                           max_latency_s=0.5)
        assert p.desired_replicas(5.0, 0.1, 2) == 1   # tail fine: demand
        assert p.desired_replicas(5.0, 0.9, 2) == 3   # tail blown: +1
        assert p.latency_signal == "p99"

    def test_gateway_records_obs_histogram(self):
        from fedml_tpu.serving.autoscale import Gateway, ReplicaSet

        class _Echo:
            def predict(self, request):
                return {"ok": 1}

            def ready(self):
                return True

        rs = ReplicaSet(lambda: _Echo(), min_replicas=1, max_replicas=1)
        gw = Gateway(rs, window_s=2.0)
        try:
            # ensure the histogram exists with the seam's own buckets
            # (a bare re-get with defaults would conflict)
            obs_metrics.record_gateway_latency(0.001)
            before = sum(
                v["count"] for v in obs_metrics.REGISTRY.histogram(
                    "serving_gateway_latency_seconds").snapshot())
            gw.predict({"x": 1})
            after = sum(
                v["count"] for v in obs_metrics.REGISTRY.histogram(
                    "serving_gateway_latency_seconds").snapshot())
            assert after == before + 1
        finally:
            rs.stop()


# --------------------------------------------------- HTTP e2e (slow) ----

@pytest.mark.slow
class TestChatEndpointE2E:
    def test_eight_concurrent_chat_clients_with_adapter_mix(
            self, lora_setup):
        import json
        import urllib.request
        args, bundle, params, tok = lora_setup
        predictor = CausalLMPredictor(
            bundle, params, tokenizer=tok, mode="batch",
            batch_opts={"slots": 4, "block_size": 16, "prefill_chunk": 8,
                        "max_adapters": 8})
        predictor.adapter_bank.add("siloA", _rand_adapter(params, 50))
        predictor.adapter_bank.add("siloB", _rand_adapter(params, 51))
        runner = ChatCompletionRunner(predictor)
        port = runner.start()
        solo = {n: predictor.generate("ping", max_new_tokens=8,
                                      adapter=n)["text"]
                for n in ("siloA", "siloB")}

        def post(i):
            model = ["siloA", "siloB"][i % 2]  # bank entry via model name
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/chat/completions",
                data=json.dumps({
                    "model": model,
                    "messages": [{"role": "user", "content": "ping"}],
                    "max_tokens": 8}).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                return model, json.load(r)

        try:
            with cf.ThreadPoolExecutor(8) as ex:
                outs = list(ex.map(post, range(8)))
            for model, out in outs:
                assert out["object"] == "chat.completion"
                assert out["choices"][0]["finish_reason"] in ("stop",
                                                              "length")
                # greedy + adapter routed by model name == solo output
                assert (out["choices"][0]["message"]["content"]
                        == solo[model])
        finally:
            runner.stop()
            predictor.close()


@pytest.mark.slow
class TestReplicaCrash:
    def test_crash_mid_stream_surfaces_cleanly_then_heals(self,
                                                          lora_setup):
        """A replica dying mid-request must yield a clean gateway error
        within the timeout (no hang, no garbage response); the health
        check then replaces it and traffic resumes."""
        from fedml_tpu.serving.autoscale import Gateway, ReplicaSet
        args, bundle, params, tok = lora_setup

        class _SlowPredictor(CausalLMPredictor):
            def chat(self, request):
                time.sleep(0.6)  # hold the request so the crash lands
                return super().chat(request)

        rs = ReplicaSet(
            predictor_factory=lambda: _SlowPredictor(
                bundle, params, tokenizer=tok, mode="batch",
                batch_opts={"slots": 2, "block_size": 16,
                            "prefill_chunk": 8}),
            min_replicas=1, max_replicas=2,
            runner_cls=ChatCompletionRunner)
        gw = Gateway(rs, window_s=5.0)
        req = {"messages": [{"role": "user", "content": "stream me"}],
               "max_tokens": 16}
        try:
            assert gw.predict(req, path="/v1/chat/completions",
                              timeout=60)["object"] == "chat.completion"
            result = {}

            def call():
                try:
                    result["out"] = gw.predict(
                        req, path="/v1/chat/completions", timeout=10)
                except Exception as e:  # the CLEAN surface we assert on
                    result["err"] = e

            t = threading.Thread(target=call)
            t.start()
            time.sleep(0.2)          # request is mid-stream on the victim
            rs.replicas[0].stop()    # crash
            t.join(timeout=15)
            assert not t.is_alive(), "gateway call hung past its timeout"
            assert ("err" in result) or ("out" in result
                                         and result["out"].get("object")
                                         == "chat.completion")
            # heal and resume
            assert rs.health_check() >= 1
            out = gw.predict(req, path="/v1/chat/completions", timeout=60)
            assert out["object"] == "chat.completion"
        finally:
            rs.stop()


@pytest.mark.slow
class TestConcurrencySoak:
    def test_soak_48_requests_mixed_adapters_compile_once(
            self, lora_setup, xla_compile_counter):
        args, bundle, params, tok = lora_setup
        batched = CausalLMPredictor(
            bundle, params, tokenizer=tok, mode="batch",
            batch_opts={"slots": 4, "block_size": 16, "prefill_chunk": 8,
                        "max_adapters": 8})
        batched.adapter_bank.add("siloA", _rand_adapter(params, 60))
        batched.adapter_bank.add("siloB", _rand_adapter(params, 61))
        try:
            batched.generate("warm", max_new_tokens=4)  # compile warmup
            xla_compile_counter.reset()

            def one(i):
                return batched.generate(
                    f"req {i} {'pad ' * (i % 7)}", max_new_tokens=8,
                    temperature=(0.0 if i % 3 else 1.1), seed=i,
                    adapter=[None, "siloA", "siloB"][i % 3])

            with cf.ThreadPoolExecutor(12) as ex:
                outs = list(ex.map(one, range(48)))
            assert len(outs) == 48
            assert all(o["finish_reason"] in ("stop", "length")
                       for o in outs)
            assert xla_compile_counter.delta() == 0
        finally:
            batched.close()


# -------------------------------- serving observability plane (ISSUE 10) ----

class _WedgeScheduler:
    """Duck-typed scheduler whose step() blocks until released — the
    deliberately wedged engine the watchdog/flight-recorder acceptance
    test needs, without burning a compile."""

    def __init__(self):
        from types import SimpleNamespace
        self.cfg = SimpleNamespace(max_seq_len=64)
        self.cache_cfg = SimpleNamespace(
            num_blocks=16, max_seq_len=64,
            blocks_needed=lambda n: 1)
        self.release_evt = threading.Event()
        self.last_step_finite = True
        self.steps_run = 0
        self._active = 0

    def can_admit(self, prompt_len, max_new):
        return self._active == 0

    def admit(self, ids, **kw):
        from fedml_tpu.llm.data import EOS
        self._active = 1
        return 0, EOS + 4   # slot 0, a non-EOS first token

    def release(self, slot):
        self._active = 0

    def step(self):
        self.steps_run += 1
        self.release_evt.wait(timeout=30)
        return {}

    def active_count(self):
        return self._active

    def slot_position(self, slot):
        return 5

    def kv_pool_stats(self):
        return {"used_blocks": 1, "free_blocks": 15,
                "headroom_requests": 3, "fragmentation": 0.5}

    def debug_state(self):
        return {"slots": [{"slot": 0, "active": bool(self._active)}],
                "kv_pool": self.kv_pool_stats()}


class TestServingTraces:
    def _report_mod(self):
        import os
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "scripts"))
        import serving_report
        return serving_report

    def test_e2e_traces_schema_valid_and_95pct_attributed(
            self, predictors, tmp_path):
        """The acceptance pin: an 8-concurrent-request session (one
        deadline eviction) produces schema-valid traces whose waterfalls
        attribute >=95% of each request's submit->finish wall to named
        spans, reconstructed by scripts/serving_report.py."""
        import json
        import os
        from fedml_tpu.core import mlops
        from fedml_tpu.core.obs import schema as obs_schema
        _, batched = predictors
        eng = batched.engine
        mlops.init(Arguments(log_file_dir=str(tmp_path), run_id="trc"))
        try:
            # the evictee first (so it owns a slot): long budget, short
            # leash -> deadline eviction mid-decode
            evict_fut = eng.submit(list(range(4, 22)), max_new_tokens=40,
                                   deadline_s=0.05)
            with cf.ThreadPoolExecutor(7) as ex:
                gens = [ex.submit(batched.generate,
                                  f"trace request number {i}",
                                  max_new_tokens=10)
                        for i in range(7)]
                outs = [g.result(timeout=60) for g in gens]
            evicted = evict_fut.result(timeout=60)
            time.sleep(0.3)   # let the engine close its decode_steps span
        finally:
            mlops.init(Arguments(enable_tracking=False))
        assert len(outs) == 7
        assert evicted["finish_reason"] == "deadline"
        assert evicted["completion_tokens"] < 40   # leash cut it short

        path = os.path.join(str(tmp_path), "run_trc.jsonl")
        lines = open(path).read().splitlines()
        problems = obs_schema.validate_lines(lines)
        assert not problems, problems[:10]
        spans = [json.loads(l) for l in lines
                 if json.loads(l).get("kind") == "span"]
        serving_names = {s["name"] for s in spans
                         if s["name"].startswith("serving.")}
        assert serving_names <= obs_schema.SERVING_SPAN_NAMES, \
            serving_names - obs_schema.SERVING_SPAN_NAMES
        reqs = [s for s in spans if s["name"] == "serving.request"]
        assert len(reqs) == 8
        # the evicted request's span carries the evict event
        assert any(ev["name"] == "evict"
                   for s in reqs for ev in s.get("events", []))
        # engine-side fan-in: decode_steps spans LINK the request spans
        # they advanced (the async-pour idiom)
        step_spans = [s for s in spans
                      if s["name"] == "serving.decode_steps"]
        assert step_spans
        req_ids = {s["span_id"] for s in reqs}
        linked = {ln["span_id"] for s in step_spans
                  for ln in s.get("links", [])}
        assert linked & req_ids
        # the waterfall: >=95% of every request's wall attributed
        sr = self._report_mod()
        import io
        out = io.StringIO()
        spans_l, snaps = sr.load_records([path])
        rc = sr.print_report(spans_l, snaps, None, 0.95, out=out)
        assert rc == 0, out.getvalue()
        assert "ttft_s" in out.getvalue()

    def test_http_traceparent_joins_request_trace(self, predictors,
                                                  tmp_path):
        """An inbound W3C traceparent header parents the whole serving
        lifecycle — serving.http AND the engine's serving.request land
        in the caller's trace — and the response echoes the context."""
        import json
        import os
        import urllib.request
        from fedml_tpu.core import mlops
        _, batched = predictors
        runner = ChatCompletionRunner(batched)
        port = runner.start()
        trace_id = "ab" * 16
        mlops.init(Arguments(log_file_dir=str(tmp_path), run_id="tp"))
        try:
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/chat/completions",
                data=json.dumps({
                    "messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 4}).encode(),
                headers={"Content-Type": "application/json",
                         "traceparent": f"00-{trace_id}-{'cd' * 8}-01"})
            with urllib.request.urlopen(req, timeout=60) as r:
                echoed = r.headers.get("traceparent")
                assert r.status == 200
        finally:
            runner.stop()
            mlops.init(Arguments(enable_tracking=False))
        assert echoed and echoed.split("-")[1] == trace_id
        path = os.path.join(str(tmp_path), "run_tp.jsonl")
        spans = [json.loads(l) for l in open(path) if l.strip()]
        spans = [s for s in spans if s.get("kind") == "span"]
        by_name = {}
        for s in spans:
            if s["trace_id"] == trace_id:
                by_name.setdefault(s["name"], []).append(s)
        assert "serving.http" in by_name, {s["name"] for s in spans}
        assert "serving.request" in by_name
        http_sp = by_name["serving.http"][0]
        assert http_sp["parent_id"] == "cd" * 8  # the caller's span
        assert by_name["serving.request"][0]["parent_id"] \
            == http_sp["span_id"]


class TestWatchdogFlightRecorder:
    def test_wedged_engine_dumps_schema_valid_black_box(self, tmp_path):
        """The acceptance pin: a deliberately wedged engine (step blocks
        forever with occupancy > 0) trips the watchdog, and the flight-
        recorder JSONL dump validates line by line."""
        import json
        import os
        from fedml_tpu.core import mlops
        from fedml_tpu.core.obs import schema as obs_schema
        from fedml_tpu.serving.batch.engine import BatchingEngine
        mlops.init(Arguments(log_file_dir=str(tmp_path), run_id="wedge"))
        sched = _WedgeScheduler()
        eng = BatchingEngine(sched, watchdog_s=0.3, flight_records=64,
                             flight_dir=str(tmp_path))
        try:
            eng.submit([5, 6, 7], max_new_tokens=8)
            deadline = time.time() + 15.0
            while time.time() < deadline and eng.watchdog.trips == 0:
                time.sleep(0.05)
            assert eng.watchdog.trips >= 1, "watchdog never tripped"
            assert eng.watchdog.last_trip_reason == "stalled"
            assert eng.health()["status"] == "stalled"
            dump = eng._flight_path
            assert dump and os.path.exists(dump)
            lines = open(dump).read().splitlines()
            assert lines
            problems = obs_schema.validate_lines(lines)
            assert not problems, problems[:10]
            events = [json.loads(l)["event"] for l in lines]
            assert "submit" in events
            assert "admit" in events
            assert "watchdog_trip" in events
            # the trip also landed as a health record in the run log
            health = [json.loads(l) for l in open(
                os.path.join(str(tmp_path), "run_wedge.jsonl"))
                if '"health"' in l]
            health = [h for h in health if h.get("kind") == "health"]
            assert health and health[-1]["status"] == "stalled"
        finally:
            sched.release_evt.set()
            eng.stop()
            mlops.init(Arguments(enable_tracking=False))

    def test_nan_logits_trip_and_health(self, tmp_path):
        """NaN/inf in decode logits is a poisoned step: progress exists
        but the output is garbage — the watchdog must still trip."""
        from fedml_tpu.core import mlops
        from fedml_tpu.serving.batch.engine import BatchingEngine
        mlops.init(Arguments(log_file_dir=str(tmp_path), run_id="nan"))
        sched = _WedgeScheduler()
        sched.release_evt.set()   # steps return immediately
        eng = BatchingEngine(sched, watchdog_s=0.0,  # drive check() by hand
                             flight_records=16, flight_dir=str(tmp_path))
        try:
            sched.last_step_finite = False
            assert eng.health()["status"] == "nan_logits"
            assert eng.watchdog.check() == "nan_logits"
            assert eng.watchdog.trips == 1
        finally:
            eng.stop()
            mlops.init(Arguments(enable_tracking=False))

    def test_decode_step_reports_nonfinite_logits(self, lora_setup):
        """The real scheduler's poison flag: poisoned base params make
        last_step_finite go False on the very next decode step."""
        import jax.numpy as jnp
        import jax
        from fedml_tpu.serving.batch import DecodeScheduler
        args, bundle, params, tok = lora_setup
        sched = DecodeScheduler(bundle.module, bundle.cfg,
                                bundle.base_params, None,
                                slots=2, block_size=16, prefill_chunk=8)
        sched.admit([5, 6, 7], max_new_tokens=4)
        sched.step()
        assert sched.last_step_finite
        poisoned = jax.tree_util.tree_map(
            lambda l: jnp.full_like(l, jnp.nan), sched.params)
        sched.params = poisoned
        sched.step()
        assert not sched.last_step_finite


class TestLiveEndpoints:
    def test_metrics_healthz_debug_scrape_during_live_session(
            self, predictors):
        """The acceptance pin: during a live batched session, /metrics
        serves Prometheus text including the TTFT and ITL histograms;
        /healthz answers ok; /debug/state shows the slot matrix."""
        import json
        import urllib.request
        _, batched = predictors
        runner = ChatCompletionRunner(batched)
        port = runner.start()
        try:
            def post(i):
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/chat/completions",
                    data=json.dumps({
                        "messages": [{"role": "user",
                                      "content": f"scrape test {i}"}],
                        "max_tokens": 24}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as r:
                    return json.load(r)

            with cf.ThreadPoolExecutor(4) as ex:
                inflight = [ex.submit(post, i) for i in range(4)]
                # scrape WHILE requests are in flight
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/metrics",
                        timeout=10) as r:
                    assert r.status == 200
                    assert r.headers["Content-Type"].startswith(
                        "text/plain")
                    text = r.read().decode()
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/healthz",
                        timeout=10) as r:
                    health = json.load(r)
                    assert r.status == 200
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/debug/state",
                        timeout=10) as r:
                    debug = json.load(r)
                outs = [f.result(timeout=60) for f in inflight]
            assert all(o["object"] == "chat.completion" for o in outs)
            # the SLO surface is live Prometheus text
            assert "# TYPE llm_ttft_seconds histogram" in text
            assert "llm_ttft_seconds_bucket" in text
            assert "# TYPE llm_inter_token_seconds histogram" in text
            assert "llm_inter_token_seconds_bucket" in text
            assert "llm_kv_blocks_used" in text
            assert "llm_queue_depth" in text
            assert health["status"] == "ok"
            assert "steps_run" in health
            slots = debug["scheduler"]["slots"]
            assert len(slots) == 4   # the fixture's slot matrix
            assert "kv_pool" in debug["scheduler"]
            assert "depth" in debug["queue"]
        finally:
            runner.stop()

    def test_healthz_503_when_wedged(self, tmp_path):
        import json
        import urllib.error
        import urllib.request
        from fedml_tpu.serving import FedMLInferenceRunner
        from fedml_tpu.serving.batch.engine import BatchingEngine

        class _P:
            def __init__(self, eng):
                self.eng = eng

            def predict(self, request):
                return {}

            def ready(self):
                return True

            def health(self):
                return self.eng.health()

            def debug_state(self):
                return self.eng.debug_state()

        sched = _WedgeScheduler()
        eng = BatchingEngine(sched, watchdog_s=0.2,
                             flight_dir=str(tmp_path))
        runner = FedMLInferenceRunner(_P(eng))
        port = runner.start()
        try:
            eng.submit([5, 6], max_new_tokens=4)
            deadline = time.time() + 15.0
            while time.time() < deadline and eng.watchdog.trips == 0:
                time.sleep(0.05)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/healthz", timeout=10)
            assert ei.value.code == 503
            assert json.load(ei.value)["status"] == "stalled"
        finally:
            sched.release_evt.set()
            runner.stop()
            eng.stop()


class TestServingOverheadGate:
    def test_tracking_off_does_nothing_and_on_stays_cheap_c8(
            self, predictors, tmp_path, tracking_counts):
        """What tracing + metrics cost the batched engine on the
        concurrency-8 block, held by counts on both sides: with them off
        a block builds no span and writes to no sink; with them on it
        builds four spans a request (request, queue, prefill, decode)
        and one ``serving.decode_steps`` span a block of
        ``DECODE_SPAN_STEPS`` steps or a busy spell, nothing a token,
        and writes one record a span. One engine serves both modes
        (hooks read process config at call time). The timing bound is
        loose on purpose: a loaded machine spreads the same block from
        0.2 to 1.2 s, so more tracing work has to fail by its count."""
        from fedml_tpu.core import mlops
        from fedml_tpu.serving.batch.engine import DECODE_SPAN_STEPS
        _, batched = predictors
        counts = tracking_counts

        def block(mode_args):
            mlops.init(mode_args)
            before = dict(counts, names=counts["names"].copy())
            t0 = time.perf_counter()
            with cf.ThreadPoolExecutor(8) as ex:
                futs = [ex.submit(batched.generate,
                                  f"overhead gate req {i}",
                                  max_new_tokens=24)
                        for i in range(8)]
                outs = [f.result(timeout=120) for f in futs]
            wall = time.perf_counter() - t0
            assert all(o["completion_tokens"] > 0 for o in outs)
            # the engine's thread ends the last block span once it finds
            # itself idle, a moment after the last future resolves
            deadline = time.monotonic() + 10
            while (batched._engine._steps_span is not None
                   and time.monotonic() < deadline):
                time.sleep(0.001)
            names = counts["names"] - before["names"]
            return (wall, counts["spans"] - before["spans"],
                    counts["sink"] - before["sink"],
                    names.pop("serving.decode_steps", 0), names,
                    sum(o["completion_tokens"] for o in outs))

        on_args = Arguments(log_file_dir=str(tmp_path), run_id="s_ovh")
        off_args = Arguments(enable_tracking=False, obs_tracing=False,
                             obs_metrics=False)
        try:
            block(on_args)                     # warm both modes
            block(off_args)
            on, off = [], []
            for _ in range(4):   # alternate, so drift hits both alike
                off.append(block(off_args))
                on.append(block(on_args))
        finally:
            mlops.init(Arguments(enable_tracking=False))
        assert all(t[1:3] == (0, 0) for t in off), off
        for _, spans, sink, step_blocks, per_request, tokens in on:
            assert sorted(per_request.values()) == [8] * 4, per_request
            # a block span ends after DECODE_SPAN_STEPS steps or when the
            # engine runs dry, which it can once a request at most
            assert 1 <= step_blocks <= tokens // DECODE_SPAN_STEPS + 8, on
            assert spans == 4 * 8 + step_blocks, on
            # a record a span, and at most the wall-clock flusher's one
            # metrics snapshot (obs_metrics_flush_s) beside them
            assert spans <= sink <= spans + 1, on
        best_on, best_off = min(t[0] for t in on), min(t[0] for t in off)
        assert best_on <= 2.0 * best_off + 0.1, (
            f"tracing+metrics cost {best_on:.4f}s against {best_off:.4f}s "
            f"at c8: on={on} off={off}")
