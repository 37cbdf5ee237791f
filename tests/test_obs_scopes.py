"""Device time by the program's own scopes (``core/obs/scopes``): the closed
vocabulary, the innermost scope of an ``op_name``, the table a program
makes from its own compiled text (every layer kind's scopes, the backward
kernels of each ``custom_vjp`` in their module's scope), the dispatch
seam's note, and the persistent cache's stale metadata."""

import collections
import re

import jax
import jax.numpy as jnp
import pytest

from fedml_tpu.core.obs import scopes
from fedml_tpu.core.obs import trace as obs_trace

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _no_programs_left():
    scopes._programs.clear()
    yield
    scopes._programs.clear()
    obs_trace.set_enabled(True)


# --------------------------------------------------------- the vocabulary ---

def test_scope_refuses_a_name_outside_the_vocabulary():
    with scopes.scope("attn.full"):
        pass
    for name in ("attn", "attn.window.kernel", "Attn.Full", ""):
        with pytest.raises(ValueError, match="unknown scope"):
            scopes.scope(name)
    assert len(set(scopes.SCOPES)) == len(scopes.SCOPES) == 24


@pytest.mark.parametrize("op_name, want", [
    ("jit(round_body)/jit(main)/shmap_body/while/body/engine.slot/while/"
     "body/local.grad/jvp(CausalLM)/layer_0/attn.full/attn/q/dot_general",
     "attn.full"),
    # nested: the innermost scope wins, so lora time is not attn.* time
    ("jit(f)/engine.slot/local.grad/jvp(CausalLM)/layer_0/attn.window/attn/"
     "lora/dot_general", "lora"),
    ("jit(f)/local.grad/jvp(CausalLM)/layer_1/moe/shared/mlp/lora/mul",
     "lora"),
    # a transform wraps the entry it meets first, whichever that is
    ("jit(f)/transpose(jvp(moe.experts))/cond/branch_1_fun/jit(_pull_back)/"
     "moe_grouped_dx/pallas_call", "moe.experts"),
    ("jit(f)/local.grad/transpose(jvp(CausalLM))/layer_2/attn.linear/attn/"
     "kda_bwd/pallas_call", "attn.linear"),
    ("jit(f)/vmap(jvp(norm))/rematted_computation/mul", "norm"),
    # the indexer inside a sparse attention module is the indexer's
    ("jit(f)/local.grad/jvp(CausalLM)/layer_0/attn.sparse/attn/attn.index/"
     "sparse_index/pallas_call", "attn.index"),
    # a flax module called like a scope is the same thing; one that only
    # contains a scope's name is not
    ("jit(f)/CausalLM/embed.attend/dot_general", None),
    ("jit(f)/CausalLM/lm_head/dot_general", None),
    ("jit(f)/jit(_threefry_split)/threefry2x32", None),
    ("", None),
])
def test_scope_of_is_the_innermost_vocabulary_name(op_name, want):
    assert scopes.scope_of(op_name) == want


HLO = '''HloModule jit_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.3 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/local.grad/jvp(M)/mlp/mul" stack_frame_id=3}
}

ENTRY %main.9 (Arg_0.1: f32[8], Arg_1.2: f32[8,8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0:T(256)} parameter(0), metadata={op_name="x"}
  %Arg_1.2 = f32[8,8]{1,0:T(8,128)} parameter(1), metadata={op_name="w"}
  %fusion.1 = f32[8]{0:T(256)} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/local.grad/jvp(M)/mlp/mul" stack_frame_id=3}
  %copy-start.2 = (f32[8,8]{1,0:T(8,128)S(1)}, f32[8,8]{1,0:T(8,128)}, u32[]{:S(2)}) copy-start(%Arg_1.2)
  %copy-done.2 = f32[8,8]{1,0:T(8,128)S(1)} copy-done(%copy-start.2)
  %kda_bwd.4 = f32[8]{0:T(256)} custom-call(%fusion.1, %copy-done.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/local.grad/transpose(jvp(M))/attn.linear/attn/kda_bwd/pallas_call" stack_frame_id=7}
  %copy.5 = f32[8]{0:T(256)} copy(%kda_bwd.4)
  %gather.6 = f32[8]{0:T(256)} fusion(%copy.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(_pull_back)/gather" stack_frame_id=9}
  %add.7 = s32[]{:T(128)} add(%constant.1, %constant.1)
  ROOT %add.8 = f32[8]{0:T(256)} add(%gather.6, %Arg_0.1), metadata={op_name="jit(step)/local.update/add"}
}
'''


def test_parse_reads_names_scopes_and_what_the_compiler_made():
    table = scopes.parse(HLO)
    assert table["fusion.1"] == "mlp"
    assert table["multiply.3"] == "mlp"        # listed, never an event
    assert table["kda_bwd.4"] == "attn.linear"
    assert table["add.8"] == "local.update"
    # no op_name: the operand's scope, through a chain of such instructions
    assert table["copy.5"] == "attn.linear"
    # an op_name cut at an inner jit (the TPU compiler's inlining inside a
    # conditional) names no scope: the operand's too
    assert table["gather.6"] == "attn.linear"
    # a prefetched weight: the chain ends at a parameter, so its user's
    assert table["copy-start.2"] == table["copy-done.2"] == "attn.linear"
    # a loop counter leads nowhere
    assert table["add.7"] is None
    assert "main.9" not in table and "fused_computation.1" not in table


# ------------------------------------ one small model of each layer kind ---

COMMON = dict(vocab_size=64, hidden_size=32, intermediate_size=48,
              num_heads=2, num_layers=2, max_seq_len=128,
              tie_embeddings=False, attention_impl="flash")
EXPERTS = dict(n_routed_experts=8, num_experts_per_tok=2,
               moe_intermediate_size=16, experts_held=4, first_expert=2)
LATENT = dict(kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
              v_head_dim=16)
EVERY = {"embed", "head", "norm", "lora", "mlp"}
KINDS = {
    # latent attention (the flash kernels at 24/16), experts with a shared
    # one behind a dense first layer
    "latent": (dict(**EXPERTS, **LATENT, n_shared_experts=1,
                    q_lora_rank=16, layers=("latent+mlp", "latent+moe")),
               EVERY | {"attn.latent", "moe.route", "moe.experts"},
               {"flash_dq": "attn.latent", "flash_dkv": "attn.latent",
                "moe_grouped_dx": "moe.experts"}),
    # a full layer and a window layer with a sink, grouped-query heads
    "window": (dict(**EXPERTS, num_kv_heads=1, head_size=24, v_head_dim=16,
                    rotary_dim=8, layers=("full+mlp", "window+moe"),
                    sliding_window=32, window_sink=True),
               EVERY | {"attn.full", "attn.window", "moe.route",
                        "moe.experts"},
               {"flash_dq": "attn.full", "flash_dkv": "attn.full",
                "flash_win_dq": "attn.window",
                "flash_win_dkv": "attn.window"}),
    # a Kimi-delta layer (its kernels take heads of 128) and a latent one
    "linear": (dict(**LATENT, layers=("linear+mlp", "latent+mlp"),
                    linear_head_dim=128, attn_output_gate=True),
               EVERY | {"attn.linear", "attn.latent"},
               {"kda_bwd": "attn.linear", "kda_pre_bwd": "attn.linear",
                "kda_post_bwd": "attn.linear", "flash_dq": "attn.latent"}),
}


def _train_step(over):
    from fedml_tpu.llm.federated import LLMBundle
    from fedml_tpu.llm.model import LLMConfig, init_llm
    from fedml_tpu.llm.trainer import CausalLMTrainer

    cfg = LLMConfig(**COMMON, **over)
    model, params = init_llm(cfg, jax.random.PRNGKey(0))
    bundle = LLMBundle(model, cfg, params, 4, 8.0)
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    lora = bundle.init(jax.random.PRNGKey(1), None)
    tok = jax.random.randint(jax.random.PRNGKey(2), (1, 129), 0, 64)
    batch = {"x": tok[:, :-1], "y": tok[:, 1:], "mask": jnp.ones((1,))}
    step = jax.jit(lambda p, b: jax.grad(
        lambda q: spec.loss(q, b, None)[0])(p))
    return step, (lora, batch)


@pytest.mark.pallas
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_train_steps_table_names_every_scope_its_model_has(
        kind, xla_compile_counter):
    """The compiled train step of a small model of each layer kind: the
    table holds every scope the model has and no other; the backward
    kernels of each ``custom_vjp`` (interpreted here: their operations
    carry the kernel's name in their ``op_name``) lie in their module's
    scope, none of them unscoped; making the table compiles nothing."""
    over, want, kernels = KINDS[kind]
    step, args = _train_step(over)
    step(*args)
    scopes.note_program(kind, step, args, compiled=True)
    xla_compile_counter.reset()
    table = scopes.table(kind)
    assert xla_compile_counter.delta() == 0
    build = scopes.last_build(kind)
    assert not build["stale"] and set(build["scopes"]) == want
    assert scopes.table(kind) is table                # made once
    text = step.lower(*args).compile().as_text()
    seen = collections.Counter()
    for line in text.splitlines():
        m = scopes._INSTRUCTION.match(line)
        op = scopes._OP_NAME.search(line)
        if m is None or op is None:
            continue
        for kernel, scope in kernels.items():
            if re.search(rf"/{kernel}/", op.group(1)):
                assert table[m.group(1)] == scope, line
                seen[kernel] += 1
    assert set(seen) == set(kernels)


# ------------------------------------------------------ the dispatch seam ---

def _tiny_sim():
    from fedml_tpu import data as data_mod
    from fedml_tpu import model as model_mod
    from fedml_tpu.arguments import Arguments
    from fedml_tpu.core.algframe.client_trainer import (
        ClassificationTrainer)
    from fedml_tpu.optimizers.registry import create_optimizer
    from fedml_tpu.simulation.tpu.engine import TPUSimulator

    args = Arguments(
        dataset="synthetic_mnist", model="lr", client_num_in_total=8,
        client_num_per_round=4, comm_round=2, epochs=1, batch_size=16,
        learning_rate=0.1, frequency_of_the_test=0, random_seed=0)
    fed, out_dim = data_mod.load(args)
    bundle = model_mod.create(args, out_dim)
    spec = ClassificationTrainer(bundle.apply)
    return TPUSimulator(args, fed, bundle, create_optimizer(args, spec),
                        spec)


def _hyper():
    from fedml_tpu.core.algframe.types import TrainHyper
    return TrainHyper(learning_rate=jnp.float32(0.1), epochs=1)


def test_a_simulators_round_is_noted_once_and_its_table_has_the_engine():
    sim = _tiny_sim()
    assert scopes.table("round") is None              # nothing noted yet
    float(sim.run_round(0, _hyper())["loss_sum"])
    noted = scopes._programs["round"]
    float(sim.run_round(1, _hyper())["loss_sum"])
    assert scopes._programs["round"] is noted         # a lookup, no more
    before = sim.dispatch_stats["compiles"]
    table = scopes.table("round")
    have = set(filter(None, table.values()))
    assert {"engine.slot", "engine.accumulate", "engine.server",
            "local.batch", "local.grad", "local.update"} <= have
    assert not scopes.last_build("round")["stale"]
    float(sim.run_round(2, _hyper())["loss_sum"])     # and no recompile
    assert sim.dispatch_stats["compiles"] == before
    # the engine is held weakly: once it is gone the table stays, a new
    # question finds no program
    del sim, noted
    import gc
    gc.collect()
    assert scopes.table("round") is table
    scopes._programs["round"].table = None
    assert scopes.table("round") is None


def test_nothing_is_noted_with_tracing_off():
    obs_trace.set_enabled(False)
    sim = _tiny_sim()
    float(sim.run_round(0, _hyper())["loss_sum"])
    assert not scopes._programs
    assert scopes.table("round") is None
    assert scopes.last_build("round") is None


def test_table_never_raises():
    broken = jax.jit(lambda x: x + 1)
    scopes.note_program("broken", broken, ("not an array",), compiled=True)
    assert scopes.table("broken") is None


# ------------------------------------------------- the persistent cache ---

def test_a_stale_cache_entry_does_not_decide_the_scopes(tmp_path):
    """The persistent cache's key leaves debug information out, and scopes
    are debug information: a step compiled under one scope name, the scope
    renamed, the step built again against the same directory is a cache
    HIT whose text carries the old name. The table gives the new one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def make(name):
        def step(x, w):
            with scopes.scope(name):
                return jnp.tanh(x @ w).sum()
        return jax.jit(jax.grad(step))

    from fedml_tpu.core import mlops
    mlops.install_compile_counter()
    # the arguments first: their own tiny programs may be answered by the
    # session's shared cache directory, which another worker fills
    args = (jnp.ones((4, 8)), jnp.ones((8, 8)))
    start = mlops.compile_phases()["cache_hits"]
    hits = lambda: mlops.compile_phases()["cache_hits"] - start  # noqa: E731
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    cc.reset_cache()
    try:
        old = make("mlp")
        old(*args)
        assert hits() == 0 and list(tmp_path.iterdir())
        new = make("norm")
        new(*args)
        assert hits() == 1                            # the old executable
        assert 'mlp' in new.lower(*args).compile().as_text()
        scopes.note_program("step", new, args, compiled=True)
        table = scopes.table("step")
        assert scopes.last_build("step")["stale"]
        assert set(filter(None, table.values())) == {"norm"}
        # past the caches, and leaving them as they were: nothing new in
        # the directory, and the cache still answers
        assert hits() == 1
        make("head")(*args)
        assert hits() == 2
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        cc.reset_cache()
