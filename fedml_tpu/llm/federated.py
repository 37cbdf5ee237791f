"""Federated LLM fine-tuning — the UnitedLLM/FedLLM analogue.

Parity target: ``spotlight_prj/unitedllm/src/unitedllm_trainer.py:57``
(HFTrainer used as the FedML ClientTrainer in a cross-silo job) and its
``FedLLM LoRA`` config. TPU-native: the trainable pytree each
silo ships is the LoRA adapter tree alone (base weights frozen and never
communicated), so a federated round aggregates kilobytes instead of the
full model — the design SURVEY §7 calls for ("get_model_params … cheap
all_gather on the LoRA adapters only").

``build_llm(args)`` wires the pieces into the standard (fed, bundle, spec)
triple, so every runner — SP golden, jitted TPU engine, cross-silo WAN
FSM — fine-tunes the LLM with zero special-casing.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from .data import ByteTokenizer, build_llm_federated
from .linear_attention import MIN_LOG_DECAY, SHORT_CONV_TAPS
from .lora import lora_init
from .model import CausalLM, LLMConfig, init_llm
from .moe import STATS as MOE_STATS
from .trainer import CausalLMTrainer

logger = logging.getLogger(__name__)
PyTree = Any


def llm_config_from_args(args) -> LLMConfig:
    """Map the flat config namespace onto LLMConfig (reference
    ``ModelArguments``, ``train/llm/configurations.py:156``)."""
    precision = str(getattr(args, "precision", "float32")).lower()
    dtype = "bfloat16" if precision in ("bf16", "bfloat16") else "float32"
    # default: the fused Pallas flash kernels on TPU (O(s·block) memory in
    # both directions), dense elsewhere (interpret-mode flash is for
    # tests, not training)
    impl = getattr(args, "llm_attention_impl", None)
    chosen = str(impl or ("flash" if jax.default_backend() == "tpu"
                          else "dense"))
    logger.info("attention impl %r (%s, backend %s)", chosen,
                "configured" if impl else "platform default",
                jax.default_backend())
    return LLMConfig(
        vocab_size=int(getattr(args, "llm_vocab_size", ByteTokenizer.vocab_size)),
        hidden_size=int(getattr(args, "llm_hidden_size", 128)),
        intermediate_size=int(getattr(args, "llm_intermediate_size", 352)),
        num_layers=int(getattr(args, "llm_num_layers", 2)),
        num_heads=int(getattr(args, "llm_num_heads", 4)),
        num_kv_heads=getattr(args, "llm_num_kv_heads", None),
        max_seq_len=int(getattr(args, "llm_max_seq_len", 128)),
        dtype=dtype,
        attention_impl=chosen,
    )


def _nemotron_h_fields(config: dict, layers: int) -> dict:
    """The :class:`LLMConfig` fields only a ``nemotron_h`` configuration
    sets (a ``hybrid_override_pattern`` of Mamba-2, expert and attention
    layers, one mixer a layer; ``relu2`` experts in a latent; attention
    without rotary), or its refusal."""
    get = config.get
    pattern = get("hybrid_override_pattern") or ""
    if len(pattern) != layers or set(pattern) - set("ME*"):
        raise NotImplementedError(
            f"hybrid_override_pattern {pattern!r}: one of M (Mamba-2), E "
            f"(experts), * (attention) for each of the {layers} layers; the "
            "dense '-' layer of smaller siblings is not built")
    for key in ("use_bias", "mamba_proj_bias", "mlp_bias", "attention_bias"):
        if get(key):
            raise NotImplementedError(f"{key}: the projections are built "
                                      "without biases")
    if get("mamba_hidden_act", "silu") != "silu":
        raise NotImplementedError(
            f"mamba_hidden_act {get('mamba_hidden_act')!r}")
    act = get("mlp_hidden_act", "relu2")
    if act not in ("relu2", "silu"):
        raise NotImplementedError(f"mlp_hidden_act {act!r}")
    heads = int(config["mamba_num_heads"])
    head_dim = int(config["mamba_head_dim"])
    groups = int(get("n_groups") or 1)
    if heads % groups:
        raise NotImplementedError(
            f"n_groups {groups} does not divide mamba_num_heads {heads}")
    expand = int(get("expand") or 2)
    if expand * int(config["hidden_size"]) != heads * head_dim:
        raise NotImplementedError(
            f"expand {expand} x hidden_size {config['hidden_size']} is not "
            f"mamba_num_heads {heads} x mamba_head_dim {head_dim}")
    return dict(
        block_pattern=pattern, ssm_heads=heads, ssm_head_dim=head_dim,
        ssm_state_size=int(config["ssm_state_size"]), ssm_groups=groups,
        ssm_conv_kernel=int(get("conv_kernel") or 4),
        ssm_conv_bias=bool(get("use_conv_bias", True)),
        ssm_chunk=int(get("chunk_size") or 128),
        mlp_activation="relu2" if act == "relu2" else "swiglu",
        moe_latent_size=int(get("moe_latent_size") or 0),
        shared_expert_size=int(
            get("moe_shared_expert_intermediate_size") or 0),
        use_rope=False)


def _kimi_linear_fields(config: dict, layers: int) -> dict:
    """The :class:`LLMConfig` fields a ``kimi_linear`` configuration reads
    its own way (Kimi delta attention by ``linear_attn_config``'s 1-based
    ``kda_layers`` / ``full_attn_layers`` with its heads, head size and
    short convolution, the unbounded softplus gate; latent attention
    without positions where ``mla_use_nope``; sigmoid experts top-k by
    score + bias, ``num_expert_group`` groups of which ``topk_group`` stay
    under ``use_grouped_topk``), or its refusal. The top-level
    ``head_dim`` (hidden / heads) is not the KDA head size and is not
    read."""
    get = config.get
    linear = dict(get("linear_attn_config") or {})
    kda = list(linear.get("kda_layers") or [])
    full = list(linear.get("full_attn_layers") or [])
    if sorted(kda + full) != list(range(1, layers + 1)):
        raise NotImplementedError(
            f"linear_attn_config lists kda_layers {kda} and full_attn_layers "
            f"{full}: together they must name each of the {layers} layers "
            "once, counting from 1")
    heads = int(linear.get("num_heads") or 0)
    if heads != int(config["num_attention_heads"]):
        raise NotImplementedError(
            f"linear_attn_config num_heads {heads}: the KDA layers are built "
            f"with the model's {config['num_attention_heads']} heads")
    taps = int(linear.get("short_conv_kernel_size") or SHORT_CONV_TAPS)
    if taps != SHORT_CONV_TAPS:
        raise NotImplementedError(
            f"short_conv_kernel_size {taps}: the short convolution is built "
            f"over {SHORT_CONV_TAPS} positions")
    act = get("moe_router_activation_func", "sigmoid")
    if act != "sigmoid":
        raise NotImplementedError(f"moe_router_activation_func {act!r}")
    grouped = bool(get("use_grouped_topk"))
    return dict(
        linear_layers=tuple(i - 1 for i in kda), kda_gate="softplus",
        linear_head_dim=int(linear["head_dim"]),
        use_rope=not get("mla_use_nope", False), attn_output_gate=False,
        num_experts_per_tok=int(get("num_experts_per_token") or 0),
        norm_topk_prob=bool(get("moe_renormalize", True)),
        n_group=int(get("num_expert_group") or 0) if grouped else 0,
        topk_group=int(get("topk_group") or 0) if grouped else 0,
        router_bias=True)


def llm_config_from_hf(config: dict, *, max_seq_len: int,
                       dtype: str = "float32", attention_impl: str = "dense",
                       first_expert: int = 0,
                       experts_held: int = 0) -> LLMConfig:
    """An :class:`LLMConfig` from a published ``config.json`` dict (the keys
    of the Llama/Mistral family, of the DeepSeek-V3 family, which ``axk1``
    shares: latent attention, sigmoid-routed experts with shared ones,
    leading dense layers, YaRN; and of the Bailing hybrid family, which
    names the experts ``num_experts`` / ``num_shared_experts`` /
    ``score_function``, routes with ``noaux_tc`` and mixes Kimi delta
    attention layers with gated latent ones by ``layer_group_size``; and of
    ``mimo_v2_flash``: ``hybrid_layer_pattern`` (1 = a window layer) with
    the window kind's ``sliding_window``, ``swa_num_key_value_heads``,
    ``swa_rope_theta`` and the two ``add_*_attention_sink_bias`` flags,
    ``head_dim`` / ``v_head_dim`` beside grouped-query heads,
    ``partial_rotary_factor``, ``attention_value_scale``,
    ``layernorm_epsilon``, ``moe_layer_freq`` as a list of zeros then ones;
    and of ``nemotron_h``: ``hybrid_override_pattern`` (``M`` a Mamba-2
    mixer with ``mamba_num_heads``, ``mamba_head_dim``, ``ssm_state_size``,
    ``n_groups``, ``conv_kernel``, ``chunk_size``, ``use_conv_bias``; ``E``
    experts of ``mlp_hidden_act`` ``relu2`` in a ``moe_latent_size`` latent
    beside a shared one of ``moe_shared_expert_intermediate_size``, routed
    by sigmoid scores with a score-correction bias; ``*`` grouped-query
    attention without rotary), one mixer a layer, ``layer_norm_epsilon``;
    and of ``kimi_linear``: :func:`_kimi_linear_fields`).
    ``sliding_window`` is read with a ``hybrid_layer_pattern`` only: alone
    it is left unread, as it always was (full causal attention is the same
    model up to that many positions). ``first_expert`` / ``experts_held``
    say which routed experts this expert-parallel rank holds (0 = all)."""
    get = config.get
    scaling = get("rope_scaling")
    experts = get("n_routed_experts") or get("num_experts")
    scoring = get("scoring_func") or get("score_function") or "sigmoid"
    method = get("topk_method", "none")
    if method not in ("none", "greedy", "noaux_tc"):
        raise NotImplementedError(f"topk_method {method!r}")
    if experts and scoring != "sigmoid":
        raise NotImplementedError(f"scoring_func {scoring!r}")
    freq, leading_dense = get("moe_layer_freq", 1), None
    if experts and isinstance(freq, (list, tuple)):
        leading_dense = len(freq) - sum(freq)
        if (len(freq) != int(config["num_hidden_layers"])
                or list(freq) != [0] * leading_dense + [1] * sum(freq)):
            raise NotImplementedError(
                "moe_layer_freq as a list is read as zeros (dense layers) "
                "then ones (expert layers), one entry a layer")
    elif experts and freq != 1:
        raise NotImplementedError("moe_layer_freq != 1")
    if scaling and scaling.get("mscale", 1) != scaling.get("mscale_all_dim", 1):
        raise NotImplementedError("rotary cos/sin scale mscale / "
                                  "mscale_all_dim != 1")
    layers = int(config["num_hidden_layers"])
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(get(key, [])[:layers]):
            raise NotImplementedError(
                f"{key} is nonzero in a layer held: the clamped SwiGLU is "
                "not built")
    if get("num_nextn_predict_layers"):
        raise NotImplementedError(
            "multi-token prediction layers are not built: leave them out "
            "(num_nextn_predict_layers 0) where their loss weight is 0")
    group = int(get("layer_group_size") or 0)
    if group and not (get("kda_safe_gate") and get("linear_silu", True)
                      and get("no_kda_lora", True)):
        raise NotImplementedError(
            "linear attention is built as Kimi delta attention with the "
            "bounded gate (kda_safe_gate), SiLU after the short "
            "convolution and a full-rank decay projection (no_kda_lora)")
    lower = float(get("kda_lower_bound", -5.0))
    if group and not MIN_LOG_DECAY <= lower < 0:
        raise NotImplementedError(
            f"kda_lower_bound {lower}: the chunked delta rule is exact for "
            f"log-decays in [{MIN_LOG_DECAY}, 0)")
    if group and int(get("short_conv_kernel_size") or SHORT_CONV_TAPS
                     ) != SHORT_CONV_TAPS:
        raise NotImplementedError(
            f"short_conv_kernel_size {get('short_conv_kernel_size')}: the "
            f"short convolution is built over {SHORT_CONV_TAPS} positions")
    hybrid = (_nemotron_h_fields(config, layers)
              if get("model_type") == "nemotron_h" else {})
    kimi = (_kimi_linear_fields(config, layers)
            if get("model_type") == "kimi_linear" else {})
    # the nemotron_h router is the sigmoid one with a score-correction bias
    noaux = method == "noaux_tc" or bool(hybrid)
    if get("attention_bias"):
        raise NotImplementedError("attention_bias: the projections are "
                                  "built without biases")
    pattern = get("hybrid_layer_pattern")
    if pattern is not None and len(pattern) != layers:
        raise NotImplementedError(
            f"hybrid_layer_pattern has {len(pattern)} entries for "
            f"{layers} layers")
    latent = bool(get("kv_lora_rank"))
    # a stated head size beside grouped-query heads (latent attention has
    # its own nope / rope / v sizes, linear attention ``linear_head_dim``)
    quotient = int(config["hidden_size"]) // int(config["num_attention_heads"])
    head_size = 0 if latent or group else int(get("head_dim") or 0)
    if head_size == quotient:
        head_size = 0
    rotary_dim = 0
    if get("partial_rotary_factor") is not None and not (
            latent or group or hybrid):
        per_head = head_size or quotient
        rotary_dim = int(per_head * float(get("partial_rotary_factor")))
        if rotary_dim % 2 or rotary_dim <= 0:
            raise NotImplementedError(
                f"partial_rotary_factor {get('partial_rotary_factor')} of a "
                f"head of {per_head} gives {rotary_dim} rotary dims: rotary "
                "turns pairs")
    first_dense = (leading_dense if leading_dense is not None
                   else int(get("first_k_dense_replace") or 0))
    fields = dict(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        intermediate_size=int(config["intermediate_size"]),
        num_layers=layers,
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=get("num_key_value_heads"),
        max_seq_len=int(max_seq_len),
        rope_theta=float(get("rope_theta", 10000.0)),
        rms_eps=float(get("rms_norm_eps", get(
            "layernorm_epsilon", get("layer_norm_epsilon", 1e-6)))),
        dtype=dtype, attention_impl=attention_impl,
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        rope_scaling=dict(scaling) if scaling else None,
        routed_scaling_factor=float(get("routed_scaling_factor") or 1.0),
        norm_topk_prob=bool(get("norm_topk_prob", True)),
        **{k: int(get(k) or 0) for k in (
            "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
            "moe_intermediate_size")},
        first_k_dense_replace=first_dense,
        head_size=head_size, rotary_dim=rotary_dim,
        attn_value_scale=float(get("attention_value_scale") or 1.0),
        layer_pattern=None if pattern is None else tuple(
            int(bool(p)) for p in pattern),
        sliding_window=int(get("sliding_window") or 0) if pattern else 0,
        window_kv_heads=int(get("swa_num_key_value_heads") or 0),
        window_rope_theta=float(get("swa_rope_theta") or 0.0),
        window_sink=bool(get("add_swa_attention_sink_bias")),
        full_sink=bool(get("add_full_attention_sink_bias")),
        n_routed_experts=int(experts or 0),
        n_shared_experts=int(get("n_shared_experts")
                             or get("num_shared_experts") or 0),
        first_expert=int(first_expert), experts_held=int(experts_held),
        n_group=int(get("n_group") or 0) if noaux else 0,
        topk_group=int(get("topk_group") or 0) if noaux else 0,
        router_bias=noaux and bool(
            get("moe_router_enable_expert_bias", True)),
        linear_layers=tuple(i for i in range(layers)
                            if group and (i + 1) % group),
        linear_head_dim=int(get("head_dim") or 0) if group else 0,
        kda_lower_bound=lower,
        attn_output_gate=bool(group) and get(
            "gated_attention_proj_granularity_type") == "head_wise",
        **hybrid)
    # kimi_linear reads some of the keys the others share its own way
    return LLMConfig(**dict(fields, **kimi))


# sums a model with experts reports a step through the ``moe_stats``
# collection, and the trainer's metrics carry out of the round program
MOE_METRICS = tuple("moe_" + k for k in MOE_STATS)


@dataclasses.dataclass
class LLMBundle:
    """ModelBundle-compatible wrapper whose trainable pytree is the LoRA
    adapter tree (or the full params when ``lora_rank == 0``).

    With a frozen base the adapters run as the model's factored side path
    ``x W + ((x a) b) * (alpha / rank)`` and are never merged into ``W``:
    the base is a constant of the forward, so the backward pass takes the
    rank-r gradients of ``a`` and ``b`` and no ``[d_in, d_out]`` weight
    gradient of a frozen kernel. The base stays in the dtype it is given in
    (a bfloat16 checkpoint is held once, in bfloat16: the forward casts
    kernels to the compute dtype, which is then no copy)."""

    module: CausalLM
    cfg: LLMConfig
    base_params: Optional[PyTree]  # None = full fine-tune
    lora_rank: int
    lora_alpha: float
    name: str = "causal_lm"

    def init(self, rng: jax.Array, sample_input: jnp.ndarray) -> PyTree:
        if self.base_params is not None:
            return lora_init(rng, self.base_params, rank=self.lora_rank)
        return self.module.init(rng, sample_input[:1])["params"]

    def __post_init__(self):
        if self.base_params is None and self.cfg.n_routed_experts:
            raise NotImplementedError(
                "routed experts are frozen (llm/moe.py takes no weight "
                "gradient of them): fine-tune a model with experts through "
                "adapters (lora_rank > 0)")

    @property
    def extra_metrics(self):
        """Names of the sums ``apply(with_stats=True)`` returns."""
        cfg = self.cfg
        return ((MOE_METRICS if cfg.n_routed_experts else ())
                + (("moe_tokens_here",) if cfg.n_routed_experts
                   and cfg.n_group > 1 else ())
                + (("kda_layer_steps",) if cfg.linear_layers else ())
                + (("kda_decays", "kda_steep_decays")
                   if cfg.kda_gate == "softplus" else ())
                + (("attn_window_layer_steps",) if cfg.window_layers
                   else ())
                + (("ssm_layer_steps",) if cfg.ssm_layers else ()))

    def apply(self, params, x, rng=None, train=False, with_stats=False):
        """-> logits, or ``(logits, {name: sum})`` over
        :attr:`extra_metrics` with ``with_stats``."""
        del rng  # no dropout in the decoder
        variables, kwargs = {"params": params}, {}
        if self.base_params is not None:
            variables = {"params": self.base_params}
            kwargs = {"adapters": params,
                      "lora_scale": self.lora_alpha / self.lora_rank}
        if not with_stats:
            return self.module.apply(variables, x, train=train, **kwargs)
        logits, state = self.module.apply(
            variables, x, train=train,
            mutable=["moe_stats", "kda_stats", "attn_stats", "ssm_stats"],
            **kwargs)
        sums = {}
        for prefix in ("moe", "kda", "attn", "ssm"):
            for layer in state.get(prefix + "_stats", {}).values():
                # a layer's one module that sows under this collection
                for module in layer.values():
                    for k, v in module.items():
                        name = f"{prefix}_{k}"
                        sums[name] = sums.get(name, 0.0) + v
        return logits, sums


def build_llm_bundle(args) -> Tuple[LLMBundle, ByteTokenizer]:
    """Model-only build (no dataset): what serving replicas need — a
    replica restart must not pay corpus construction just to rebuild the
    bundle an artifact's params plug into."""
    cfg = llm_config_from_args(args)
    rng = jax.random.PRNGKey(int(getattr(args, "random_seed", 0)))
    module, base_params = init_llm(cfg, rng)
    rank = int(getattr(args, "lora_rank", 8))
    alpha = float(getattr(args, "lora_alpha", 16.0))
    bundle = LLMBundle(module, cfg,
                       base_params if rank > 0 else None, rank, alpha)
    return bundle, ByteTokenizer()


def build_llm(args) -> Tuple[Any, LLMBundle, CausalLMTrainer, ByteTokenizer]:
    """→ (fed_dataset, bundle, trainer_spec, tokenizer)."""
    bundle, _ = build_llm_bundle(args)
    n_silos = int(getattr(args, "client_num_in_total", 2))
    fed, tokenizer = build_llm_federated(args, n_silos,
                                         bundle.cfg.max_seq_len)
    spec = CausalLMTrainer(bundle.apply, bundle.extra_metrics)
    return fed, bundle, spec, tokenizer


def run_federated_llm(args) -> dict:
    """Run a federated LoRA fine-tune with the standard runner dispatch
    (simulation backend or cross-silo per ``args.training_type``).
    ``llm_adapter_export_dir`` additionally writes the global + per-silo
    personalized adapters as named artifacts the serving adapter bank
    (``serving/batch/``) loads."""
    from ..runner import FedMLRunner

    fed, bundle, spec, _ = build_llm(args)
    export_dir = getattr(args, "llm_adapter_export_dir", None)
    if export_dir and int(getattr(args, "lora_rank", 8)) <= 0:
        # fail BEFORE the (possibly hours-long) run, not after it
        raise ValueError("llm_adapter_export_dir needs lora_rank > 0 "
                         "(the adapter bank serves adapters over a "
                         "frozen base)")
    runner = FedMLRunner(args, dataset=fed, model=bundle,
                         client_trainer=spec)
    result = runner.run()
    export_dir = getattr(args, "llm_adapter_export_dir", None)
    if export_dir and isinstance(result, dict) and "params" in result:
        export_silo_adapters(args, export_dir, result=result,
                             prebuilt=(fed, bundle, spec))
    return result


# --- adapter-bank artifacts -------------------------------------------------
# The serving side of the federated-personalization loop: named LoRA
# adapter trees (kilobytes each) written with the msgpack artifact codec,
# plus a manifest the AdapterBank loads. One gateway then serves every
# silo's personalization side by side over a shared base model.

_MANIFEST = "manifest.json"


def _safe_name(name: str) -> str:
    import re
    safe = re.sub(r"[^A-Za-z0-9_.-]", "_", str(name))
    if not safe:
        raise ValueError(f"adapter name {name!r} is empty after "
                         "sanitization")
    return safe


def save_adapter_artifacts(adapters, out_dir: str, *,
                           lora_rank: Optional[int] = None,
                           lora_alpha: Optional[float] = None) -> str:
    """Write ``{name: adapter_tree}`` as one msgpack artifact per adapter
    plus ``manifest.json``; returns the manifest path."""
    import json
    import os

    from ..serving import save_model

    os.makedirs(out_dir, exist_ok=True)
    manifest = {"format": "fedml_tpu_adapter_bank_v1", "adapters": {}}
    if lora_rank is not None:
        manifest["lora_rank"] = int(lora_rank)
    if lora_alpha is not None:
        manifest["lora_alpha"] = float(lora_alpha)
    for name, tree in adapters.items():
        fname = _safe_name(name) + ".fmtpu"
        save_model(tree, os.path.join(out_dir, fname))
        manifest["adapters"][str(name)] = fname
    path = os.path.join(out_dir, _MANIFEST)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=2)
    os.replace(tmp, path)
    logger.info("adapter artifacts: %d adapters -> %s",
                len(manifest["adapters"]), out_dir)
    return path


def load_adapter_artifacts(manifest_dir: str) -> dict:
    """Manifest dir → ``{name: adapter_tree}`` (msgpack artifacts only —
    same trust story as every served model)."""
    import json
    import os

    from ..serving import load_model

    with open(os.path.join(manifest_dir, _MANIFEST)) as f:
        manifest = json.load(f)
    if manifest.get("format") != "fedml_tpu_adapter_bank_v1":
        raise ValueError(f"{manifest_dir}: not an adapter-bank manifest")
    return {name: load_model(os.path.join(manifest_dir, fname))
            for name, fname in manifest["adapters"].items()}


def personalize_adapter(spec, global_adapter, silo_data, *,
                        learning_rate: float = 1e-3, steps: int = 4,
                        step_fn=None):
    """A few local SGD steps from the global adapter over one silo's
    batches — the cheap per-silo personalization pass whose output the
    adapter bank serves. ``silo_data``: ``{"x": [nb, bs, L], "y", "mask"}``
    numpy/jnp arrays. Returns ``(adapter, step_fn)`` so callers
    personalizing many silos reuse the compiled step."""
    import optax

    opt = optax.sgd(float(learning_rate))
    if step_fn is None:
        def _step(params, opt_state, batch):
            grads, _ = jax.grad(spec.loss, has_aux=True)(params, batch,
                                                         None)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state
        step_fn = jax.jit(_step)
    params = global_adapter
    opt_state = opt.init(params)
    n_batches = int(silo_data["x"].shape[0])
    for s in range(int(steps)):
        j = s % n_batches
        batch = {"x": jnp.asarray(silo_data["x"][j]),
                 "y": jnp.asarray(silo_data["y"][j]),
                 "mask": jnp.asarray(silo_data["mask"][j])}
        params, opt_state = step_fn(params, opt_state, batch)
    return params, step_fn


def export_silo_adapters(args, out_dir: str, result: Optional[dict] = None,
                         prebuilt=None) -> str:
    """Federated LoRA → a served adapter bank: run (or reuse) the
    federated fine-tune, personalize the global adapter per silo with a
    few local steps on that silo's shard, and write ``global`` +
    ``silo_<i>`` named artifacts. Returns the manifest path."""
    if prebuilt is not None:
        fed, bundle, spec = prebuilt
    else:
        fed, bundle, spec, _ = build_llm(args)
    if int(getattr(args, "lora_rank", 8)) <= 0:
        raise ValueError("adapter export needs lora_rank > 0 (the bank "
                         "serves adapters over a frozen base)")
    if result is None:
        from ..runner import FedMLRunner
        result = FedMLRunner(args, dataset=fed, model=bundle,
                             client_trainer=spec).run()
    global_adapter = result["params"]
    adapters = {"global": global_adapter}
    steps = int(getattr(args, "llm_adapter_personalize_steps", 4))
    step_fn = None
    import numpy as np
    for i in range(fed.num_clients):
        silo = {"x": np.asarray(fed.train.x[i]),
                "y": np.asarray(fed.train.y[i]),
                "mask": np.asarray(fed.train.mask[i])}
        adapters[f"silo_{i}"], step_fn = personalize_adapter(
            spec, global_adapter, silo,
            learning_rate=float(getattr(args, "learning_rate", 1e-3)),
            steps=steps, step_fn=step_fn)
    return save_adapter_artifacts(
        adapters, out_dir,
        lora_rank=int(getattr(args, "lora_rank", 8)),
        lora_alpha=float(getattr(args, "lora_alpha", 16.0)))
