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
from .model import CausalLM, LLMConfig, init_llm, layer_stats
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


# key maps' parts that families share, by the DeepSeek-V3 names (the
# Bailing and Kimi families name the experts ``num_experts`` /
# ``num_shared_experts``; a caller that holds some of them states the
# router's count under the first name, as ``fed_rounds_bailing.py`` does)
_EXPERTS = {"n_routed_experts": ("n_routed_experts", "num_experts"),
            "n_shared_experts": ("n_shared_experts", "num_shared_experts"),
            **{k: k for k in ("num_experts_per_tok", "moe_intermediate_size",
                              "routed_scaling_factor", "norm_topk_prob")}}
_LATENT = {k: k for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                          "qk_rope_head_dim", "v_head_dim")}
_DEFAULTS = {f.name: f.default for f in dataclasses.fields(LLMConfig)}


def _read(config: dict, keys: dict) -> dict:
    """A key map ``{field: published key, or keys in order}`` read in each
    field's type: a flag is its key's truth (the default where the key is
    missing), a size or scale the first key set to other than null or zero
    (else the default)."""
    out = {}
    for field, names in keys.items():
        names = (names,) if isinstance(names, str) else names
        default = _DEFAULTS[field]
        if isinstance(default, bool):
            out[field] = bool(config.get(names[0], default))
        else:
            got = [config[k] for k in names if config.get(k)]
            out[field] = type(default)(got[0] if got else default)
    return out


def _noaux(config: dict, always: bool = False) -> dict:
    """The group limit and the router's bias of routing by score + bias,
    where ``topk_method`` is ``noaux_tc`` (or ``always``)."""
    get = config.get
    if not (always or get("topk_method") == "noaux_tc"):
        return {}
    return dict(n_group=int(get("n_group") or 0),
                topk_group=int(get("topk_group") or 0),
                router_bias=bool(get("moe_router_enable_expert_bias", True)))


def _layers(config: dict, mixers, experts: int) -> tuple:
    """``mixer+moe`` where there are experts after the leading dense layers
    (``first_k_dense_replace``, or the zeros of a ``moe_layer_freq`` list
    before its ones), ``mixer+mlp`` elsewhere."""
    freq = config.get("moe_layer_freq", 1)
    dense = int(config.get("first_k_dense_replace") or 0)
    if experts and isinstance(freq, (list, tuple)):
        dense = len(freq) - sum(freq)
        if (len(freq) != len(mixers)
                or list(freq) != [0] * dense + [1] * sum(freq)):
            raise NotImplementedError(
                "moe_layer_freq as a list is read as zeros (dense layers) "
                "then ones (expert layers), one entry a layer")
    elif experts and freq != 1:
        raise NotImplementedError("moe_layer_freq != 1")
    return tuple(m + ("+moe" if experts and i >= dense else "+mlp")
                 for i, m in enumerate(mixers))


def _short_conv(taps) -> None:
    """Refuses a short convolution over other than 4 positions."""
    if int(taps or SHORT_CONV_TAPS) != SHORT_CONV_TAPS:
        raise NotImplementedError(
            f"short_conv_kernel_size {taps}: the short convolution is built "
            f"over {SHORT_CONV_TAPS} positions")


def _head_sizes(config: dict, rotary: bool = True) -> dict:
    """A stated ``head_dim`` (none where it is hidden / heads) and, with
    ``rotary``, the whole pairs ``partial_rotary_factor`` turns a head."""
    quotient = int(config["hidden_size"]) // int(config["num_attention_heads"])
    head_size = int(config.get("head_dim") or 0)
    head_size = 0 if head_size == quotient else head_size
    factor, rotary_dim = config.get("partial_rotary_factor"), 0
    if rotary and factor is not None:
        rotary_dim = int((head_size or quotient) * float(factor))
        if rotary_dim % 2 or rotary_dim <= 0:
            raise NotImplementedError(
                f"partial_rotary_factor {factor} of a head of "
                f"{head_size or quotient} gives {rotary_dim} rotary dims: "
                "rotary turns pairs")
    return dict(head_size=head_size, rotary_dim=rotary_dim)


def _deepseek_fields(config: dict, layers: int) -> dict:
    """The Llama/Mistral keys and DeepSeek-V3's (``axk1``): latent attention
    where ``kv_lora_rank`` is set, experts after leading dense layers.
    ``sliding_window`` stays unread: full causal attention is the same model
    up to that many positions."""
    fields = _read(config, {**_EXPERTS, **_LATENT})
    mixer = "latent" if fields["kv_lora_rank"] else "full"
    return dict(fields, **_noaux(config),
                **(_head_sizes(config) if mixer == "full" else {}),
                layers=_layers(config, [mixer] * layers,
                               fields["n_routed_experts"]))


def _bailing_fields(config: dict, layers: int) -> dict:
    """Bailing hybrid (Ling): Kimi delta attention with the bounded gate in
    all but the last layer of each ``layer_group_size``, gated latent
    attention in that one."""
    get = config.get
    if not (get("kda_safe_gate") and get("linear_silu", True)
            and get("no_kda_lora", True)):
        raise NotImplementedError(
            "linear attention is built as Kimi delta attention with the "
            "bounded gate (kda_safe_gate), SiLU after the short "
            "convolution and a full-rank decay projection (no_kda_lora)")
    fields = _read(config, {**_EXPERTS, **_LATENT,
                            "linear_head_dim": "head_dim"})
    lower = float(get("kda_lower_bound", _DEFAULTS["kda_lower_bound"]))
    if not MIN_LOG_DECAY <= lower < 0:
        raise NotImplementedError(
            f"kda_lower_bound {lower}: the chunked delta rule is exact for "
            f"log-decays in [{MIN_LOG_DECAY}, 0)")
    _short_conv(get("short_conv_kernel_size"))
    group = int(config["layer_group_size"])
    softmax = "latent" if fields["kv_lora_rank"] else "full"
    return dict(
        fields, **_noaux(config), kda_lower_bound=lower,
        attn_output_gate=get(
            "gated_attention_proj_granularity_type") == "head_wise",
        layers=_layers(config, ["linear" if (i + 1) % group else softmax
                                for i in range(layers)],
                       fields["n_routed_experts"]))


def _mimo_v2_flash_fields(config: dict, layers: int) -> dict:
    """MiMo-V2-Flash: window layers where ``hybrid_layer_pattern`` is 1,
    of the ``sliding_window`` / ``swa_*`` / sink keys; stated head sizes,
    partial rotary, a value scale; ``moe_layer_freq`` a list."""
    pattern = config.get("hybrid_layer_pattern") or [0] * layers
    if len(pattern) != layers:
        raise NotImplementedError(
            f"hybrid_layer_pattern has {len(pattern)} entries for "
            f"{layers} layers")
    fields = _read(config, {
        **_EXPERTS, "v_head_dim": "v_head_dim",
        "attn_value_scale": "attention_value_scale",
        "sliding_window": "sliding_window",
        "window_kv_heads": "swa_num_key_value_heads",
        "window_rope_theta": "swa_rope_theta",
        "window_sink": "add_swa_attention_sink_bias",
        "full_sink": "add_full_attention_sink_bias"})
    return dict(fields, **_noaux(config), **_head_sizes(config),
                layers=_layers(config, ["window" if p else "full"
                                        for p in pattern],
                               fields["n_routed_experts"]))


def _nemotron_h_fields(config: dict, layers: int) -> dict:
    """``nemotron_h``: one mixer a layer by ``hybrid_override_pattern``,
    ``M`` Mamba-2, ``E`` experts in a latent routed by score + bias, ``*``
    attention without rotary."""
    get = config.get
    pattern = get("hybrid_override_pattern") or ""
    if len(pattern) != layers or set(pattern) - set("ME*"):
        raise NotImplementedError(
            f"hybrid_override_pattern {pattern!r}: one of M (Mamba-2), E "
            f"(experts), * (attention) for each of the {layers} layers; the "
            "dense '-' layer of smaller siblings is not built")
    for key in ("use_bias", "mamba_proj_bias", "mlp_bias"):
        if get(key):
            raise NotImplementedError(f"{key}: the projections are built "
                                      "without biases")
    if get("mamba_hidden_act", "silu") != "silu":
        raise NotImplementedError(
            f"mamba_hidden_act {get('mamba_hidden_act')!r}")
    act = get("mlp_hidden_act", "relu2")
    if act not in ("relu2", "silu"):
        raise NotImplementedError(f"mlp_hidden_act {act!r}")
    fields = _read(config, {
        **_EXPERTS, "ssm_heads": "mamba_num_heads",
        "ssm_head_dim": "mamba_head_dim", "ssm_state_size": "ssm_state_size",
        "ssm_groups": "n_groups", "ssm_conv_kernel": "conv_kernel",
        "ssm_conv_bias": "use_conv_bias", "ssm_chunk": "chunk_size",
        "moe_latent_size": "moe_latent_size",
        "shared_expert_size": "moe_shared_expert_intermediate_size"})
    heads, groups = fields["ssm_heads"], fields["ssm_groups"]
    if heads % groups:
        raise NotImplementedError(
            f"n_groups {groups} does not divide mamba_num_heads {heads}")
    expand, inner = int(get("expand") or 2), heads * fields["ssm_head_dim"]
    if expand * int(config["hidden_size"]) != inner:
        raise NotImplementedError(
            f"expand {expand} x hidden_size {config['hidden_size']} is not "
            f"mamba_num_heads {heads} x mamba_head_dim {inner // heads}")
    return dict(
        fields, **_noaux(config, always=True),
        **_head_sizes(config, rotary=False), use_rope=False,
        mlp_activation="relu2" if act == "relu2" else "swiglu",
        layers=tuple({"M": "ssm", "E": "moe", "*": "full"}[c]
                     for c in pattern))


def _kimi_linear_fields(config: dict, layers: int) -> dict:
    """``kimi_linear``: Kimi delta attention under the unbounded softplus
    gate in ``linear_attn_config``'s 1-based ``kda_layers`` (the top-level
    ``head_dim`` is not its head size), latent attention without positions
    where ``mla_use_nope`` in the others; experts by score + bias."""
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
    _short_conv(linear.get("short_conv_kernel_size"))
    act = get("moe_router_activation_func", "sigmoid")
    if act != "sigmoid":
        raise NotImplementedError(f"moe_router_activation_func {act!r}")
    fields = _read(config, {
        **_EXPERTS, **_LATENT,
        "num_experts_per_tok": "num_experts_per_token",
        "norm_topk_prob": "moe_renormalize",
        **({"n_group": "num_expert_group", "topk_group": "topk_group"}
           if get("use_grouped_topk") else {})})
    softmax = "latent" if fields["kv_lora_rank"] else "full"
    return dict(
        fields, router_bias=True, kda_gate="softplus",
        linear_head_dim=int(linear["head_dim"]),
        use_rope=not get("mla_use_nope", False),
        layers=_layers(config, ["linear" if i + 1 in kda else softmax
                                for i in range(layers)],
                       fields["n_routed_experts"]))


def _keye_vl2_fields(config: dict, layers: int) -> dict:
    """``KeyeVL2``'s language model: in every layer grouped-query
    attention with an RMSNorm a head on q and k over the keys a lightning
    indexer selects (``sa_config``), then softmax-routed experts
    (``decoder_sparse_step`` 1, no ``mlp_only_layers``) without a shared
    one. ``mrope_section`` gives a text token one position in each of its
    sections, which then turn as one plain rotary over the whole head."""
    get = config.get
    sa = dict(get("sa_config") or {})
    if int(sa.get("indexer_num_kv_heads", 1)) != 1:
        raise NotImplementedError(
            f"indexer_num_kv_heads {sa['indexer_num_kv_heads']}: the "
            "indexer is built with one key head")
    if int(get("decoder_sparse_step", 1)) != 1 or get("mlp_only_layers"):
        raise NotImplementedError(
            "decoder_sparse_step != 1 or mlp_only_layers: every layer is "
            "built with experts")
    if get("use_sliding_window"):
        raise NotImplementedError("use_sliding_window: the layers are "
                                  "built without a window")
    fields = _read(config, _EXPERTS)
    head = int(get("head_dim") or 0)
    sections = (get("rope_scaling") or {}).get("mrope_section")
    if sections and 2 * sum(sections) != head:
        raise NotImplementedError(
            f"mrope_section {sections} covers {2 * sum(sections)} of a "
            f"head's {head} dims: a text token's rotary is built over all")
    return dict(fields, **_head_sizes(config), router_scoring="softmax",
                qk_norm=True, index_heads=int(sa["indexer_num_heads"]),
                index_head_dim=int(sa["indexer_head_dim"]),
                index_topk=int(sa["topk"]),
                layers=("sparse+moe",) * layers)


# a published ``model_type`` -> the key map of its family
_FAMILIES = {"axk1": _deepseek_fields, "bailing_hybrid": _bailing_fields,
             "mimo_v2_flash": _mimo_v2_flash_fields,
             "nemotron_h": _nemotron_h_fields,
             "kimi_linear": _kimi_linear_fields,
             "KeyeVL2": _keye_vl2_fields}


def _family(config: dict):
    """The key map of ``config``'s family: by its ``model_type`` where
    that names one, else Bailing by ``layer_group_size``, MiMo by
    ``hybrid_layer_pattern``, else the Llama/DeepSeek-V3 keys."""
    return _FAMILIES.get(config.get("model_type")) or (
        _bailing_fields if config.get("layer_group_size") else
        _mimo_v2_flash_fields if config.get("hybrid_layer_pattern") is not None
        else _deepseek_fields)


def llm_config_from_hf(config: dict, *, max_seq_len: int,
                       dtype: str = "float32", attention_impl: str = "dense",
                       first_expert: int = 0,
                       experts_held: int = 0) -> LLMConfig:
    """An :class:`LLMConfig` from a published ``config.json`` dict: the
    keys every family shares here, the rest (``layers`` among them) by its
    family's key map (:func:`_family`). ``first_expert`` / ``experts_held``
    say which routed experts this expert-parallel rank holds (0 = all)."""
    get = config.get
    if get("num_nextn_predict_layers"):
        raise NotImplementedError(
            "multi-token prediction layers are not built: leave them out "
            "(num_nextn_predict_layers 0) where their loss weight is 0")
    if get("attention_bias"):
        raise NotImplementedError("attention_bias: the projections are "
                                  "built without biases")
    method = get("topk_method", "none")
    if method not in ("none", "greedy", "noaux_tc"):
        raise NotImplementedError(f"topk_method {method!r}")
    score = get("scoring_func") or get("score_function") or "sigmoid"
    if (get("n_routed_experts") or get("num_experts")) and score != "sigmoid":
        raise NotImplementedError(f"scoring_func {score!r}")
    scaling = get("rope_scaling")
    if scaling and scaling.get("type", scaling.get("rope_type")) == "default":
        scaling = None      # the plain rotary
    if scaling and scaling.get("mscale", 1) != scaling.get("mscale_all_dim", 1):
        raise NotImplementedError("rotary cos/sin scale mscale / "
                                  "mscale_all_dim != 1")
    layers = int(config["num_hidden_layers"])
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        if any(get(key, [])[:layers]):
            raise NotImplementedError(
                f"{key} is nonzero in a layer held: the clamped SwiGLU is "
                "not built")
    return LLMConfig(
        vocab_size=int(config["vocab_size"]),
        hidden_size=int(config["hidden_size"]),
        intermediate_size=int(config["intermediate_size"]), num_layers=layers,
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=get("num_key_value_heads"), max_seq_len=int(max_seq_len),
        rope_theta=float(get("rope_theta", 10000.0)),
        rms_eps=float(get("rms_norm_eps", get(
            "layernorm_epsilon", get("layer_norm_epsilon", 1e-6)))),
        dtype=dtype, attention_impl=attention_impl,
        tie_embeddings=bool(get("tie_word_embeddings", False)),
        rope_scaling=dict(scaling) if scaling else None,
        first_expert=int(first_expert), experts_held=int(experts_held),
        remat=bool(get("gradient_checkpointing", False)),
        **_family(config)(config, layers))


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
        """Names of the sums ``apply(with_stats=True)`` returns: those the
        layers' kinds sow (:func:`~.model.layer_stats`),
        ``<prefix>_<sum>``."""
        return tuple(f"{prefix}_{k}" for prefix, sums
                     in layer_stats(self.cfg).items() for k in sums)

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
        prefixes = tuple(layer_stats(self.cfg))
        logits, state = self.module.apply(
            variables, x, train=train,
            mutable=[p + "_stats" for p in prefixes], **kwargs)
        sums = {}
        for prefix in prefixes:
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
