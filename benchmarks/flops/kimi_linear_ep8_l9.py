"""FLOPs one federated LoRA round of ``kimi_linear_ep8_l9`` needs, from shapes.

What the algorithm needs on this rank, not what a program does, counted as
``flops/ling3flash_ep8_l7.py`` counts it. Per trained position: a frozen
matmul weight that the position USES costs 4 (forward and the activation
gradient; it has no weight gradient), an adapter weight 6, the sliced head
4 * vocab * hidden; a frozen convolution tap 4; causal softmax attention half
of the full square over ``d_qk + d_v`` (forward 2, backward 4); the delta
rule's recurrence 7 multiply-adds an element of a head's ``d_k x d_v`` state
a token, its backward twice that (Ling's count: how a program cuts the row
into chunks, and what its exact form of the chunks' diagonal blocks costs,
is no work); the embedding lookup and the router's top-k cost nothing. Of
the routed experts a position uses those of its top-k that this rank holds:
``top_k * held / published`` of them in expectation; ``grouped_expert_work``
counts the slots a run really routed. The KDA layers' heads and head size
are ``linear_attn_config``'s (the top-level ``head_dim`` is hidden /
heads).
"""

RECURRENCE_FLOPS = 7        # an element of the state, a token, forward


def _linear(cfg):
    return cfg["linear_attn_config"]


def _is_linear(cfg, layer):
    return layer + 1 in _linear(cfg)["kda_layers"]


def _kda_params(cfg):
    h = cfg["hidden_size"]
    d = _linear(cfg)["head_dim"]
    wide = _linear(cfg)["num_heads"] * d
    return {"q": (h, wide), "k": (h, wide), "v": (h, wide), "o": (wide, h)}


def _kda_gates(cfg):
    """The frozen products beside q k v o: beta a head, the decay's and the
    output gate's low-rank pairs."""
    h = cfg["hidden_size"]
    nh, d = _linear(cfg)["num_heads"], _linear(cfg)["head_dim"]
    return {"b": (h, nh), "f_a": (h, d), "f_b": (d, nh * d), "g_a": (h, d),
            "g_b": (d, nh * d)}


def _latent_params(cfg):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return {"q": (h, nh * qk),
            "kv_a": (h, cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]),
            "kv_b": (cfg["kv_lora_rank"],
                     nh * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"])),
            "o": (nh * cfg["v_head_dim"], h)}


def _swiglu(h, width):
    return {"gate": (h, width), "up": (h, width), "down": (width, h)}


def _frozen(pairs):
    return sum(a * b for a, b in pairs.values())


def _adapters(pairs, rank):
    return sum(rank * (a + b) for a, b in pairs.values())


def expected_slots_per_position(cfg):
    """Routed slots a position sends to the experts held here."""
    return (cfg["num_experts_per_token"] * cfg["num_experts"]
            / cfg["published"]["num_experts"])


def flops_per_position(cfg, seq_len):
    h, r = cfg["hidden_size"], cfg["lora_rank"]
    nh, d = _linear(cfg)["num_heads"], _linear(cfg)["head_dim"]
    layers = cfg["num_hidden_layers"]
    n_linear = sum(_is_linear(cfg, i) for i in range(layers))
    kda, latent = _kda_params(cfg), _latent_params(cfg)
    per_kda = (4 * (_frozen(kda) + _frozen(_kda_gates(cfg))
                    + 3 * nh * d * _linear(cfg)["short_conv_kernel_size"])
               + 6 * _adapters(kda, r)
               + 3 * RECURRENCE_FLOPS * nh * d * d)
    d_qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    per_latent = (4 * _frozen(latent) + 6 * _adapters(latent, r)
                  + 3 * cfg["num_attention_heads"]
                  * (d_qk + cfg["v_head_dim"]) * seq_len)
    dense = _swiglu(h, cfg["intermediate_size"])
    shared = _swiglu(h, cfg["moe_intermediate_size"]
                     * cfg["num_shared_experts"])
    expert = _frozen(_swiglu(h, cfg["moe_intermediate_size"]))
    router = h * cfg["published"]["num_experts"]
    n_dense = cfg["first_k_dense_replace"]
    per_dense = 4 * _frozen(dense) + 6 * _adapters(dense, r)
    per_sparse = (4 * (_frozen(shared) + router
                       + expected_slots_per_position(cfg) * expert)
                  + 6 * _adapters(shared, r))
    return (n_linear * per_kda + (layers - n_linear) * per_latent
            + n_dense * per_dense + (layers - n_dense) * per_sparse
            + 4 * cfg["vocab_size"] * h)


def flops_per_round(cfg, traffic):
    positions = (traffic["clients_per_round"] * traffic["rows_per_client"]
                 * traffic["seq_len"] * traffic["local_epochs"])
    return float(flops_per_position(cfg, traffic["seq_len"]) * positions)


def flash_kernel_work(cfg, traffic):
    """{kernel: (FLOPs, bytes)} of ONE invocation of each flash kernel (one
    batch of rows through one latent-attention layer) at ``d_qk != d_v``:
    causal half-squares over the head sizes, every bfloat16 operand and
    result once (as ``flops/ling3flash_ep8_l7.py`` counts them)."""
    s, nh = traffic["seq_len"], cfg["num_attention_heads"]
    d_qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    d_v = cfg["v_head_dim"]
    rows = traffic["batch_size"]
    half = rows * nh * s * s
    qk, v = rows * nh * s * d_qk * 2, rows * nh * s * d_v * 2   # bytes
    return {"fwd": (half * (d_qk + d_v), 2.0 * qk + 2 * v),
            "dq": (half * (2 * d_qk + d_v), 3.0 * qk + 2 * v),
            "dkv": (half * (2 * d_qk + 2 * d_v), 3.0 * qk + 3 * v)}


def kda_kernel_work(cfg, traffic):
    """{kernel: (FLOPs, bytes)} of ONE invocation of each KDA kernel (one
    batch of rows through one linear-attention layer), as
    ``flops/ling3flash_ep8_l7.py`` counts it, so the two cells' shares
    compare: the recurrence's own FLOPs (``RECURRENCE_FLOPS`` an element of
    every head's state a token forward, twice that backward) and every
    operand and result once: q, k, v and o in bfloat16, the log-decay in
    float32 a key channel, beta in float32 a head; backward reads them and
    o's cotangent and writes a gradient for each."""
    s, nh, d = traffic["seq_len"], _linear(cfg)["num_heads"], \
        _linear(cfg)["head_dim"]
    tokens = traffic["batch_size"] * s * nh
    state = RECURRENCE_FLOPS * tokens * d * d
    operands = tokens * (3 * d * 2 + d * 4 + 4)      # q k v, g, beta
    out = tokens * d * 2
    return {"fwd": (float(state), float(operands + out)),
            "bwd": (2.0 * state, float(2 * operands + out))}


def expert_layer_steps(cfg, traffic):
    """Expert layers times train steps a round."""
    return ((cfg["num_hidden_layers"] - cfg["first_k_dense_replace"])
            * train_steps(traffic))


def train_steps(traffic):
    return (traffic["clients_per_round"] * traffic["local_epochs"]
            * -(-traffic["rows_per_client"] // traffic["batch_size"]))


def grouped_expert_work(cfg, slots, layer_steps):
    """(FLOPs, bytes) the grouped products need for ``slots`` token-slots
    routed to held experts over ``layer_steps`` passes through an expert
    layer (forward and backward each): three products a slot forward and
    three for the activation gradient, 2 * hidden * width each; padding
    rows are no work. Bytes: every slot's operands and results once in
    bfloat16, and each held expert's three kernels once a pass and
    direction."""
    h, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = slots * 6 * 2.0 * h * w
    rows = slots * 2.0 * 6 * (h + w)
    kernels = layer_steps * 2.0 * cfg["num_experts"] * 3 * h * w * 2
    return flops, rows + kernels
