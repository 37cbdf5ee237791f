"""FLOPs one federated LoRA round of ``axk1_ep16_l5`` needs, from shapes.

What the algorithm needs on this rank, not what a program does. Per trained
position: a frozen matmul weight that the position USES costs 4 (forward and
the activation gradient; it has no weight gradient), an adapter weight 6,
the sliced head 4 * vocab * hidden, causal attention half of the full
square over ``d_qk + d_v`` (forward 2, backward 4); the embedding lookup
and the router's top-k cost nothing. Of the routed experts a position uses
those of its top-k that this rank holds: ``top_k * held / published`` of
them in expectation (the router is near uniform over the published
experts); ``grouped_expert_work`` counts the slots a run really routed.
"""


def _latent_params(cfg):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return {"q_a": (h, cfg["q_lora_rank"]),
            "q_b": (cfg["q_lora_rank"], nh * qk),
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
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["published"]["n_routed_experts"])


def flops_per_position(cfg, seq_len):
    h, r = cfg["hidden_size"], cfg["lora_rank"]
    nh = cfg["num_attention_heads"]
    d_qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    attn = _latent_params(cfg)
    core = 3 * nh * (d_qk + cfg["v_head_dim"]) * seq_len
    dense = _swiglu(h, cfg["intermediate_size"])
    shared = _swiglu(h, cfg["moe_intermediate_size"] * cfg["n_shared_experts"])
    expert = _frozen(_swiglu(h, cfg["moe_intermediate_size"]))
    router = h * cfg["published"]["n_routed_experts"]
    n_dense = cfg["first_k_dense_replace"]
    n_sparse = cfg["num_hidden_layers"] - n_dense
    per_attn = 4 * _frozen(attn) + 6 * _adapters(attn, r) + core
    per_dense = 4 * _frozen(dense) + 6 * _adapters(dense, r)
    per_sparse = (4 * (_frozen(shared) + router
                       + expected_slots_per_position(cfg) * expert)
                  + 6 * _adapters(shared, r))
    return (cfg["num_hidden_layers"] * per_attn + n_dense * per_dense
            + n_sparse * per_sparse + 4 * cfg["vocab_size"] * h)


def flops_per_round(cfg, traffic):
    positions = (traffic["clients_per_round"] * traffic["rows_per_client"]
                 * traffic["seq_len"] * traffic["local_epochs"])
    return float(flops_per_position(cfg, traffic["seq_len"]) * positions)


def flash_kernel_work(cfg, traffic):
    """{kernel: (FLOPs, bytes)} of ONE invocation of each flash kernel (one
    batch of rows through one layer) at ``d_qk != d_v``. A causal product
    over a head size d is ``rows * heads * s * s * d`` (half the square).
    Forward: QK^T over d_qk and PV over d_v. dQ: the scores again (d_qk),
    dP = dO V^T (d_v), dQ = dS K (d_qk). dK/dV: the scores (d_qk), dV = P^T
    dO (d_v), dP (d_v), dK = dS^T Q (d_qk). Bytes: each kernel reads its
    bfloat16 operands and writes its results once (q, k, dq, dk at d_qk;
    v, o, do, dv at d_v), all at the 64 heads: the shared rotary key is
    one vector a token in the model, but a kernel that reads a key per head
    needs it per head."""
    s, nh = traffic["seq_len"], cfg["num_attention_heads"]
    d_qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    d_v = cfg["v_head_dim"]
    rows = traffic["batch_size"]
    half = rows * nh * s * s                 # one causal product a unit of d
    qk, v = rows * nh * s * d_qk * 2, rows * nh * s * d_v * 2   # bytes
    return {"fwd": (half * (d_qk + d_v), 2.0 * qk + 2 * v),
            "dq": (half * (2 * d_qk + d_v), 3.0 * qk + 2 * v),
            "dkv": (half * (2 * d_qk + 2 * d_v), 3.0 * qk + 3 * v)}


def expert_layer_steps(cfg, traffic):
    """Expert layers times train steps a round."""
    steps = (traffic["clients_per_round"] * traffic["local_epochs"]
             * -(-traffic["rows_per_client"] // traffic["batch_size"]))
    return (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]) * steps


def grouped_expert_work(cfg, slots, layer_steps):
    """(FLOPs, bytes) the grouped products need for ``slots`` token-slots
    routed to held experts over ``layer_steps`` passes through an expert
    layer (forward and backward each), as the program's counter reports the
    slots: three products a slot forward (gate, up, down) and three for the
    activation gradient, 2 * hidden * width each; padding rows are no work.
    Bytes: every slot's operands and results once in bfloat16 (in and out
    of each of the six products), and each held expert's three kernels once
    a pass and direction: no layout can read them less often, since the
    held kernels of one layer (1.06 GB) fit no on-chip memory."""
    h, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = slots * 6 * 2.0 * h * w
    rows = slots * 2.0 * 6 * (h + w)
    kernels = layer_steps * 2.0 * cfg["n_routed_experts"] * 3 * h * w * 2
    return flops, rows + kernels
