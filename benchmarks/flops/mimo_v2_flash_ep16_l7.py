"""FLOPs one federated LoRA round of ``mimo_v2_flash_ep16_l7`` needs, from
shapes.

What the algorithm needs on this rank, not what a program does. Per trained
position: a frozen matmul weight that the position USES costs 4 (forward and
the activation gradient; it has no weight gradient), an adapter weight 6,
the sliced head 4 * vocab * hidden; softmax attention over ``d_qk + d_v``
(forward 2, backward 4 a score and unit of head size) over the causal
half-square in a full layer and over the band in a window layer, where row
i has ``min(i + 1, window)`` keys; the sink, the rotary turn, the value
scale, the embedding lookup and the router's top-k cost nothing. Of the
routed experts a position uses those of its top-k that this rank holds:
``top_k * held / published`` of them in expectation; ``grouped_expert_work``
counts the slots a run really routed. There is no shared expert.
"""


def _is_window(cfg, layer):
    return bool(cfg["hybrid_layer_pattern"][layer])


def _attn_params(cfg, window):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    kv = cfg["swa_num_key_value_heads" if window else "num_key_value_heads"]
    d_qk, d_v = cfg["head_dim"], cfg["v_head_dim"]
    return {"q": (h, nh * d_qk), "k": (h, kv * d_qk), "v": (h, kv * d_v),
            "o": (nh * d_v, h)}


def _swiglu(h, width):
    return {"gate": (h, width), "up": (h, width), "down": (width, h)}


def _frozen(pairs):
    return sum(a * b for a, b in pairs.values())


def _adapters(pairs, rank):
    return sum(rank * (a + b) for a, b in pairs.values())


def band_scores(seq_len, window):
    """Scores of one head's row block: row i has ``min(i + 1, window)``."""
    w = min(window, seq_len)
    return w * (w + 1) // 2 + (seq_len - w) * w


def expected_slots_per_position(cfg):
    """Routed slots a position sends to the experts held here."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["published"]["n_routed_experts"])


def flops_per_position(cfg, seq_len):
    """The mean over a row's positions (a window row's keys grow to the
    window over its first positions)."""
    h, r = cfg["hidden_size"], cfg["lora_rank"]
    nh = cfg["num_attention_heads"]
    heads = nh * (cfg["head_dim"] + cfg["v_head_dim"])
    layers = cfg["num_hidden_layers"]
    n_window = sum(_is_window(cfg, i) for i in range(layers))
    n_sparse = sum(map(bool, cfg["moe_layer_freq"]))

    def per_attn(window):
        p = _attn_params(cfg, window)
        scores = (band_scores(seq_len, cfg["sliding_window"]) / seq_len
                  if window else seq_len / 2)       # keys a position
        return 4 * _frozen(p) + 6 * _adapters(p, r) + 6 * heads * scores

    dense = _swiglu(h, cfg["intermediate_size"])
    expert = _frozen(_swiglu(h, cfg["moe_intermediate_size"]))
    router = h * cfg["published"]["n_routed_experts"]
    per_dense = 4 * _frozen(dense) + 6 * _adapters(dense, r)
    per_sparse = 4 * (router + expected_slots_per_position(cfg) * expert)
    return (n_window * per_attn(True) + (layers - n_window) * per_attn(False)
            + (layers - n_sparse) * per_dense + n_sparse * per_sparse
            + 4 * cfg["vocab_size"] * h)


def flops_per_round(cfg, traffic):
    positions = (traffic["clients_per_round"] * traffic["rows_per_client"]
                 * traffic["seq_len"] * traffic["local_epochs"])
    return float(flops_per_position(cfg, traffic["seq_len"]) * positions)


def _kernel_work(cfg, traffic, scores, kv_heads):
    """{kernel: (FLOPs, bytes)} of one invocation of each of the three
    attention kernels over ``scores`` live scores a head and row. A product
    over a head size d costs 2 * scores * d. Forward: QK^T over d_qk and PV
    over d_v. dQ: the scores again (d_qk), dP = dO V^T (d_v), dQ = dS K
    (d_qk). dK/dV: the scores (d_qk), dV = P^T dO (d_v), dP (d_v), dK =
    dS^T Q (d_qk). Bytes: each kernel's bfloat16 operands and results once:
    q, o, do, dq at the query heads; k, v, dk, dv at the ``kv_heads`` the
    MODEL has (a program that repeats them before the kernel moves more
    than the work needs)."""
    s, nh = traffic["seq_len"], cfg["num_attention_heads"]
    d_qk, d_v = cfg["head_dim"], cfg["v_head_dim"]
    rows = traffic["batch_size"]
    unit = 2.0 * rows * nh * scores          # one product a unit of d
    q, o = rows * nh * s * d_qk * 2, rows * nh * s * d_v * 2    # bytes
    k, v = rows * kv_heads * s * d_qk * 2, rows * kv_heads * s * d_v * 2
    return {"fwd": (unit * (d_qk + d_v), float(q + k + v + o)),
            "dq": (unit * (2 * d_qk + d_v), float(2 * q + k + v + o)),
            "dkv": (unit * (2 * d_qk + 2 * d_v), float(q + 2 * k + 2 * v + o))}


def flash_kernel_work(cfg, traffic):
    """One invocation of each full-attention kernel (one batch of rows
    through one full layer): the causal half-square, ``s * s / 2`` scores a
    head as the other configurations' files count it."""
    s = traffic["seq_len"]
    return _kernel_work(cfg, traffic, s * s / 2.0,
                        cfg["num_key_value_heads"])


def window_kernel_work(cfg, traffic):
    """One invocation of each window kernel (one batch of rows through one
    window layer): row i has ``min(i + 1, window)`` keys. The sink's bytes
    (one float a head) are nothing."""
    return _kernel_work(
        cfg, traffic, band_scores(traffic["seq_len"], cfg["sliding_window"]),
        cfg["swa_num_key_value_heads"])


def train_steps(traffic):
    return (traffic["clients_per_round"] * traffic["local_epochs"]
            * -(-traffic["rows_per_client"] // traffic["batch_size"]))


def expert_layer_steps(cfg, traffic):
    """Expert layers times train steps a round."""
    return sum(map(bool, cfg["moe_layer_freq"])) * train_steps(traffic)


def grouped_expert_work(cfg, slots, layer_steps):
    """(FLOPs, bytes) the grouped products need for ``slots`` token-slots
    routed to held experts over ``layer_steps`` passes through an expert
    layer (forward and backward each): three products a slot forward and
    three for the activation gradient, 2 * hidden * width each; padding
    rows are no work. Bytes: every slot's operands and results once in
    bfloat16, and each held expert's three kernels once a pass and
    direction (the held kernels of one layer, 805 MB, fit no on-chip
    memory)."""
    h, w = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = slots * 6 * 2.0 * h * w
    rows = slots * 2.0 * 6 * (h + w)
    kernels = layer_steps * 2.0 * cfg["n_routed_experts"] * 3 * h * w * 2
    return flops, rows + kernels
