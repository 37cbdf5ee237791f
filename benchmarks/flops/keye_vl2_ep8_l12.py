"""FLOPs one federated LoRA round of ``keye_vl2_ep8_l12`` needs, from shapes.

What the algorithm needs on this rank, not what a program does. Per trained
position: a frozen matmul weight that the position USES costs 4 (forward and
the activation gradient; it has no weight gradient), an adapter weight 6,
the sliced head 4 * vocab * hidden. Attention runs over the SELECTED keys
alone, ``min(topk, t + 1)`` for position t: forward 2, backward 4 a score
and unit of head size over ``d_qk + d_v``. The indexer runs forward only (no
gradient reaches it): its projections cost 2 a weight, its scores 2 *
heads * head size over every causal key. The norms, the rotary, the
selection itself, the embedding lookup and the router's top-k cost nothing.
Of the routed experts a position uses those of its top-k that this rank
holds: ``top_k * held / published`` of them in expectation;
``grouped_expert_work`` counts the slots a run really routed. There is no
shared expert and no dense layer.
"""


def _attn_params(cfg):
    h, nh, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    d = cfg["head_dim"]
    return {"q": (h, nh * d), "k": (h, kv * d), "v": (h, kv * d),
            "o": (nh * d, h)}


def _index_params(cfg):
    sa, h = cfg["sa_config"], cfg["hidden_size"]
    return h * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                + sa["indexer_head_dim"] + sa["indexer_num_heads"])


def selected_scores(seq_len, topk):
    """Keys a row's queries keep in all: ``min(topk, t + 1)`` for t."""
    k = min(topk, seq_len)
    return k * (k + 1) // 2 + (seq_len - k) * k


def causal_scores(seq_len):
    return seq_len * (seq_len + 1) // 2


def expected_slots_per_position(cfg):
    """Routed slots a position sends to the experts held here."""
    return (cfg["num_experts_per_tok"] * cfg["num_experts"]
            / cfg["published"]["num_experts"])


def flops_per_position(cfg, seq_len):
    """The mean over a row's positions."""
    h, r = cfg["hidden_size"], cfg["lora_rank"]
    sa = cfg["sa_config"]
    p = _attn_params(cfg)
    frozen = sum(a * b for a, b in p.values())
    adapters = sum(r * (a + b) for a, b in p.values())
    units = cfg["num_attention_heads"] * 2 * cfg["head_dim"]
    attn = 6 * units * selected_scores(seq_len, sa["topk"]) / seq_len
    index = (2 * _index_params(cfg)
             + 2 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
             * causal_scores(seq_len) / seq_len)
    expert = 3 * h * cfg["moe_intermediate_size"]
    router = h * cfg["published"]["num_experts"]
    experts = 4 * (router + expected_slots_per_position(cfg) * expert)
    layer = 4 * frozen + 6 * adapters + attn + index + experts
    return (cfg["num_hidden_layers"] * layer
            + 4 * cfg["vocab_size"] * h)


def flops_per_round(cfg, traffic):
    positions = (traffic["clients_per_round"] * traffic["rows_per_client"]
                 * traffic["seq_len"] * traffic["local_epochs"])
    return float(flops_per_position(cfg, traffic["seq_len"]) * positions)


def sparse_kernel_work(cfg, traffic):
    """{kernel: (FLOPs, bytes)} of one invocation of each sparse attention
    kernel (one batch of rows through one layer), as the semantics needs
    them, not as an implementation chose to compute them (a kernel that
    computes less reads higher).

    ``index``: the index scores over every causal pair at every index head
    (2 * head size a pair and head; the ReLU and the weighted sum are
    nothing); bytes: the index q, k (bfloat16), the head weights (float32)
    and the selection written as one bit a causal pair. ``fwd``, ``dq``,
    ``dkv``: attention over the SELECTED pairs, ``min(t + 1, topk)`` keys a
    query: forward QK^T and PV (d_qk + d_v); dQ the scores again, dP and dS
    K (2 d_qk + d_v); dK/dV the scores, P^T dO, dP and dS^T Q (2 d_qk + 2
    d_v); a product over a head size d is 2 d a score and query head.
    Bytes: each kernel's bfloat16 operands and results once, q, o, do and
    dq at the query heads and k, v, dk, dv at the key-value heads (each
    read once for its group of query heads), and the selection's bits."""
    s, rows = traffic["seq_len"], traffic["batch_size"]
    sa = cfg["sa_config"]
    nh, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    ih, idim = sa["indexer_num_heads"], sa["indexer_head_dim"]
    bits = rows * causal_scores(s) / 8.0
    index = (2.0 * rows * ih * idim * causal_scores(s),
             rows * s * (ih * idim * 2 + idim * 2 + ih * 4) + bits)
    unit = 2.0 * rows * nh * selected_scores(s, sa["topk"])
    q = rows * nh * s * d * 2.0
    k = rows * kv * s * d * 2.0
    return {"index": index,
            "fwd": (unit * 2 * d, q + 2 * k + q + bits),
            "dq": (unit * 3 * d, 2 * q + 2 * k + q + bits),
            "dkv": (unit * 4 * d, q + 4 * k + q + bits)}


def train_steps(traffic):
    return (traffic["clients_per_round"] * traffic["local_epochs"]
            * -(-traffic["rows_per_client"] // traffic["batch_size"]))


def expert_layer_steps(cfg, traffic):
    """Expert layers times train steps a round: every layer has experts."""
    return cfg["num_hidden_layers"] * train_steps(traffic)


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
