"""FLOPs one federated LoRA round of ``mistral7b_v01_l4`` needs, from shapes.

They count what the algorithm needs, not what today's program does. Per
trained position: a frozen matmul weight costs 4 (forward and the activation
gradient; it has no weight gradient), an adapter weight 6, the frozen output
head 4 * vocab * hidden, causal attention 6 * hidden * seq a layer (half of
the full square, forward and backward); the embedding lookup costs nothing.
Today's program merges W + a @ b and takes full weight gradients, so the
hardware does about 6 a frozen weight and ``round_mfu`` reads near two thirds
of the hardware's own utilisation.
"""


def frozen_matmul_params_per_layer(cfg):
    h, i = cfg["hidden_size"], cfg["intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return h * q + 2 * h * kv + q * h + 3 * h * i


def adapter_params_per_layer(cfg):
    h, i, r = cfg["hidden_size"], cfg["intermediate_size"], cfg["lora_rank"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    pairs = ((h, q), (h, kv), (h, kv), (q, h), (h, i), (h, i), (i, h))
    return sum(r * (a + b) for a, b in pairs)


def flops_per_position(cfg, seq_len):
    layers = cfg["num_hidden_layers"]
    return (layers * (4 * frozen_matmul_params_per_layer(cfg)
                      + 6 * adapter_params_per_layer(cfg)
                      + 6 * cfg["num_attention_heads"] * cfg["head_dim"]
                      * seq_len)
            + 4 * cfg["vocab_size"] * cfg["hidden_size"])


def flops_per_round(cfg, traffic):
    positions = (traffic["clients_per_round"] * traffic["rows_per_client"]
                 * traffic["seq_len"] * traffic["local_epochs"])
    return float(flops_per_position(cfg, traffic["seq_len"]) * positions)


def flash_kernel_work(cfg, traffic):
    """{kernel: (FLOPs, bytes)} of ONE invocation of each flash kernel (one
    batch of rows through one layer), as each kernel's job needs them. A full
    [s, d] x [d, s] product is 2 * s * s * d a head; causal attention needs
    half the square. Forward: QK^T and PV (2 products). dQ: the scores again,
    dP = dO V^T, dQ = dS K (3). dK/dV: the scores, dV = P^T dO, dP,
    dK = dS^T Q (4). Bytes: each kernel reads its bfloat16 operands and writes
    its results once; k, v, dk and dv at the key-value heads, the rest at the
    query heads (today's program repeats k and v to the query heads before
    the kernel; the algorithm does not need that)."""
    s, d = traffic["seq_len"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    rows = traffic["batch_size"]
    half_square = rows * s * s * d * nh          # one causal product
    qh, kvh = rows * nh * s * d * 2, rows * nkv * s * d * 2   # bytes a tensor
    return {"fwd": (2.0 * half_square, 2.0 * qh + 2 * kvh),
            "dq": (3.0 * half_square, 3.0 * qh + 2 * kvh),
            "dkv": (4.0 * half_square, 2.0 * qh + 4 * kvh)}
