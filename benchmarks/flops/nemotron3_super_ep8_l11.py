"""FLOPs one federated LoRA round of ``nemotron3_super_ep8_l11`` needs, from
shapes.

What the algorithm needs on this rank, not what a program does. Per trained
position: a frozen matmul weight that the position USES costs 4 (forward and
the activation gradient; it has no weight gradient), an adapter weight 6,
the sliced head 4 * vocab * hidden; a frozen convolution tap 4; causal
softmax attention half of the full square over ``2 * head_dim`` (forward 2,
backward 4); the state-space recurrence 5 multiply-adds an element of a
head's ``P x N`` state a token (decay 1, rank-1 write 2, read for the output
2), its backward twice that; the embedding lookup and the router's top-k
cost nothing. Of the routed experts a position uses those of its top-k that
this rank holds: ``top_k * held / published`` of them in expectation;
``grouped_expert_work`` counts the slots a run really routed. An expert is
``relu(l U)^2 V``: two products, no gate.
"""

RECURRENCE_FLOPS = 5        # an element of the state, a token, forward
CHUNK = 128                 # the model's published chunk_size


def _mamba_params(cfg):
    h = cfg["hidden_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    wide = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return {"in_proj": (h, inner + wide + cfg["mamba_num_heads"]),
            "out_proj": (inner, h)}


def _attn_params(cfg):
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"q": (h, nh * d), "k": (h, kv * d), "v": (h, kv * d),
            "o": (nh * d, h)}


def _expert_params(cfg):
    """The adapted projections of an expert layer."""
    h, lat = cfg["hidden_size"], cfg["moe_latent_size"]
    shared = cfg["moe_shared_expert_intermediate_size"]
    return {"latent_down": (h, lat), "latent_up": (lat, h),
            "shared_up": (h, shared), "shared_down": (shared, h)}


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
    pattern = cfg["hybrid_override_pattern"]
    nh, p, n = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                cfg["ssm_state_size"])
    mamba, attn, expert = (_mamba_params(cfg), _attn_params(cfg),
                           _expert_params(cfg))
    conv = (nh * p + 2 * cfg["n_groups"] * n) * cfg["conv_kernel"]
    per_mamba = (4 * (_frozen(mamba) + conv) + 6 * _adapters(mamba, r)
                 + 3 * RECURRENCE_FLOPS * nh * p * n)
    per_attn = (4 * _frozen(attn) + 6 * _adapters(attn, r)
                + 3 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
                * seq_len)
    routed = 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]
    router = h * cfg["published"]["n_routed_experts"]
    per_expert = (4 * (_frozen(expert) + router
                       + expected_slots_per_position(cfg) * routed)
                  + 6 * _adapters(expert, r))
    return (pattern.count("M") * per_mamba + pattern.count("*") * per_attn
            + pattern.count("E") * per_expert + 4 * cfg["vocab_size"] * h)


def flops_per_round(cfg, traffic):
    positions = (traffic["clients_per_round"] * traffic["rows_per_client"]
                 * traffic["seq_len"] * traffic["local_epochs"])
    return float(flops_per_position(cfg, traffic["seq_len"]) * positions)


def flash_kernel_work(cfg, traffic):
    """{kernel: (FLOPs, bytes)} of ONE invocation of each flash kernel (one
    batch of rows through the one attention layer): causal half-squares
    over the head size (as ``flops/mimo_v2_flash_ep16_l7.py`` counts them),
    every bfloat16 operand and result once: q, o, do, dq at the query heads;
    k, v, dk, dv at the 2 key-value heads the MODEL has (a program that
    repeats them before the kernel moves more than the work needs)."""
    s, nh, kv = (traffic["seq_len"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"])
    d = cfg["head_dim"]
    rows = traffic["batch_size"]
    unit = 2.0 * rows * nh * s * s / 2.0      # one product a unit of d
    q = rows * nh * s * d * 2                 # bytes; o the same
    k = rows * kv * s * d * 2                 # v the same
    return {"fwd": (unit * 2 * d, float(2 * q + 2 * k)),
            "dq": (unit * 3 * d, float(3 * q + 2 * k)),
            "dkv": (unit * 4 * d, float(2 * q + 4 * k))}


def ssd_kernel_work(cfg, traffic):
    """{kernel: (FLOPs, bytes)} of ONE invocation of each state-space kernel
    (one batch of rows through one Mamba-2 layer): the chunked form's
    products at the model's PUBLISHED chunk of 128 positions, a constant of
    the model and no choice of a program: a head and position ``2 * chunk *
    P`` for the masked product inside the chunk and ``2 * P * N`` for each
    of the state's update and the state's read, a group and position ``2 *
    chunk * N`` for ``C B^T``; backward twice that. Bytes, every operand
    once: x and y in bfloat16, B and C at the groups the model has, the
    step size and its running sum in float32 a head; backward reads those
    and y's cotangent, writes a gradient for each, and reads the state that
    enters every chunk (float32): the same work whatever implements it."""
    s, nh, p = (traffic["seq_len"], cfg["mamba_num_heads"],
                cfg["mamba_head_dim"])
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    tokens = traffic["batch_size"] * s
    flops = tokens * (nh * (2.0 * CHUNK * p + 2 * 2.0 * p * n)
                      + g * 2.0 * CHUNK * n)
    x = tokens * nh * p * 2                       # bytes; y the same
    bc = 2 * tokens * g * n * 2
    steps = 2 * tokens * nh * 4
    states = tokens // CHUNK * nh * p * n * 4
    return {"fwd": (flops, float(2 * x + bc + steps)),
            "bwd": (2.0 * flops, float(3 * x + 2 * (bc + steps) + states))}


def train_steps(traffic):
    return (traffic["clients_per_round"] * traffic["local_epochs"]
            * -(-traffic["rows_per_client"] // traffic["batch_size"]))


def expert_layer_steps(cfg, traffic):
    """Expert layers times train steps a round."""
    return cfg["hybrid_override_pattern"].count("E") * train_steps(traffic)


def grouped_expert_work(cfg, slots, layer_steps):
    """(FLOPs, bytes) the grouped products need for ``slots`` token-slots
    routed to held experts over ``layer_steps`` passes through an expert
    layer (forward and backward each): four products a slot, ``up`` and
    ``down`` forward and their activation gradients, ``2 * latent * width``
    each (the expert is not gated); padding rows are no work. Bytes: every
    slot's operands and results once in bfloat16, and each held expert's
    two kernels once a pass and direction (the held kernels of one layer,
    705 MB, fit no on-chip memory)."""
    lat, w = cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    flops = slots * 4 * 2.0 * lat * w
    rows = slots * 2.0 * 4 * (lat + w)
    kernels = layer_steps * 2.0 * cfg["n_routed_experts"] * 2 * lat * w * 2
    return flops, rows + kernels
