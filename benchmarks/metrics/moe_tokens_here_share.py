"""``moe_tokens_here_share``: the share, in percent, of tokens that send
this rank at least one of their top-k slots, over every pass through an
expert layer the program recorded: ``fed_moe_tokens_here_total`` over
``fed_moe_layer_steps_total`` times the tokens a train step
(``batch_size * seq_len`` of the cell's traffic), counters that the round
program's own result feeds (``core/obs/metrics.py``). Under group-limited
routing a token keeps ``topk_group`` of ``n_group`` groups, so a rank that
holds one group sees about ``topk_group / n_group`` of the tokens (50 at 4
of 8) and the expert layer's gathers, row buffers and grouped products
follow that share. Source: program counter. Moves ``round_s``. Reads
nothing where the program counted no such tokens (a program without the
counter, a model routed without a group limit)."""


def read(ctx):
    try:
        from fedml_tpu.core.obs import REGISTRY
        here = REGISTRY.counter("fed_moe_tokens_here_total").value()
        passes = REGISTRY.counter("fed_moe_layer_steps_total").value()
    except (ImportError, AttributeError):
        return None
    traffic = ctx["cell"].traffic
    tokens = traffic.get("batch_size", 0) * traffic.get("seq_len", 0)
    if not here or not passes or not tokens:
        return None
    return 100.0 * here / (passes * tokens)
