"""``scope_attn_ssm_ms``: device milliseconds a traced round in the scope
``attn.ssm``: the whole Mamba-2 mixer (the in and out projections, the
causal convolution, SiLU, softplus, the running decay sums, the SSD kernels,
the gated grouped norm), the ``lora`` side paths inside it left out; less
the ``ssd_fwd`` / ``ssd_bwd`` kernels' time (``ssd_kernels_roofline`` reads
those) it is the element-wise work around them. An operation counts under
its innermost scope only (``harness/scope_time.py``). Source: device trace.
Moves ``round_s``. Reads nothing without the program's scope table or a
trace, and nothing from a program without the scope."""

from harness import scope_time


def read(ctx):
    return scope_time.ms_a_round(ctx, "attn.ssm") or None
