"""``moe_grouped_roofline``: the expert layer's grouped products' share of
their roofline, in percent: the least time the chip could take for the
token-slots the program's own counters say it routed to held experts
(``fed_moe_slots_held_total`` over ``fed_moe_rounds_total``: the mean of
every round the program recorded, the set-up's among them, which train the
same rows through the same frozen router; times the traced rounds; FLOPs
and bytes from ``flops/<config>.py::grouped_expert_work``; padding rows
are no work), over the device time of the kernels named
``moe_grouped_fwd`` and ``moe_grouped_dx`` (``llm/moe.py``). Source: device
trace (the time) and a program counter (the work). Moves ``round_s``. Reads
nothing where the trace has no such kernel or the program no such
counter."""

KERNELS = ("moe_grouped_fwd", "moe_grouped_dx")


def slots_per_round():
    try:
        from fedml_tpu.core.obs import REGISTRY
        total = REGISTRY.counter("fed_moe_slots_held_total").value()
        recorded = REGISTRY.counter("fed_moe_rounds_total").value()
    except (ImportError, AttributeError):
        return None
    return total / recorded if recorded else None


def read(ctx):
    trace, peaks = ctx["trace"], ctx["peaks"]
    flops_mod = ctx["flops_module"]
    slots = slots_per_round()
    if (not trace or not peaks or not slots or not ctx["traced_rounds"]
            or not hasattr(flops_mod, "grouped_expert_work")):
        return None
    seconds = sum(total_s for name, (_, total_s) in trace["op_calls"].items()
                  if any(k in name.split(" ", 1)[0] for k in KERNELS))
    if seconds <= 0:
        return None
    cell, rounds = ctx["cell"], ctx["traced_rounds"]
    flops, bytes_ = flops_mod.grouped_expert_work(
        cell.config, slots * rounds,
        flops_mod.expert_layer_steps(cell.config, cell.traffic) * rounds)
    least = max(flops / peaks["bf16_flops_per_s"],
                bytes_ / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
