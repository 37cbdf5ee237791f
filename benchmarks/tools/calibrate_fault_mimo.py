#!/usr/bin/env python3
"""``tools/calibrate_fault.py`` with the two faults that are a window
model's own, each the reference with the fault put in the system's place:

``no_window``  window layers attend causally to every earlier key (the
               window set to the row's length): a program that lost its
               window, or whose plan computes blocks it should skip.
``no_sink``    no layer's softmax has the sink's column (its logit at minus
               infinity): a program that dropped the operand. The other
               weights are the same: the reference draws a layer's sink
               whether or not the configuration has the flag.

    python3 benchmarks/tools/calibrate_fault_mimo.py \
        --workload mimo_v2_flash_lora_silo2_seq4096 \
        --faults no_window no_sink half_steps --seeds 3 --first-seed 1000

Everything else (``KEY=VALUE``, ``half_steps``, the printing, the exit
code) is that file's. Not part of a benchmark run.
"""

from __future__ import annotations

import copy
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import calibrate_fault  # noqa: E402


def _with(cell, **keys):
    twin = copy.copy(cell)
    twin.config = dict(cell.config, **keys)
    return twin


def no_window(cell, data, _spec):
    return _with(cell, sliding_window=cell.traffic["seq_len"]), data


def no_sink(cell, data, _spec):
    return _with(cell, add_swa_attention_sink_bias=False,
                 add_full_attention_sink_bias=False), data


calibrate_fault.FAULTS.update(no_window=no_window, no_sink=no_sink)

if __name__ == "__main__":
    sys.exit(calibrate_fault.main())
