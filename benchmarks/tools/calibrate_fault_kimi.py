#!/usr/bin/env python3
"""``tools/calibrate_fault.py`` with the two faults that are Kimi Linear's
own, each the reference with the fault put in the system's place:

``clamp_decay``  every log-decay of the KDA layers clamped at -5, the
                 floor of the bounded gate: a program that ran the bounded
                 form's chunk step, or clamped the unbounded gate to keep it
                 in its range.
``rotary``       the latent layers turn their 64 rotary dims at
                 ``rope_theta`` (half-split) where the model has no
                 position term: a program that took the rotary branch.

    python3 benchmarks/tools/calibrate_fault_kimi.py \\
        --workload kimi_linear_lora_silo2_seq4096 \\
        --faults clamp_decay rotary half_steps --seeds 3 --first-seed 1000

Both are keys the reference reads and no configuration file sets
(``fault_clamp_decay``, ``fault_rotary``); the weights are the same.
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


def clamp_decay(cell, data, _spec):
    return _with(cell, fault_clamp_decay=True), data


def rotary(cell, data, _spec):
    return _with(cell, fault_rotary=True), data


calibrate_fault.FAULTS.update(clamp_decay=clamp_decay, rotary=rotary)

if __name__ == "__main__":
    sys.exit(calibrate_fault.main())
