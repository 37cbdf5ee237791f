#!/usr/bin/env python3
"""``tools/calibrate_fault.py`` with the two faults that are a state-space /
``relu2`` model's own, each the reference with the fault put in the
system's place:

``no_decay``    every Mamba-2 head's ``A`` is 0: the state is a plain
                running sum of ``delta x B^T`` that forgets nothing: a
                program that lost the decay, or applied it to nothing.
``plain_relu``  the routed and the shared experts compute ``relu`` where
                the model has ``relu^2``: a program that took the wrong
                activation's branch.

    python3 benchmarks/tools/calibrate_fault_nemotron.py \
        --workload nemotron3_super_lora_silo2_seq4096 \
        --faults no_decay plain_relu half_steps --seeds 3 --first-seed 1000

Both are keys the reference reads and no configuration file sets
(``fault_no_decay``, ``fault_plain_relu``); the weights are the same.
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


def no_decay(cell, data, _spec):
    return _with(cell, fault_no_decay=True), data


def plain_relu(cell, data, _spec):
    return _with(cell, fault_plain_relu=True), data


calibrate_fault.FAULTS.update(no_decay=no_decay, plain_relu=plain_relu)

if __name__ == "__main__":
    sys.exit(calibrate_fault.main())
