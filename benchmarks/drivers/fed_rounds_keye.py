"""Driver ``fed_rounds_keye``: ``fed_rounds_hf`` for Keye-VL-2.0's language
model. Its configuration file keeps the family's published key names, which
count the routed experts as ``num_experts`` as the Bailing family's do, so
``fed_rounds_bailing``'s builder hands ``fed_rounds_hf``'s the view it reads
and ``llm_config_from_hf`` reads the rest (``sa_config``, the rotary's
``mrope_section``) itself.

Importing this file needs the program's sparse attention kernels; on a
program without them the import fails at once, before any data or weight is
made.
"""

from __future__ import annotations

from harness import manifest

from fedml_tpu.llm.sparse_attention import SPARSE_KERNEL_NAMES  # noqa: F401

bailing = manifest.load_module("drivers", "fed_rounds_bailing")


def build(cfg, traffic, program_seed, data, trainable, frozen):
    return bailing.build(cfg, traffic, program_seed, data, trainable, frozen)
