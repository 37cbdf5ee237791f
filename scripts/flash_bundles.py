#!/usr/bin/env python3
"""What the flash kernels' loops cost in instruction bundles, read here, with
no chip: compiles ``flash_causal_attention`` forward and backward for a v5e
(``jax.experimental.topologies``) with libtpu's LLO dump on, and counts, in
each kernel's final bundles, every loop's length and what fills it.

    python3 scripts/flash_bundles.py 1 4096 4 192 128     # b s h d_qk d_v
    python3 scripts/flash_bundles.py 2 1024 4 128 128 --mask --blocks 256,256
    python3 scripts/flash_bundles.py --kda 1 4096 32 128  # b s h d
    python3 scripts/flash_bundles.py --kda 1 4096 32 128 --unbounded

``--kda`` does the same for the linear-attention kernels (``kda_fwd``,
``kda_bwd``: ``llm/linear_attention.py``), in the bounded gate's form or,
with ``--unbounded``, the form exact at any decay. Their body is
straight-line code, one chunk of two heads, so the only loop is the grid
and its length is a grid step.

A bundle issues in a cycle unless it waits, so a loop's length is the least
its iteration can take; PR 31's probes read 0.70-0.78 ns a bundle on the
v5e for these kernels (PERF.md section 6). One ``vmatmul`` of a 16-row bf16
register holds an MXU for 16 cycles, and there are four: ``vmatmul`` x 4 is
the MXU's own time for the loop, and a loop shorter than that is MXU-bound.
The outermost loop is the grid; what it holds beyond its inner loops is
paid once a program. Nothing here is a device time.
"""

from __future__ import annotations

import argparse
import collections
import glob
import os
import re
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUNDLE = re.compile(r"\s*(0x[0-9a-f]+|\d+)\s+(?:[A-Z]{2})?\s*:\s*>*\s*\{(.*)\}")
ASSIGNED = re.compile(r"=\s*([a-z][a-z0-9_]*)")    # %v1 = vadd.f32 ...
BARE = re.compile(r"^\s*%?\d*\s*([a-z][a-z0-9_]*)")  # %12 vst ... / sbr.rel
SHOWN = ("vmatmul", "vmatpush", "vpop", "vst", "vld", "vpow2", "vsel",
         "vcmp", "vmul", "vadd", "vsub", "vmax", "vxpose")


def loops(path):
    """[(first, last, {opcode: count})] for every back edge of the file."""
    bundles = {}
    for line in open(path):
        m = BUNDLE.match(line)
        if m:
            bundles[int(m.group(1), 0)] = m.group(2)
    out = []
    for n, text in bundles.items():
        for target in re.findall(r"sbr\.rel \([^)]*\) target bundleno = (\d+)",
                                 text):
            if int(target) <= n:
                ops = collections.Counter()
                for i in range(int(target), n + 1):
                    for ins in bundles.get(i, "").split(";;"):
                        m = ASSIGNED.search(ins) or BARE.search(ins)
                        if m:
                            ops[m.group(1)] += 1
                out.append((int(target), n, ops))
    return sorted(out)


def kda_train(args):
    """(function, argument shapes): ``kda_attention`` through the kernels,
    forward and backward, in bfloat16 as the benchmark runs it."""
    import jax
    import jax.numpy as jnp

    from fedml_tpu.llm.linear_attention import kda_attention

    b, s, h, d = args.kda
    qk = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((b, s, h, d), jnp.float32)
    beta = jax.ShapeDtypeStruct((b, s, h), jnp.float32)

    def train(*a):
        return jax.value_and_grad(
            lambda *a: kda_attention(
                *a, impl="flash", unbounded=args.unbounded).astype(
                jnp.float32).sum(), argnums=(0, 1, 2, 3, 4))(*a)

    return train, (qk, qk, qk, g, beta)


def flash_train(args):
    import jax
    import jax.numpy as jnp

    from fedml_tpu.llm.attention import flash_causal_attention

    b, s, h, d_qk, d_v = args.shape
    q = jax.ShapeDtypeStruct((b, s, h, d_qk), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((b, s, h, d_v), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((b, s), jnp.float32)
    block_q, block_k = (int(x) for x in args.blocks.split(","))

    def train(q, k, v, mask):
        return jax.value_and_grad(
            lambda q, k, v: flash_causal_attention(
                q, k, v, block_q, block_k,
                attn_mask=mask if args.mask else None).astype(
                    jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    return train, (q, q, v, mask)


def compile_with_dump(args):
    sys.path.insert(0, REPO)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from fedml_tpu.core import kernels

    device = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    where = SingleDeviceSharding(device)
    train, shapes = kda_train(args) if args.kda else flash_train(args)
    with kernels.compile_for_tpu():
        jax.jit(train, in_shardings=where, out_shardings=where).lower(
            *shapes).compile()


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("shape", type=int, nargs="*", metavar="N",
                    help="b s h d_qk d_v (the flash kernels)")
    ap.add_argument("--kda", type=int, nargs=4, metavar="N",
                    help="b s h d: the linear-attention kernels instead")
    ap.add_argument("--unbounded", action="store_true",
                    help="--kda: the form exact at any decay")
    ap.add_argument("--mask", action="store_true",
                    help="the variant with a key mask")
    ap.add_argument("--blocks", default="512,512")
    ap.add_argument("--dump", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if bool(args.kda) == bool(args.shape) or len(args.shape) not in (0, 5):
        ap.error("give b s h d_qk d_v, or --kda b s h d")
    if args.dump:           # the child: libtpu reads its flags when it loads
        return compile_with_dump(args)
    with tempfile.TemporaryDirectory() as dump:
        env = dict(os.environ, JAX_PLATFORMS="cpu", TPU_LOG_DIR="disabled",
                   LIBTPU_INIT_ARGS=f"--xla_jf_dump_to={dump} "
                                    "--xla_jf_dump_llo_text=true")
        # the dumper aborts on a report template this libtpu lacks, after
        # the bundles are written: the files decide, not the exit code
        subprocess.run([sys.executable, __file__, *sys.argv[1:],
                        "--dump", dump], env=env, capture_output=True)
        for kernel in (("kda_fwd", "kda_bwd") if args.kda else
                       ("flash_fwd", "flash_dq", "flash_dkv")):
            files = [f for f in glob.glob(
                f"{dump}/*{kernel}*final_bundles.txt")
                if "schedule-analysis" not in f]
            if not files:
                sys.exit(f"no final bundles for {kernel}: the compile failed")
            print(kernel)
            for first, last, ops in loops(sorted(files)[-1]):
                print(f"  loop [{first}, {last}]: {last - first + 1} bundles; "
                      + " ".join(f"{k} {ops[k]}" for k in SHOWN if ops[k]))


if __name__ == "__main__":
    main()
