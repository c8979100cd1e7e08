"""The control of the correctness check: the program itself with every
tile product of its fused kernels at float32 ``high`` precision instead of
``highest``.  The configurations state float32 with full-precision
products, so ``high`` (three bf16 passes: hi·hi + hi·lo + lo·hi) is the
nearest precision below, and lowering the kernels' one tile product is the
step a later change would be tempted to take.  The check has to find it
not correct.

The product is emulated with explicit bf16 operands, so the CPU (Pallas
interpret mode) computes what a TPU's ``Precision.HIGH`` does.

    python3 -m chipbench.control --workload <cell> --seeds 1,2,3 \\
        --seconds <s> --high <0|1>

runs the cell once per seed in one process, the program as it is
(``--high 0``) or lowered (``--high 1``), and prints one JSON line of
readings per seed.  It needs the chip like the benchmark; the benchmark's
own runs never run it.
"""
from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp

# the program's fused kernels, each of which takes its tile products from
# one function, ``tile_dot``
KERNELS = ("repro.kernels.band_cholesky", "repro.kernels.band_solve",
           "repro.kernels.selinv")


def tile_dot_high(a, b, trans_a: bool = False, trans_b: bool = False):
    """The program's ``tile_dot`` contract (2-D tiles, float32 result) at
    ``high`` precision: each operand split into a bf16 head and tail, the
    tail·tail product dropped."""
    if trans_a:
        a = a.T
    dims = (((1,), (1 if trans_b else 0,)), ((), ()))

    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    def dot(p, q):
        return jax.lax.dot_general(p, q, dims,
                                   preferred_element_type=jnp.float32)

    ah, al = split(a)
    bh, bl = split(b)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def lower_precision(setattr_=setattr) -> int:
    """Put :func:`tile_dot_high` in place of every kernel module's
    ``tile_dot``; call before anything is traced.  Returns how many
    modules were changed and raises where none has the function."""
    n = 0
    for name in KERNELS:
        mod = importlib.import_module(name)
        if hasattr(mod, "tile_dot"):
            setattr_(mod, "tile_dot", tile_dot_high)
            n += 1
    if not n:
        raise RuntimeError(f"none of {KERNELS} has a tile_dot to lower")
    return n


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys
    import time

    t_start = time.perf_counter()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "src"))
    from . import harness

    p = argparse.ArgumentParser(description="control readings of a cell")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--high", type=int, choices=(0, 1), required=True)
    args = p.parse_args(argv)

    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, args.workload)
    harness.configure_jax(root)
    devices = harness.require_chips(cell["chips"])
    if args.high:
        lower_precision()
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(bench, cell, seed, args.seconds, False,
                               t_start, devices)
        print(json.dumps({"who": "control" if args.high else "program",
                          "workload": cell["name"], "seed": seed,
                          "correct": res["correct"],
                          "attempted": res["attempted"],
                          "readings": res["readings"]}), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
