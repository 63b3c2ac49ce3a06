"""Readings that the limits in ``limits/<workload>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload heartbeat-paper --seeds 101 102 ... --controls 3

For each seed: the cell's engine is built and warmed up exactly as a
benchmark run does it (no window), and the comparison numbers are read for
the program against the plain reference (the lower readings).  On the first
``--controls`` seeds the same numbers are read for the reference put in the
program's place computed in bfloat16 (the control) and with each planted
fault of ``reference.FAULTS`` that the cell can have (the upper readings),
and, for the record only, the program against the reference with local
training at ``highest`` precision.
One JSON line per reading; runs on the chip, one process for all seeds.

``--emulate`` reads the same numbers on the CPU, where the chip's default
matmul precision does not exist: the reference then rounds its operands to
bfloat16 (``reference.EMULATED_DEFAULT``) and the program runs in float32,
so the sound readings stand for the gap that bfloat16 rounding opens
between the program and a float32 reference.  They are an emulation, not
readings of the program on the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time

import run as bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--emulate", action="store_true", help="emulate on the CPU (see above)")
    args = ap.parse_args(argv)
    cell = bench.load_cell(args.workload)
    if not args.emulate:
        bench.require_chips(int(cell["workload"]["chips"]))
    bench.enable_compile_cache()
    counter = bench.CompileCounter()
    sys.path.insert(0, str(bench.ROOT / "src"))
    import jax.numpy as jnp

    import compare
    import reference

    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        driver = bench.load_driver(cell["traffic"]["driver"])(cell["config"], cell["traffic"], seed)
        prog, _ = bench.warm_up(driver, counter)
        fed = driver.federation()
        if args.emulate:
            fed = dataclasses.replace(fed, precision=reference.EMULATED_DEFAULT)
        del driver
        gc.collect()
        t1 = time.perf_counter()
        ref = reference.run_calls(fed, seed, compare.CALLS)
        t2 = time.perf_counter()
        out = {"seed": seed, "kind": "program", "numbers": compare.numbers(prog, ref),
               "program_losses": [l for c in prog for l in c["losses"]],
               "reference_losses": [l for c in ref for l in c["losses"]],
               "program_s": t1 - t0, "reference_s": t2 - t1}
        print(json.dumps(out), flush=True)
        if i >= args.controls:
            continue
        variants = [("control_bf16", dict(dtype=jnp.bfloat16))] + [(f, dict(fault=f)) for f in reference.FAULTS]
        for kind, kw in variants:
            other = reference.run_calls(fed, seed, compare.CALLS, **kw)
            print(json.dumps({"seed": seed, "kind": kind, "numbers": compare.numbers(other, ref),
                              "losses": [l for c in other for l in c["losses"]]}), flush=True)
        # not compared: the program against the reference at ``highest``
        highest = reference.run_calls(dataclasses.replace(fed, precision="highest"), seed,
                                      compare.CALLS)
        print(json.dumps({"seed": seed, "kind": "program_vs_highest",
                          "numbers": compare.numbers(prog, highest)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
