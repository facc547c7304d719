#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        [--control-seeds a,b,c] --out <dir>

In one process, for each seed: the program's numbers (the cell's own
set-up) against the float32 reference; and for each control seed, the
same numbers for the control — the reference in fp8 put in the
program's place — and for each fault the job can have, planted in the
reference put in the program's place. Each reading is judged against
the limits in the cell's file as a run judges it (``correct``). One
JSON line per reading goes to standard output and to
``<out>/calibrate-<cell>.jsonl``; ``calibration/<cell>.jsonl`` keeps
the readings the committed limits were set from. The benchmark's runs
do not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from chipbench.harness import (Bench, Context, fail, judge,  # noqa: E402
                               use_compile_cache)


def seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def readings(bench, cell, seed, devices, control: bool, emit) -> None:
    ctx = Context(bench, cell, seed, devices)
    job = bench.job(ctx.job).Job(ctx)
    t0 = time.perf_counter()
    job.setup()
    answers = job.answers()
    job.close()
    gc.collect()
    ref = job.reference("f32")
    emit(seed, "program", job.numbers(answers, ref),
         time.perf_counter() - t0)
    del answers
    if not control:
        return
    emit(seed, "control_fp8", job.numbers(job.reference("fp8"), ref), None)
    for fault in job.faults():
        emit(seed, f"fault_{fault}",
             job.numbers(job.reference("f32", fault=fault), ref), None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=seeds, required=True)
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    bench = Bench()
    cell = bench.workload(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail("JAX found no TPU; nothing was run")
    devices = devices[:cell["chips"]]
    use_compile_cache()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    with open(out / f"calibrate-{args.workload}.jsonl", "a") as log:
        def emit(seed, what, numbers, seconds):
            _, correct = judge(numbers, cell["limits"])
            line = json.dumps({"cell": args.workload, "seed": seed,
                               "what": what, "numbers": numbers,
                               "correct": correct, "seconds": seconds})
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()

        for seed in args.seeds + [s for s in args.control_seeds
                                  if s not in args.seeds]:
            readings(bench, cell, seed, devices, seed in args.control_seeds,
                     emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
