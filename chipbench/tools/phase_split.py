#!/usr/bin/env python3
"""Run one training cell and split its step by phase and its idle time
by host action (``phases.py``).

    python3 chipbench/tools/phase_split.py --workload <cell> --seed <n> \
        --seconds <s> [--out <dir>]

In one process: the cell's set-up, as a run makes it; a window of
``--seconds`` with the profiler off, then one as long under it; then
the compiled step's HLO text (the job's ``step_hlo``: ``Trainer.lower_step``
compiled again, from the process's own cache) and the split of the traced
window. Prints one JSON line: the two windows' tokens per
second (what tracing costs), the split in ms a step, and
``phases.coverage``; with ``--out``, also writes it to
``<dir>/phase-split-<cell>-<seed>.json``. Exits 2 where JAX finds no
TPU. The benchmark's runs do not run this.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import phases  # noqa: E402
from chipbench.harness import (Bench, Context, fail, find_xplane,  # noqa: E402
                               process_age_s, use_compile_cache, window)


def cache_hits() -> list:
    """A one-item list that counts loads from the persistent compilation
    cache from now on."""
    from jax._src import monitoring
    hits = [0]

    def on(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            hits[0] += 1
    monitoring.register_event_listener(on)
    return hits


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    bench = Bench()
    cell = bench.workload(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail("JAX found no TPU; nothing was run")
    devices = devices[:cell["chips"]]
    from chipbench import program
    program.import_program()
    use_compile_cache()
    hits = cache_hits()
    ctx = Context(bench, cell, args.seed, devices)
    job = bench.job(ctx.job).Job(ctx)
    job.setup()
    setup_s = process_age_s()

    rates, step = {}, job.first_window_step
    trace_dir = tempfile.mkdtemp(prefix="phase-split-")
    for name, tdir in (("untraced", None), ("traced", trace_dir)):
        records, seconds = window(job, args.seconds, step, tdir)
        step += len(records)
        rates[name] = sum(r["tokens"] for r in records) / seconds

    t0, h0 = time.perf_counter(), hits[0]
    hlo = job.step_hlo() if hasattr(job, "step_hlo") else None
    hlo_s = time.perf_counter() - t0
    split = phases.split(find_xplane(trace_dir), [d.id for d in devices],
                         step_hlo=hlo)
    shutil.rmtree(trace_dir, ignore_errors=True)
    job.close()

    gap = split["longest_gap"]
    ms = lambda d: None if d is None else {
        k: 1e3 * v for k, v in sorted(d.items(), key=lambda kv: -kv[1])}
    result = {
        "cell": cell["name"], "seed": args.seed, "setup_s": setup_s,
        "device_kind": devices[0].device_kind,
        "tokens_per_s": rates,
        "tracing_cost": 1.0 - rates["traced"] / rates["untraced"],
        "step_hlo": {"seconds": hlo_s, "cache_hits": hits[0] - h0,
                     "read": hlo is not None},
        "window_s": split["window_s"], "busy_s": split["busy_s"],
        "steps": split["steps"],
        "phases_ms": ms(split["phases"]), "modules_ms": ms(split["modules"]),
        "idle_ms": ms(split["idle"]),
        "idle_in_modules_ms": ms(split["idle_in_modules"]),
        "longest_gap": {"ms": 1e3 * gap["s"], "span": gap["span"],
                        "module": gap["module"]},
        "coverage": phases.coverage(split),
    }
    line = json.dumps(result)
    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        (out / f"phase-split-{cell['name']}-{args.seed}.json").write_text(
            line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
