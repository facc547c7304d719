"""The benchmark harness: finds a cell's pieces by name, runs it, and
prints one result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic mix, job
kind or metric is a file of its own, found by the name in
``BENCHMARK.json``; adding one is adding files:

  workloads/<cell>.json     the cell: config, traffic, chips, and the
                            limits of its comparison
  configs/<config>.json     one model configuration, as run
  mixes/<traffic>.json      one traffic mix: the job its users run, the
                            job's arguments, and the rows (``traffic.py``)
  jobs/<job>.py             how the window drives the program
  metrics/<metric>.py       one reader per metric: ``read(run)`` returns
                            its value, or None where it finds nothing
  reference/<family>.py     the plain float32 reference of a family

A reader's ``run`` holds ``cell``, ``config``, ``mix``, ``chips``,
``device_kind``, ``setup_s``, ``window_s`` (host clock), ``steps``,
``tokens``, ``failed``, ``memory_peak_bytes``, ``flops_per_token``,
``counters`` (each of the program's counters that the job keeps, as the
window's last step read it) and, with ``--trace 1``, ``trace``
(``trace.reduce_profile``) and ``phases`` (``phases.split``: self time
by phase scope and by program, idle by innermost host span, seconds a
step; its ``phases`` is None for a job that gives no step HLO); else
both None.

A run: set-up (weights from the seed on the device, the job's first
steps, which compile), then ``--seconds`` of steps, then the peak
device memory, then — with the program's state freed — the reference,
which decides ``correct``. With ``--trace 1`` the window runs under the
profiler and the cell's per-layer metrics are read from the trace;
after the peak is read, the job compiles its step again for its HLO
text (``step_hlo``; from the process's own cache), which the phase
split reads. With ``--trace 0`` the end-to-end metrics are read.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Optional

import numpy as np

BENCH_DIR = Path(__file__).resolve().parent
REPO = BENCH_DIR.parent


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    ticks = os.sysconf("SC_CLK_TCK")
    start = float(Path("/proc/self/stat").read_text().rsplit(")", 1)[1]
                  .split()[19]) / ticks
    uptime = float(Path("/proc/uptime").read_text().split()[0])
    return uptime - start


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(path)
    name = "chipbench_" + "_".join(path.with_suffix("").parts[-2:]).replace(
        "-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """The benchmark's files, found by name."""

    def __init__(self, bench_dir: Path = BENCH_DIR,
                 benchmark_json: Optional[Path] = None):
        self.dir = Path(bench_dir)
        path = benchmark_json or self.dir.parent / "BENCHMARK.json"
        self.spec = json.loads(Path(path).read_text())

    def _json(self, kind: str, name: str) -> dict:
        return json.loads((self.dir / kind / f"{name}.json").read_text())

    def workload(self, name: str) -> dict:
        cell = self._json("workloads", name)
        entry = next((w for w in self.spec["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise KeyError(f"cell {name!r} is not in BENCHMARK.json")
        for key in ("config", "traffic", "chips"):
            if cell[key] != entry[key]:
                raise ValueError(f"cell {name}: {key} is {cell[key]!r} in "
                                 f"its file, {entry[key]!r} in "
                                 f"BENCHMARK.json")
        return dict(cell, name=name)

    def config(self, name: str) -> dict:
        return dict(self._json("configs", name), name=name)

    def mix(self, name: str) -> dict:
        from chipbench.traffic import load_mix
        return load_mix(self.dir, name)

    def job(self, name: str) -> ModuleType:
        return load_module(self.dir / "jobs" / f"{name}.py")

    def family(self, name: str) -> ModuleType:
        return load_module(self.dir / "reference" / f"{name}.py")

    def metrics(self, cell: str, kind: str) -> list:
        """(entry, reader module) of every ``kind`` metric the cell
        reports: those that list it, or list no cells at all."""
        out = []
        for m in self.spec[kind]:
            if "workloads" in m and cell not in m["workloads"]:
                continue
            out.append((m, load_module(self.dir / "metrics"
                                       / f"{m['name']}.py")))
        return out


class Context:
    """What a job gets: the cell and everything it names, the seed, and
    the devices it runs on."""

    def __init__(self, bench: Bench, cell: dict, seed: int, devices):
        from chipbench.traffic import MotifStream
        self.cell = cell
        self.config = bench.config(cell["config"])
        self.mix = bench.mix(cell["traffic"])
        #: the job kind the traffic asks for (``jobs/<job>.py``) and its
        #: arguments (consumers, learning rate)
        self.job, self.args = self.mix["job"], self.mix["job_args"]
        self.family = bench.family(self.config["family"])
        self.seed = int(seed)
        self.devices = list(devices)
        self.chips = len(self.devices)
        self.stream = MotifStream(self.mix, self.seed,
                                  self.config["vocab_size"])

    def key(self, purpose: int):
        """A JAX key for one purpose, from the seed (any size of seed)."""
        import jax
        state = np.random.SeedSequence([self.seed, purpose]).generate_state(1)
        return jax.random.PRNGKey(int(state[0]) & 0x7FFFFFFF)

    @property
    def seed31(self) -> int:
        return int(np.random.SeedSequence([self.seed, 0]).generate_state(1)[0]
                   ) & 0x7FFFFFFF


class CompileCounter:
    """Counts backend compiles (and persistent-cache loads) as JAX
    reports them."""

    def __init__(self):
        from jax._src import monitoring
        self.n = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def use_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where the environment sets it (JAX reads it itself), else a fixed
    directory in the checkout. Every program is kept, small ones too."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return 2


def window(job, seconds: float, first_step: int, trace_dir: Optional[str]):
    """Run steps until ``seconds`` have passed; the rate is taken over
    every step and the whole time, the last step included."""
    import jax
    records = []
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("window"):
            t0 = t1 = time.perf_counter()
            i = first_step
            while t1 - t0 < seconds:
                records.append(job.step(i))
                t = time.perf_counter()
                records[-1]["seconds"], t1 = t - t1, t
                i += 1
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    return records, t1 - t0


def judge(numbers: dict, limits: dict) -> tuple:
    """Each compared number beside its limit, and whether every one is
    within it."""
    checks = {k: {"value": numbers[k], "limit": limit}
              for k, limit in limits.items()}
    correct = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    return checks, correct


def find_xplane(trace_dir: str) -> str:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return str(found[0])


def run(argv=None, *, bench: Optional[Bench] = None,
        require_tpu: bool = True) -> int:
    args = parse_args(argv)
    bench = bench or Bench()
    try:
        cell = bench.workload(args.workload)
    except (FileNotFoundError, KeyError, ValueError) as e:
        return fail(f"unknown or inconsistent cell {args.workload!r}: {e}")

    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        return fail(f"JAX found no TPU (first device is "
                    f"{devices[0].platform}); nothing was run")
    if len(devices) < cell["chips"]:
        return fail(f"cell {cell['name']} needs {cell['chips']} chips, JAX "
                    f"found {len(devices)}")
    devices = devices[:cell["chips"]]
    try:
        import chipbench.program as program
        program.import_program()
    except ImportError as e:
        return fail(str(e))
    cache = use_compile_cache()
    compiles = CompileCounter()

    ctx = Context(bench, cell, args.seed, devices)
    job = bench.job(ctx.job).Job(ctx)
    job.setup()
    setup_s = process_age_s()
    print(f"chipbench: {cell['name']} seed {args.seed}: set-up "
          f"{setup_s:.2f} s (compile cache {cache})", file=sys.stderr,
          flush=True)

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    n_before = compiles.n
    records, window_s = window(job, args.seconds, job.first_window_step,
                               trace_dir)
    window_compiles = compiles.n - n_before
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    summary = split = None
    if trace_dir:
        from chipbench import phases
        from chipbench.trace import reduce_profile
        hlo = job.step_hlo() if hasattr(job, "step_hlo") else None
        xplane, ids = find_xplane(trace_dir), [d.id for d in devices]
        summary = reduce_profile(xplane, ids)
        split = phases.split(xplane, ids, step_hlo=hlo)
        shutil.rmtree(trace_dir, ignore_errors=True)

    from chipbench import flops
    record = {
        "cell": cell, "config": ctx.config, "mix": ctx.mix,
        "chips": ctx.chips, "device_kind": devices[0].device_kind,
        "setup_s": setup_s, "window_s": window_s,
        "steps": len(records),
        "tokens": sum(r["tokens"] for r in records),
        "failed": sum(bool(r["failed"]) for r in records),
        "memory_peak_bytes": peak,
        "flops_per_token": flops.per_token(ctx.config, ctx.mix["seq"],
                                           job.weight_grads),
        "counters": records[-1].get("counters", {}) if records else {},
        "trace": summary, "phases": split,
    }
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry, reader in bench.metrics(cell["name"], kind):
        value = reader.read(record)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    answers = job.answers()
    job.close()
    gc.collect()
    checks, correct = judge(job.numbers(answers, job.reference("f32")),
                            cell["limits"])

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": len(records),
              "failed": record["failed"], "metrics": metrics,
              "device": device, "window_compiles": window_compiles}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    step_s = [r["seconds"] for r in records]
    print(f"chipbench: {len(records)} steps in {window_s:.3f} s, "
          f"{window_compiles} compile(s) in the window; step median "
          f"{np.median(step_s):.4f} s, slowest {max(step_s):.4f} s (window "
          f"step {int(np.argmax(step_s))})", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct: {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
