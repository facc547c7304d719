#!/usr/bin/env python3
"""Record the small scoped profiler trace that ``tests/`` checks the
phase split (``phases.py``) on, with the compiled step's HLO text.

    python3 chipbench/tools/record_scoped_trace.py --out <dir>

On one TPU device it runs a few steps of a small program named as the
trainer's is (``jit_step_fn``): matrix products under the phase scope
``pex.forward``, some of them under ``pex.stats`` nested in it, and one
product under no scope. The host loop writes the harness's spans
(``window`` around ``loader`` / ``run_step``) and, inside
``run_step``, the program's span ``trainer.fetch_loss`` around the
wait for the result and a 3 ms host pause, after a 1 ms pause of
``run_step``'s own; ``loader`` pauses 2 ms. Writes
``<dir>/trace_scoped.xplane.pb`` and the compiled step's HLO text,
without its stack-frame tables, to ``<dir>/trace_scoped.hlo.txt``; a
summary of the device's lines goes to standard output.
"""
import argparse
import shutil
import sys
import tempfile
import time
from pathlib import Path


def without_stack_frames(hlo: str) -> str:
    """The HLO text less its stack-frame tables (source file names and
    lines), which the phase split does not read."""
    out, skip = [], False
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and (line.startswith("%") or line.startswith("ENTRY")):
            skip = False
        if not skip:
            out.append(line)
    return "\n".join(out) + "\n"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_scoped_trace: JAX found no TPU", file=sys.stderr)
        return 2

    def step_fn(x):
        with jax.named_scope("pex.forward"):
            for _ in range(2):
                x = jnp.tanh(x @ x) * 0.5
            with jax.named_scope("pex.stats"):
                s = jnp.tanh(x @ x.T) * 0.5
        return (s @ x) * 0.5

    step = jax.jit(step_fn)
    x = jax.device_put(jnp.ones((2048, 2048), jnp.bfloat16) * 0.01,
                       devices[0])
    jax.block_until_ready(step(x))
    hlo = without_stack_frames(step.lower(x).compile().as_text())

    tmp = tempfile.mkdtemp(prefix="record-scoped-trace-")
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(args.steps):
            with jax.profiler.TraceAnnotation("loader"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("run_step"):
                time.sleep(0.001)
                y = step(x)
                with jax.profiler.TraceAnnotation("trainer.fetch_loss"):
                    jax.block_until_ready(y)
                    time.sleep(0.003)
    jax.profiler.stop_trace()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(next(Path(tmp).rglob("*.xplane.pb")),
                out / "trace_scoped.xplane.pb")
    (out / "trace_scoped.hlo.txt").write_text(hlo)
    shutil.rmtree(tmp, ignore_errors=True)

    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(out / "trace_scoped.xplane.pb"))
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:0"):
            for ln in plane.lines:
                names = sorted({e.name.split(" = ")[0] for e in ln.events})
                print(f"line {ln.name!r}: {names[:20]}")
    for name in ("trace_scoped.xplane.pb", "trace_scoped.hlo.txt"):
        print(f"wrote {out / name} ({(out / name).stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
