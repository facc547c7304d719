#!/usr/bin/env python3
"""Record the small profiler trace that ``tests/`` checks the trace
reduction on.

    python3 chipbench/tools/record_trace.py --out <dir>

On every TPU device of the machine it runs a few steps of a small
program — matrix products, and on several devices an all-reduce of a
sharded array (``psum``) — inside the harness's host spans (``window``
around ``loader`` / ``run_step`` / ``host_fetch``), with a host pause
in ``loader`` so that the device idles there. The ``.xplane.pb`` is
copied to ``<dir>/trace_small.xplane.pb``; a summary of its planes and
lines goes to standard output.
"""
import argparse
import shutil
import sys
import tempfile
import time
from pathlib import Path


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("record_trace: JAX found no TPU", file=sys.stderr)
        return 2
    mesh = Mesh(devices, ("d",))
    n = len(devices)

    def body(x):
        for _ in range(4):
            x = jnp.tanh(x @ x) * 0.5
        return jax.lax.psum(x, "d") if n > 1 else x

    step = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("d"),
                                 out_specs=P("d")))
    x = jax.device_put(jnp.ones((n * 1024, 1024), jnp.bfloat16) * 0.01,
                       NamedSharding(mesh, P("d")))
    jax.block_until_ready(step(x))

    tmp = tempfile.mkdtemp(prefix="record-trace-")
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(args.steps):
            with jax.profiler.TraceAnnotation("loader"):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("run_step"):
                y = step(x)
                jax.block_until_ready(y)
            with jax.profiler.TraceAnnotation("host_fetch"):
                float(y[0, 0])
    jax.profiler.stop_trace()

    src = next(Path(tmp).rglob("*.xplane.pb"))
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "trace_small.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)

    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(out / "trace_small.xplane.pb"))
    for plane in data.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(f"plane {plane.name!r}: {lines[:12]}")
        if plane.name.startswith("/device:TPU:0"):
            for ln in plane.lines:
                names = sorted({e.name for e in ln.events})
                print(f"  line {ln.name!r}: {names[:20]}")
    print(f"wrote {out / 'trace_small.xplane.pb'} "
          f"({(out / 'trace_small.xplane.pb').stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
