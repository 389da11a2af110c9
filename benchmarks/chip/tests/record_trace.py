#!/usr/bin/env python3
"""Record the small profiler trace that ``test_bench_trace.py`` reads.

    python3 benchmarks/chip/tests/record_trace.py <out_dir>

Run on one TPU chip. Inside a host span ``bench.window`` it calls a small
jitted program (a ``while_loop`` of elementwise work on a 1024 x 1024
array) three times, each in a span ``bench.analytic``, and sleeps 20 ms
in a span ``bench.sleep`` after each call, so that the device is idle for
about 60 ms that the host spans account for. It writes the trace's
``.xplane.pb`` to ``<out_dir>/small.xplane.pb`` and prints its planes and
lines with a few events of each.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData
    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 2

    @jax.jit
    def work(x):
        return jax.lax.while_loop(lambda c: c[0] < 50,
                                  lambda c: (c[0] + 1, jnp.sin(c[1]) * 1.5),
                                  (0, x))[1]

    x = jnp.ones((1024, 1024), jnp.float32)
    work(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.analytic"):
                work(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "small.xplane.pb")
    shutil.copy(found[0], path)
    shutil.rmtree(tmp)
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:4]:
                print(f"    {ev.name!r} start={ev.start_ns} dur={ev.duration_ns}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
