#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, its traffic, the traffic's driver and the
per-layer metrics' readers are found by name (``loader.py``), so a new
cell, traffic mix or metric is new files and entries, not an edit here.

With no TPU, or fewer chips than the cell asks for, it exits 2 and prints
no result. Otherwise it sets up (the driver's build and warm-up), measures
for ``--seconds``, reads the peak device memory, then checks every answer
of the window against the plain reference. The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its
per-layer metrics with ``--trace 1``, read from a profiler trace of the
same window), ``device``, ``breakdown`` (traced runs) and, last,
``checks``: each number compared with its limit, also printed as the last
lines of standard error.

JAX's persistent compilation cache is kept in ``.jax_cache`` at the root
of the checkout, whatever the environment says, so that only the first
run of a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from loader import ROOT, UnknownName, cell, load, read_json  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"


class CompileClock:
    """JAX's own compile events: ``backend_compile_duration`` fires once
    per executable, whether XLA compiled it or loaded it from the
    persistent cache; ``cache_hits`` counts the loads."""

    XLA = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.xla_s, self.events, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == self.XLA:
            self.xla_s += secs
            self.events += 1

    def _event(self, event, **_):
        if event == self.HIT:
            self.hits += 1

    def snap(self):
        return (self.xla_s, self.events, self.hits)

    def delta(self, before):
        xla_s, events, hits = (a - b for a, b in zip(self.snap(), before))
        return {"xla_s": xla_s, "compiles": events - hits, "cache_hits": hits}


def use_checkout_cache() -> None:
    """Before JAX is imported: the persistent cache at the checkout's
    fixed path, every program cached whatever its compile time."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def chips_or_none(n: int):
    """The first ``n`` TPU devices, or None (with the reason on stderr)."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"run.py: JAX finds no devices: {e}", file=sys.stderr)
        return None
    if devs[0].platform != "tpu":
        print(f"run.py: no TPU (JAX sees {devs[0].platform}); refusing to run",
              file=sys.stderr)
        return None
    if len(devs) < n:
        print(f"run.py: the cell needs {n} chips, JAX sees {len(devs)}",
              file=sys.stderr)
        return None
    return devs[:n]


def run_cell(c: dict, seed: int, seconds: float, trace: bool, devices,
             t_start: float) -> dict:
    """Set up, measure and check one run of cell ``c`` on ``devices``;
    return its result object."""
    import jax
    import trace_reduce
    clock = CompileClock()
    session = load("drivers", c["traffic_file"]["driver"]).Session(
        c, seed, devices, clock)
    setup_s = time.perf_counter() - t_start
    tmp = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            session.window(seconds)
        if trace:
            jax.profiler.stop_trace()
            red = trace_reduce.reduce(trace_reduce.extract(
                trace_reduce.find_xplane(tmp)))
            if not any(red["busy_s"].values()):
                raise RuntimeError("no operation ran on the device in the "
                                   "traced window")
    finally:
        if tmp:
            shutil.rmtree(tmp, ignore_errors=True)
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)}
    checks, failed = session.check()
    attempted = len(session.outs)
    if trace:
        r = dict(session.readings(), trace=red, device_kind=d0.device_kind)
        values = {m["name"]: load("metrics", m["name"]).read(r)
                  for m in c["per_layer"]}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in c["per_layer"] if values[m["name"]] is not None}
        busy = red["busy_s"]
        device.update(busy_s=sum(busy.values()) / len(busy),
                      window_s=red["window_s"])
    else:
        values = dict(session.end_to_end(), setup_s=setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in c["end_to_end"]}
    result = {"correct": failed == 0 and attempted > 0,
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    print(session.summary(), file=sys.stderr)
    result["checks"] = {k: dict(v, value=_finite(v["value"]))
                        for k, v in checks.items()}
    return result


def _finite(x):
    """A number JSON can carry: a non-finite reading as its name."""
    return x if math.isfinite(x) else str(x)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        c = cell(read_json(ROOT / "BENCHMARK.json"), args.workload)
    except UnknownName as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    use_checkout_cache()
    devices = chips_or_none(int(c["chips"]))
    if devices is None:
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    result = run_cell(c, args.seed, args.seconds, bool(args.trace), devices,
                      T_START)
    for name, chk in result["checks"].items():
        print(f"check {name} = {chk['value']!r} (limit {chk['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
