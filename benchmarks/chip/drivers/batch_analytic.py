"""Batch analytics back to back: the traffic of a user who runs one
analytic over a partitioned graph and waits for its answer on the host.

The traffic file (``traffic/<name>.json``) gives:

  ``analytic``    the name of ``analytics/<analytic>.py`` (the program's
                  entry and the bytes of one sweep) and of
                  ``references/<analytic>.py`` (the plain reference and
                  the comparison)
  ``params``      keyword arguments of the program's entry
  ``mesh_parts``  0 for the one-device ``local`` backend; otherwise the
                  chips of a ``("parts",)`` mesh for the ``shard_map``
                  backend

Set-up builds the seed's view of the configuration's graph through the
program's GoFS build and makes one warm-up run, which compiles (or loads
from the persistent cache) every program the window uses. The window then
calls the entry back to back until ``seconds`` have passed. Every run's
answer is kept and compared with the reference once the window has closed.
"""
from __future__ import annotations

import time

import numpy as np

import graphs
from loader import load


class Session:
    def __init__(self, cell: dict, seed: int, devices, clock):
        import jax
        cfg, traffic = cell["config_file"], cell["traffic_file"]
        self.params = dict(traffic.get("params", {}))
        self.analytic = load("analytics", traffic["analytic"])
        self.reference = load("references", traffic["analytic"])
        k = int(traffic.get("mesh_parts", 0))
        self.mesh = (jax.make_mesh((k,), ("parts",), devices=devices[:k],
                                   axis_types=(jax.sharding.AxisType.Auto,))
                     if k else None)
        with jax.profiler.TraceAnnotation("bench.build"):
            self.ds = graphs.dataset(cfg)
            self.view = graphs.build(self.ds, cfg, seed)
        self.clock = clock
        before = clock.snap()
        with jax.profiler.TraceAnnotation("bench.warmup"):
            self._call()
        self.warmup = clock.delta(before)
        self.peak_after_warmup = peak_bytes(devices)
        self.outs, self.sweeps, self.run_s = [], [], []
        self.window_s = 0.0

    def _call(self):
        return self.analytic.call(self.view, self.params, self.mesh)

    def window(self, seconds: float) -> None:
        """Runs back to back until ``seconds`` have passed; the window is
        first start to last end."""
        import jax
        before = self.clock.snap()
        t0 = time.perf_counter()
        while True:
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.analytic"):
                out, tele = self._call()
            self.run_s.append(time.perf_counter() - t)
            self.outs.append(out)
            self.sweeps.append(int(np.asarray(tele.local_iters).sum()))
            if time.perf_counter() - t0 >= seconds:
                break
        self.window_s = time.perf_counter() - t0
        self.in_window = self.clock.delta(before)

    def check(self):
        """(numbers, failed runs): each number compared is its worst over
        the window's runs."""
        ds, ref = self.ds, self.reference
        want = ref.reference(ds.n, ds.src, ds.dst, ds.w, ds.root, self.params)
        worst, failed = {}, 0
        for out in self.outs:
            nums = ref.compare(self.view.to_canonical(out), want)
            if any(not v <= ref.LIMITS[k] for k, v in nums.items()):
                failed += 1
            for k, v in nums.items():
                worst[k] = v if k not in worst else max(worst[k], v)
        return {k: {"value": v, "limit": ref.LIMITS[k]}
                for k, v in worst.items()}, failed

    def end_to_end(self) -> dict:
        return {"analytic_s": self.window_s / len(self.outs),
                "peak_hbm_mib": self.peak_after_warmup / 2**20}

    def summary(self) -> str:
        """One line on the window, for standard error."""
        r = sorted(self.run_s)
        return (f"window: {len(r)} runs in {self.window_s:.3f} s; seconds "
                f"per run min {r[0]:.4f} median {r[len(r) // 2]:.4f} max "
                f"{r[-1]:.4f}; compiles {self.in_window['compiles']}, cache "
                f"loads {self.in_window['cache_hits']}")

    def readings(self) -> dict:
        """What the per-layer readers read, besides the trace."""
        pg = self.view.pg
        return {"build_s": self.view.build_s,
                "setup_compile_s": self.warmup["xla_s"],
                "runs": len(self.outs),
                "sweeps": self.sweeps,
                "window_compiles": self.in_window["compiles"],
                "window_cache_loads": self.in_window["cache_hits"],
                "num_parts": pg.num_parts,
                "full_sweep_bytes": self.analytic.full_sweep_bytes(
                    self.ds.n, self.ds.arcs)}


def peak_bytes(devices) -> int:
    """The largest ``peak_bytes_in_use`` over the devices."""
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
