"""The four-chip cell's per-layer readers: on readings, registries and
stage maps made by hand, on a program that records none of them, and on
one tiny run of the cell through ``run.run_cell`` on four host devices.

The CPU's profiler records no device ops, so that run's trace reduction is
given one made-up interval per HLO instruction of the program's own
compiled loops (``program_obs.op_stages``); its counters, stage names and
answers are the program's."""
import time

import jax
import pytest

import run
import trace_reduce
from loader import ROOT, cell, load, read_json
from repro.obs import MetricsRegistry

CELL = "delaunay-n16.sssp-4chip"
NEW = ("collective_share", "mesh_sweep_device_s",
       "mesh_lockstep_sweeps_per_run", "chip_wait_share")
STAGES = {"jit_gopher_tiered": {"fusion.12": "gopher.sweep",
                                "fusion.13": "gopher.sweep",
                                "while.4": "gopher.sweep",
                                "all-to-all.2": "gopher.route",
                                "fusion.30": "gopher.deliver"}}


def readings(runs=2, chips=4):
    ops = [["jit_gopher_tiered:while.4 while tuple", 40.0],
           ["jit_gopher_tiered:fusion.12 fusion f32[4,4096,16]", 24.0],
           ["jit_gopher_tiered:fusion.13 fusion pred[4,4096,16]", 8.0],
           ["jit_gopher_tiered:all-to-all.2 all-to-all f32[4,2,64]", 4.0],
           ["jit_gopher_tiered:fusion.30 fusion f32[4,4096]", 1.0]]
    return {"runs": runs,
            "trace": {"device_ops": ops, "window_s": 12.0,
                      "busy_s": {d: 10.0 for d in range(chips)},
                      "collective_s": {d: 2.5 for d in range(chips)}}}


def registry(lock=(1000, 1100, 1200), wait=(300, 400, 500)):
    """A warm-up run and two window runs of each count."""
    reg = MetricsRegistry()
    for a, b in zip(lock, wait):
        reg.histogram("engine_lockstep_sweeps").observe(a)
        reg.histogram("engine_chip_wait_sweeps").observe(b)
    return reg


def test_the_cell_and_its_metrics_are_entries():
    c = cell(read_json(ROOT / "BENCHMARK.json"), CELL)
    assert c["chips"] == 4 and c["traffic_file"]["mesh_parts"] == 4
    assert [m["name"] for m in c["per_layer"]] == list(NEW)
    for m in c["per_layer"]:
        assert m["workloads"] == [CELL] and m["moves"] == "analytic_s"


def test_the_layout_keeps_the_one_chip_graph():
    """The 2x2 deployment differs from ``delaunay-n16`` in its layout
    alone: the same graph, weights, partitioning, root and guarantees."""
    spec = read_json(ROOT / "BENCHMARK.json")
    cfg = cell(spec, CELL)["config_file"]
    one = cell(spec, "delaunay-n16.sssp")["config_file"]
    layout = {"name", "source", "deployment", "chips",
              "partitions_per_chip", "assumed"}
    assert {k: v for k, v in cfg.items() if k not in layout} \
        == {k: v for k, v in one.items() if k not in layout}
    assert cfg["chips"] * cfg["partitions_per_chip"] == cfg["partitions"]
    assert cfg["chips"] == cell(spec, CELL)["traffic_file"]["mesh_parts"]


def test_collective_share_is_collective_over_busy_time():
    assert load("metrics", "collective_share").read(readings()) \
        == pytest.approx(25.0)


def test_mesh_sweep_device_s_is_per_run_per_chip():
    # 24 + 8 s of sweep fusions in 2 runs over 4 chips; the while skipped
    assert load("metrics", "mesh_sweep_device_s").read(readings(), STAGES) \
        == pytest.approx(32.0 / 2 / 4)


def test_mesh_lockstep_sweeps_per_run_is_the_mean_of_the_window():
    assert load("metrics", "mesh_lockstep_sweeps_per_run").read(
        {"runs": 2}, registry().recent) == pytest.approx(1150.0)


def test_chip_wait_share_is_wait_over_all_chips_slots():
    got = load("metrics", "chip_wait_share").read(readings(),
                                                  registry().recent)
    assert got == pytest.approx(100.0 * 900 / (4 * 2300))
    one = load("metrics", "chip_wait_share").read(
        readings(chips=1), registry(wait=(0, 0, 0)).recent)
    assert one == 0.0


@pytest.mark.parametrize("name", NEW)
def test_mesh_readers_find_nothing_to_read(name):
    read = load("metrics", name).read
    empty = {"runs": 2, "trace": {"device_ops": [], "window_s": 1.0,
                                  "busy_s": {}, "collective_s": {}}}
    if name == "collective_share":
        assert read(empty) is None
        return
    if name == "mesh_sweep_device_s":
        assert read(readings(), {}) is None                     # no stages
        # the loop's stages unnamed: no counted op is in gopher.sweep
        assert read(readings(), {"jit_gopher_tiered": {}}) is None
        assert read(readings(runs=0), STAGES) is None
        assert read(empty, STAGES) is None
        return
    assert read(readings(), MetricsRegistry().recent) is None  # no samples
    assert read(readings(), lambda *a: None) is None           # no recent()
    assert read(readings(runs=0), registry().recent) is None
    if name == "chip_wait_share":
        only_lock = MetricsRegistry()
        only_lock.histogram("engine_lockstep_sweeps").observe(10)
        assert read(readings(), only_lock.recent) is None
        assert read(empty, registry().recent) is None          # no chips


def _stand_in_extract(real_extract, ms=1_000_000):
    """``trace_reduce.extract`` with the host spans of the real trace and,
    on each of four devices, one op per named instruction of the program's
    compiled loops, laid end to end from the window's start: sweep ops 3
    ms, collectives 2 ms, the rest 1 ms."""
    def extract(path):
        tr = real_extract(path)
        lo = min(s for name, s, _ in tr["host"] if name == "bench.window")
        stages = load("analytics", "program_obs").op_stages()
        ops, t = [], lo
        for module, instrs in stages.items():
            for instr, stage in instrs.items():
                opcode = instr.rsplit(".", 1)[0]
                dur = (3 if stage == "gopher.sweep"
                       else 2 if trace_reduce.is_collective(opcode) else 1)
                ops.append((f"{module}:{instr} {opcode}", opcode, t,
                            t + dur * ms))
                t += dur * ms
        return dict(tr, devices={d: list(ops) for d in range(4)})
    return extract


def test_a_tiny_traced_run_of_the_cell_reads_every_metric(monkeypatch):
    from repro.core import engine
    from repro.obs import default_registry
    monkeypatch.setattr(engine, "_RUNNER_CACHE", {})   # this cell's loops
    monkeypatch.setattr(trace_reduce, "extract",
                        _stand_in_extract(trace_reduce.extract))
    c = cell(read_json(ROOT / "BENCHMARK.json"), CELL)
    c["config_file"] = dict(c["config_file"], vertices=256)
    res = run.run_cell(c, 2**31 + 211, 0.05, True, jax.devices()[:4],
                       time.perf_counter())
    assert res["correct"] is True and res["failed"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(got) == set(NEW)
    assert got["mesh_sweep_device_s"] > 0 and got["collective_share"] > 0
    lock = default_registry().recent("engine_lockstep_sweeps",
                                     res["attempted"])
    assert got["mesh_lockstep_sweeps_per_run"] == pytest.approx(
        sum(lock) / len(lock))
    assert 0 < got["chip_wait_share"] < 100
    assert res["device"]["count"] == 4
