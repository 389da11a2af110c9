"""Gopher Scope: tracing, metrics and skew analytics.

Contract under test:
  - the metrics registry is Prometheus-shaped (labeled counters / gauges /
    bounded histograms), snapshots to a schema-valid dict, and hands back
    the same metric object per (name, labels);
  - the tracer nests spans run -> phase -> superstep -> stage, exports a
    valid Chrome trace, and DISABLED degenerates to the shared no-op span
    (no span objects, no recording);
  - Telemetry's round-indexed wire accounting holds across ALL SIX
    exchange disciplines: wire_hist has supersteps+1 entries summing to
    wire_slots, count_hist is consistent with pair_slots, phase
    annotations are monotone (the megastep route ships nothing — its wire
    accounting is all zero while the logical counts persist);
  - the traced stepped driver is bit-identical to the fused compiled loop
    (states AND telemetry), on every discipline — tracing observes, never
    perturbs;
  - the engine, tier planner and serving loop feed the registry, and
    GraphQueryService.stats() reports latency percentiles, cache hit rate
    and live per-partition imbalance.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import GopherEngine, PhasedTierPlan, SemiringProgram, TierPlan
from repro.core import init_max_vertex, make_sssp_init
from repro.gofs import bfs_grow_partition, road_grid
from repro.gofs.formats import partition_graph
from repro.obs import (MetricsRegistry, SkewTracker, Tracer, imbalance_score,
                       skew_report, validate_chrome_trace, validate_metrics)
from repro.obs.trace import _NOOP_SPAN

MODES = ("dense", "compact", "tiered", "phased", "megastep", "auto")


@pytest.fixture(scope="module")
def road():
    g = road_grid(14, 14, drop_frac=0.05, seed=1, weighted=True)
    return g, partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)


def _prog(pg, algo="cc"):
    if algo == "cc":
        return SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    return SemiringProgram(
        semiring="min_plus",
        init_fn=make_sssp_init(int(pg.part_of[0]), int(pg.local_of[0])))


def _plan(pg, exchange):
    if exchange == "tiered":
        return TierPlan.from_graph(pg)
    if exchange == "phased":
        return PhasedTierPlan.from_graph(pg)
    return None


# ---------------- metrics registry ----------------

def test_metrics_registry_basics():
    reg = MetricsRegistry()
    c = reg.counter("reqs_total", labels={"route": "a"})
    c.inc()
    c.inc(2)
    assert reg.counter("reqs_total", labels={"route": "a"}) is c
    assert c.value == 3
    reg.gauge("depth").set(7)
    h = reg.histogram("lat")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    snap = reg.snapshot()
    validate_metrics(snap)
    assert snap["counters"]["reqs_total{route=a}"] == 3
    assert snap["gauges"]["depth"] == 7
    s = snap["histograms"]["lat"]
    assert s["count"] == 4 and s["sum"] == 10.0 and s["p50"] == 2.5
    reg.clear()
    assert reg.snapshot()["counters"] == {}


def test_metrics_validate_rejects_garbage():
    with pytest.raises(AssertionError):
        validate_metrics({"format": "something-else"})
    with pytest.raises(AssertionError):
        validate_metrics({"format": "gopher-metrics-v1",
                          "counters": {"x": "not-a-number"},
                          "gauges": {}, "histograms": {}})


# ---------------- tracer ----------------

def test_tracer_nesting_and_chrome_export(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("run", kind="test") as run:
        with tr.span("phase", phase=0):
            with tr.span("superstep", step=0):
                with tr.span("sweep"):
                    pass
        run.set(supersteps=1)
    assert tr.balanced
    depths = {s.name: s.depth for s in tr.spans}
    assert depths == {"run": 0, "phase": 1, "superstep": 2, "sweep": 3}
    trace = tr.chrome_trace()
    validate_chrome_trace(trace)
    run_ev = next(e for e in trace["traceEvents"] if e["name"] == "run")
    assert run_ev["args"]["supersteps"] == 1
    p = tr.write_chrome_trace(str(tmp_path / "t.json"))
    import json
    validate_chrome_trace(json.load(open(p)))
    lines = tr.jsonl().splitlines()
    assert len(lines) == len(tr.spans)


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    s = tr.span("run", big=1)
    assert s is _NOOP_SPAN           # shared object, zero allocation
    with s as inner:
        inner.set(x=2)
    assert tr.spans == [] and tr.balanced


def test_unbalanced_spans_detected():
    tr = Tracer(enabled=True)
    span = tr.span("run")
    span.__enter__()
    assert not tr.balanced and tr.open_spans() == ["run"]
    span.__exit__(None, None, None)
    assert tr.balanced


# ---------------- skew analytics ----------------

def test_imbalance_score():
    assert imbalance_score(None) == 0.0
    assert imbalance_score(np.zeros(4)) == 0.0
    assert imbalance_score(np.ones(4)) == 1.0
    assert imbalance_score(np.array([4.0, 0, 0, 0])) == 4.0


def test_skew_tracker_accumulates_and_resets():
    class T:
        def __init__(self, li, ps=None):
            self.local_iters = np.asarray(li)
            self.pair_slots = ps
    tr = SkewTracker()
    tr.observe(T([2.0, 1.0, 1.0, 0.0], np.ones((4, 4))))
    tr.observe(T([2.0, 1.0, 1.0, 0.0], np.ones((4, 4))))
    assert tr.runs == 2 and tr.imbalance() == 2.0
    assert float(tr.pair_slots.sum()) == 32.0
    rep = tr.report()
    assert rep["straggler"] == 0 and rep["runs"] == 2
    tr.observe(T([1.0, 1.0]))        # repartition: shape change resets
    assert tr.liters.size == 2 and tr.pair_slots is None


# ---------------- Telemetry invariants, all five disciplines ----------------

@pytest.mark.parametrize("exchange", MODES)
@pytest.mark.parametrize("algo", ("cc", "sssp"))
def test_telemetry_round_invariants(road, exchange, algo):
    g, pg = road
    eng = GopherEngine(pg, _prog(pg, algo), exchange=exchange,
                       tier_plan=_plan(pg, exchange))
    state, t = eng.run()
    assert t.wire_hist is not None
    assert len(t.wire_hist) == t.supersteps + 1
    assert int(np.sum(t.wire_hist)) == t.wire_slots
    if t.exchange == "megastep":     # auto resolves here on local
        # fused route: no routed buffers at all — zero wire, zero bytes,
        # but the logical frontier observation still feeds the profiles
        assert t.wire_slots == 0 and t.bytes_on_wire == 0
        assert int(np.sum(t.count_hist)) > 0
    else:
        assert t.wire_hist[0] > 0    # the prime round is accounted
    if t.exchange == "dense":
        assert t.count_hist is None  # dense measures no packed counts
    else:
        assert len(t.count_hist) == t.supersteps + 1
        # pair_slots is the (P, P) breakdown of the same packed counts
        assert int(np.sum(t.pair_slots)) == int(np.sum(t.count_hist))
        assert t.pair_rounds == t.supersteps + 1   # no retry on this graph
    if t.exchange == "phased":
        assert len(t.phase_hist) == t.supersteps + 1
        assert t.phase_hist[0] == 0                 # prime ships in phase 0
        assert np.all(np.diff(t.phase_hist) >= 0)   # phases only advance
        assert int(np.sum(t.phase_wire)) == t.wire_slots
        sw = np.asarray(t.phase_switch_steps)
        assert np.all(np.diff(sw) > 0)              # strictly monotone
        assert np.sum(t.phase_pair_slots) == np.sum(t.pair_slots)


# ---------------- traced == untraced ----------------

@pytest.mark.parametrize("exchange", MODES)
def test_traced_run_bit_identical(road, exchange):
    g, pg = road
    prog = _prog(pg, "sssp")
    plan = _plan(pg, exchange)
    s0, t0 = GopherEngine(pg, prog, exchange=exchange, tier_plan=plan).run()
    tracer = Tracer(enabled=True)
    s1, t1 = GopherEngine(pg, prog, exchange=exchange, tier_plan=plan,
                          tracer=tracer).run()
    np.testing.assert_array_equal(np.asarray(s0["x"]), np.asarray(s1["x"]))
    assert t0.supersteps == t1.supersteps
    assert t0.wire_slots == t1.wire_slots
    np.testing.assert_array_equal(t0.wire_hist, t1.wire_hist)
    np.testing.assert_array_equal(t0.local_iters, t1.local_iters)
    assert t0.lockstep_sweeps == t1.lockstep_sweeps
    assert t0.chip_wait_sweeps == t1.chip_wait_sweeps
    if t0.count_hist is not None:
        np.testing.assert_array_equal(t0.count_hist, t1.count_hist)
        np.testing.assert_array_equal(t0.pair_slots, t1.pair_slots)
    # span tree: balanced, valid chrome, one superstep span per superstep
    assert tracer.balanced
    trace = tracer.chrome_trace()
    validate_chrome_trace(trace)
    names = [s.name for s in tracer.spans]
    assert names.count("superstep") == t1.supersteps
    if t1.exchange == "megastep":
        # one fused dispatch per superstep: a single 'megastep' child
        # replaces the staged sweep/pack/exchange trio
        assert names.count("megastep") == t1.supersteps
        assert "sweep" not in names and "exchange" not in names
        assert {"run", "phase", "prime", "halt-vote"} <= set(names)
    else:
        assert names.count("sweep") == t1.supersteps
        assert {"run", "phase", "prime", "pack", "exchange",
                "halt-vote"} <= set(names)


def test_traced_shard_map_phased():
    """The acceptance scenario: a phased shard_map traced run emits a valid
    Chrome trace with nested run -> phase -> superstep -> stage spans and
    matches the fused loop bit-for-bit."""
    prog = r"""
import numpy as np
from repro.core import (GopherEngine, PhasedTierPlan, SemiringProgram,
                        compat, make_sssp_init)
from repro.gofs import bfs_grow_partition, road_grid
from repro.gofs.formats import partition_graph
from repro.obs import Tracer, validate_chrome_trace
g = road_grid(14, 14, drop_frac=0.05, seed=1, weighted=True)
pg = partition_graph(g, bfs_grow_partition(g, 8, seed=0), 8)
mesh = compat.make_mesh((4,), ("parts",))
prog = SemiringProgram(semiring="min_plus",
                       init_fn=make_sssp_init(int(pg.part_of[0]),
                                              int(pg.local_of[0])))
plan = PhasedTierPlan.from_graph(pg)
s0, t0 = GopherEngine(pg, prog, backend="shard_map", mesh=mesh,
                      exchange="phased", tier_plan=plan).run()
tr = Tracer(enabled=True)
s1, t1 = GopherEngine(pg, prog, backend="shard_map", mesh=mesh,
                      exchange="phased", tier_plan=plan, tracer=tr).run()
assert np.array_equal(np.asarray(s0["x"]), np.asarray(s1["x"]))
assert t0.supersteps == t1.supersteps and t0.wire_slots == t1.wire_slots
assert np.array_equal(t0.wire_hist, t1.wire_hist)
assert np.array_equal(t0.phase_hist, t1.phase_hist)
assert tr.balanced
trace = tr.chrome_trace()
validate_chrome_trace(trace)
by_name = {}
for s in tr.spans:
    by_name.setdefault(s.name, s)
assert by_name["run"].depth == 0
assert by_name["phase"].depth == 1
assert by_name["superstep"].depth == 2
for stage in ("sweep", "pack", "exchange", "halt-vote"):
    assert by_name[stage].depth == 3
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


# ---------------- metrics feeds ----------------

def test_engine_feeds_metrics(road):
    g, pg = road
    reg = MetricsRegistry()
    eng = GopherEngine(pg, _prog(pg, "cc"), metrics=reg)
    _, t = eng.run()
    snap = reg.snapshot()
    validate_metrics(snap)
    labels = f"{{backend=local,exchange={t.exchange}}}"
    assert snap["counters"][f"engine_runs_total{labels}"] == 1
    assert snap["counters"][f"engine_supersteps_total{labels}"] \
        == t.supersteps
    assert snap["counters"][f"engine_wire_slots_total{labels}"] \
        == t.wire_slots
    assert snap["gauges"][f"engine_partition_imbalance{labels}"] \
        == pytest.approx(imbalance_score(t.local_iters))


def test_telemetry_skew_method(road):
    g, pg = road
    _, t = GopherEngine(pg, _prog(pg, "cc"), exchange="compact").run()
    rep = t.skew()
    assert rep["imbalance"] >= 1.0
    assert 0 <= rep["straggler"] < pg.num_parts
    assert rep["wire"]["send_imbalance"] >= 1.0
    assert rep == skew_report(t)


def test_service_stats_live_metrics(road):
    from repro.serving.service import GraphQueryService
    g, pg = road
    svc = GraphQueryService({"g": pg})
    svc.submit("sssp", "g", [0])
    svc.submit("sssp", "g", [5])
    svc.drain()
    svc.query("sssp", "g", [0])          # exact-cache hit
    s = svc.stats()                      # the Gopher Scope serving report
    assert s["served"] == 3 and s["cache_hits"] == 1
    assert s["cache_hit_rate"] == pytest.approx(1 / 3, abs=1e-3)
    assert s["p50_ms"] <= s["p95_ms"] <= s["p99_ms"]
    assert s["imbalance"]["g"] >= 1.0
    assert s["skew"]["g"]["runs"] == 1
    assert s["result_cache"]["hit_rate"] == pytest.approx(1 / 3, abs=1e-3)
    assert svc.stats.summary()["served"] == 3   # attribute API still works
    assert svc.cache.hit_rate() == pytest.approx(1 / 3, abs=1e-3)


def test_tier_profile_drift_metrics(road):
    from repro.core import host_graph_block, update_profile
    from repro.obs import metrics as obs_metrics
    g, pg = road
    reg = MetricsRegistry()
    old = obs_metrics.default_registry()
    obs_metrics.set_default_registry(reg)
    try:
        hb = host_graph_block(pg)
        update_profile(hb, np.zeros((pg.num_parts, pg.num_parts)), rounds=1)
        snap = reg.snapshot()
        assert snap["counters"][
            "tiers_profile_updates_total{profile=wire}"] == 1
        assert snap["gauges"]["tiers_profile_drift{profile=wire}"] > 0
    finally:
        obs_metrics.set_default_registry(old)


# ---------------- program spans, device stages, lockstep count ----------------

PREP_SPANS = ("engine", "layout", "upload", "compose_mailbox", "dispatch")
PROGRAM_SPANS = ("entry",) + PREP_SPANS + ("download",)
ANALYTICS = ("sssp", "pagerank")


@pytest.fixture(scope="module")
def mesh_graph():
    """A graph of this section's own shapes, so the runner-cache entries
    its analytics compile are told apart from other tests'."""
    g = road_grid(9, 11, drop_frac=0.05, seed=2, weighted=True)
    return partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)


def _analytic(pg, name):
    from repro import algorithms
    if name == "sssp":
        return algorithms.sssp(pg, 0)
    return algorithms.pagerank(pg)


def _own_loops(pg, name):
    """This section's compiled BSP loops of analytic ``name``."""
    from repro.core import PageRankProgram, engine
    want = PageRankProgram if name == "pagerank" else SemiringProgram
    return {k: v for k, v in engine._RUNNER_CACHE.items()
            if isinstance(k[0], want) and k[2] == "megastep"
            and k[8:11] == (pg.num_parts, pg.v_max, pg.mailbox_cap)}


@pytest.mark.parametrize("name", ANALYTICS)
def test_program_spans_reach_the_profiler(mesh_graph, name, tmp_path):
    """Every gopher.* host span lands in a jax.profiler trace (read the
    way the benchmark's trace reduction reads it), inside gopher.entry."""
    import glob
    import jax
    from jax.profiler import ProfileData
    _analytic(mesh_graph, name)                  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        _analytic(mesh_graph, name)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"
             for line in plane.lines for ev in line.events
             if ev.name.startswith("gopher.")]
    entries = [(s, e) for n, s, e in spans if n == "gopher.entry"]
    assert len(entries) == 1
    (lo, hi), = entries
    found = {n for n, s, e in spans if lo <= s and e <= hi}
    assert found == {"gopher." + s for s in PROGRAM_SPANS}
    # the compiled loop is named, so its host span is too
    names = {ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events}
    assert "PjitFunction(gopher_megastep)" in names


@pytest.mark.parametrize("name", ANALYTICS)
def test_op_stages_name_the_hot_stages(mesh_graph, name):
    from repro.obs import op_stages
    _analytic(mesh_graph, name)
    stages = op_stages(_own_loops(mesh_graph, name))
    assert set(stages) == {"jit_gopher_megastep"}
    found = set(stages["jit_gopher_megastep"].values())
    assert {"gopher.sweep", "gopher.deliver", "gopher.stats"} <= found
    assert found <= {"gopher.deliver", "gopher.sweep", "gopher.frontier",
                     "gopher.stats", "gopher.update"}


def test_hlo_stages_reads_the_innermost_scope():
    from repro.obs.trace import hlo_stages
    text = """HloModule jit_gopher_megastep, is_scheduled=true

%body {
  %fusion.27 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(gopher_megastep)/while/body/gopher.sweep/while/body/gopher.frontier/ne" stack_frame_id=3}
  ROOT %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, metadata={op_name="jit(gopher_megastep)/while/body/gopher.deliver/gather"}
  %copy.1 = f32[8]{0} copy(%p), metadata={op_name="jit(gopher_megastep)/while/body/copy"}
  %add.2 = s32[] add(%a, %b)
}
"""
    module, stages = hlo_stages(text)
    assert module == "jit_gopher_megastep"
    assert stages == {"fusion.27": "gopher.frontier",
                      "fusion.3": "gopher.deliver"}


def _host_lockstep(pg):
    """Lockstep sweeps of SSSP from 0, counted by a host loop over the
    fused supersteps: the partitions' fixpoints run side by side and each
    one's frontier stays empty once it empties, so a superstep's lockstep
    sweeps are its busiest partition's."""
    import jax
    from repro.core.blocks import graph_block
    from repro.kernels import megastep as mega
    prog = SemiringProgram(
        semiring="min_plus",
        init_fn=make_sssp_init(int(pg.part_of[0]), int(pg.local_of[0])))
    gb = graph_block(pg)
    cm = mega.compose_mailbox(gb)
    st = jax.vmap(prog.init)(gb)
    x, ch, fr = (st[k].reshape(-1) for k in ("x", "changed_v", "frontier"))
    step = jax.jit(lambda x, ch, fr: mega.megastep_semiring(
        x, ch, fr, cm, "min_plus")[:4])
    steps = total = 0
    while True:
        x, ch, fr, li = step(x, ch, fr)
        steps += 1
        total += int(np.asarray(li).max())
        if not np.asarray(ch).any():
            return steps, total


@pytest.mark.parametrize("name", ANALYTICS)
def test_lockstep_sweeps_count_the_flat_fixpoint(mesh_graph, name):
    _, t = _analytic(mesh_graph, name)
    if name == "pagerank":
        assert t.lockstep_sweeps == t.supersteps == 30
        return
    li = np.asarray(t.local_iters)
    assert li.max() <= t.lockstep_sweeps <= li.sum()
    assert _host_lockstep(mesh_graph) == (t.supersteps, t.lockstep_sweeps)
    # the traced stepped driver counts the same sweeps
    _, tt = GopherEngine(mesh_graph, _prog(mesh_graph, "sssp"),
                         tracer=Tracer(enabled=True)).run()
    assert tt.lockstep_sweeps == t.lockstep_sweeps


def test_staged_loop_counts_lockstep_sweeps_and_no_chip_wait(mesh_graph):
    """On one device the staged loop's vmapped fixpoints run as long as
    the busiest partition's, the megastep's definition; no chip waits."""
    _, t = GopherEngine(mesh_graph, _prog(mesh_graph, "sssp"),
                        exchange="dense").run()
    assert (t.supersteps, t.lockstep_sweeps) == _host_lockstep(mesh_graph)
    assert t.chip_wait_sweeps == 0


@pytest.mark.parametrize("exchange", ("dense", "tiered", "phased"))
def test_op_stages_name_the_staged_loops_stages(mesh_graph, exchange):
    from repro.core import engine
    from repro.obs import op_stages
    prog = _prog(mesh_graph, "sssp")
    GopherEngine(mesh_graph, prog, exchange=exchange,
                 tier_plan=_plan(mesh_graph, exchange)).run()
    loops = {k: v for k, v in engine._RUNNER_CACHE.items()
             if k[0] is prog and k[2] == exchange}
    stages = op_stages(loops)
    assert set(stages) == {f"jit_gopher_{exchange}"}
    assert set(stages[f"jit_gopher_{exchange}"].values()) == {
        "gopher.sweep", "gopher.pack", "gopher.route", "gopher.deliver",
        "gopher.stats"}


@pytest.mark.parametrize("name", ANALYTICS)
def test_each_run_samples_every_program_span(mesh_graph, name):
    from repro.obs import SPAN_SECONDS
    from repro.obs import metrics as obs_metrics
    reg = MetricsRegistry()
    old = obs_metrics.default_registry()
    obs_metrics.set_default_registry(reg)
    try:
        runs = 3
        for _ in range(runs):
            _, t = _analytic(mesh_graph, name)
        for span in PROGRAM_SPANS:
            got = reg.recent(SPAN_SECONDS, 10, {"span": span})
            assert len(got) == runs, span
            assert all(s >= 0 for s in got)
        assert reg.recent("engine_lockstep_sweeps", 10) \
            == [float(t.lockstep_sweeps)] * runs
        validate_metrics(reg.snapshot())
    finally:
        obs_metrics.set_default_registry(old)


def test_step_records_into_an_enabled_tracer_only():
    from repro.obs import step
    reg = MetricsRegistry()
    on, off = Tracer(enabled=True), Tracer(enabled=False)
    with step("layout", tracer=on, metrics=reg):
        with step("upload", tracer=on, metrics=reg):
            pass
    with step("layout", tracer=off, metrics=reg):
        pass
    assert [(s.name, s.depth) for s in on.spans] == [
        ("gopher.upload", 1), ("gopher.layout", 0)]
    assert on.balanced and off.spans == []
    validate_chrome_trace(on.chrome_trace())
    assert len(reg.recent("gopher_span_seconds", 5, {"span": "layout"})) == 2
    assert reg.recent("gopher_span_seconds", 5, {"span": "absent"}) == []
