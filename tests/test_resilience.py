"""Gopher Shield tests: deterministic fault injection, checkpoint/replay
recovery, checksum fallback, mesh-shrink failover, serving degradation,
and the delta/block validation that guards the zero-repack path."""
import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

try:
    from hypothesis import assume, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                             # CI installs it (dev reqs);
    HAVE_HYPOTHESIS = False                     # everything else still runs

import jax  # noqa: E402

from repro.core import (  # noqa: E402
    GopherEngine,
    SemiringProgram,
    compat,
    host_graph_block,
    init_max_vertex,
    make_sssp_init,
    verify_host_block,
)
from repro.gofs.formats import PAD, partition_graph  # noqa: E402
from repro.gofs.generators import random_graph, road_grid  # noqa: E402
from repro.gofs.partition import bfs_grow_partition  # noqa: E402
from repro.launch.elastic import rebalance_hint  # noqa: E402
from repro.resilience.balance import (  # noqa: E402
    BalancePolicy,
    apply_migration,
    migrate_and_resume,
    plan_migration,
    run_with_rebalance,
    to_global,
)
from repro.gofs.temporal import (  # noqa: E402
    DeltaValidationError,
    EdgeDelta,
    apply_delta,
    validate_delta,
)
from repro.resilience import (  # noqa: E402
    RecoveryExhausted,
    faults,
    run_with_recovery,
)
from repro.resilience.degrade import CircuitBreaker, backoff_delays  # noqa: E402
from repro.resilience.failover import _largest_divisor_at_most  # noqa: E402
from repro.serving.service import GraphQueryService  # noqa: E402
from repro.resilience.checkpoint import Checkpointer  # noqa: E402


def _pg(n=100, deg=4.0, parts=8, seed=3):
    g = random_graph(n, avg_degree=deg, seed=seed, weighted=True)
    return g, partition_graph(g, bfs_grow_partition(g, parts, seed=0), parts)


def _prog(algo, pg):
    if algo == "cc":
        return SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    return SemiringProgram(
        semiring="min_plus",
        init_fn=make_sssp_init(int(pg.part_of[0]), int(pg.local_of[0])))


def _eq(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return (len(la) == len(lb)
            and all(np.array_equal(np.asarray(x), np.asarray(y))
                    for x, y in zip(la, lb)))


# ------------------------------------------------------------ fault plans

def test_fault_plan_is_deterministic_and_replayable():
    spec = faults.FaultSpec("svc.query", "poisoned_query", prob=0.5, times=3)
    plan = faults.FaultPlan([spec], seed=11)

    def drive():
        hits = []
        for v in range(40):
            try:
                plan.fire("svc.query")
            except faults.PoisonedQueryFault as e:
                hits.append(e.visit)
        return hits

    first = drive()
    assert len(first) == 3                      # times= disarms the spec
    plan.reset()
    assert drive() == first                     # same seed -> same visits


def test_fault_plan_exact_visit_and_noop_when_unarmed():
    plan = faults.FaultPlan(
        [faults.FaultSpec("engine.superstep", "crash", at=2)])
    plan.fire("engine.superstep")               # visit 0
    plan.fire("engine.superstep")               # visit 1
    with pytest.raises(faults.CrashFault):
        plan.fire("engine.superstep")           # visit 2 fires
    plan.fire("engine.superstep")               # visit 3: shot already spent
    assert [f["visit"] for f in plan.fired] == [2]
    faults.fire("engine.superstep")             # no plan armed -> no-op


# --------------------------------------------- crash-at-any-superstep gate

_REF = {}


def _reference(algo):
    if algo not in _REF:
        _, pg = _pg()
        state, _ = GopherEngine(pg, _prog(algo, pg), backend="local",
                                exchange="dense").run()
        _REF[algo] = (pg, state)
    return _REF[algo]


def _crash_case(algo, mode, backend, k):
    """Kill the run at superstep k, restore from the last committed
    snapshot, finish — the final state must be bit-identical to the
    fault-free run (recovery replays megastep over its compact staged
    fallback)."""
    pg, ref = _reference(algo)
    kw = {}
    if backend == "shard_map":
        kw = dict(mesh=compat.make_mesh((1,), ("parts",)))
    eng = GopherEngine(pg, _prog(algo, pg), backend=backend, exchange=mode,
                       **kw)
    plan = faults.FaultPlan(
        [faults.FaultSpec("engine.superstep", "crash", at=k)])
    with tempfile.TemporaryDirectory() as d:
        with faults.inject(plan):
            state, tele, rep = run_with_recovery(eng, Checkpointer(d),
                                                 every=1)
    assert _eq(state, ref)
    # at= either fired (crash really happened, then recovered) or the run
    # finished before visit k — both end bit-identical
    assert rep.restarts == len(plan.fired)


@pytest.mark.parametrize("algo,mode,backend,k", [
    ("cc", "dense", "local", 0),
    ("cc", "compact", "shard_map", 2),
    ("cc", "megastep", "local", 1),
    ("sssp", "compact", "local", 3),
    ("sssp", "dense", "shard_map", 1),
    ("sssp", "megastep", "local", 4),
])
def test_crash_superstep_corners(algo, mode, backend, k):
    """Deterministic corners of the crash-at-any-superstep property —
    always runs, even without hypothesis installed."""
    _crash_case(algo, mode, backend, k)


@pytest.mark.skipif(not HAVE_HYPOTHESIS,
                    reason="property sweep needs hypothesis "
                           "(requirements-dev.txt)")
def test_crash_at_any_superstep_recovers_bit_identical():
    """Gopher Shield acceptance property: for ANY superstep k, exchange
    mode, backend, and idempotent-⊕ program, crash + recover ends
    bit-identical to the fault-free run."""

    @settings(max_examples=12, deadline=None)
    @given(algo=st.sampled_from(["cc", "sssp"]),
           mode=st.sampled_from(["dense", "compact", "megastep"]),
           backend=st.sampled_from(["local", "shard_map"]),
           k=st.integers(0, 5))
    def prop(algo, mode, backend, k):
        assume(not (mode == "megastep" and backend == "shard_map"))
        _crash_case(algo, mode, backend, k)

    prop()


def test_recovery_exhaustion_raises_with_report():
    _, pg = _pg(n=60, parts=4)
    eng = GopherEngine(pg, _prog("cc", pg), backend="local",
                       exchange="compact")
    plan = faults.FaultPlan(
        [faults.FaultSpec("engine.superstep", "crash", prob=1.0, times=99)])
    with tempfile.TemporaryDirectory() as d:
        with faults.inject(plan):
            with pytest.raises(RecoveryExhausted) as ei:
                run_with_recovery(eng, Checkpointer(d), every=1,
                                  max_restarts=2)
    rep = ei.value.report
    # max_restarts=2 -> 3 attempts, every one downed by an injected crash
    assert rep.attempts == 3 and rep.restarts == 3
    assert all(f["kind"] == "crash" for f in rep.faults)


# --------------------------------------------------- checksum fallback

def test_checkpoint_checksum_fallback_past_corrupt_snapshot():
    """Bit-rot in the newest snapshot: latest_good_step skips it and the
    resumed run still finishes bit-identical to the fault-free reference."""
    pg, ref = _reference("cc")
    prog = _prog("cc", pg)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        GopherEngine(pg, prog, backend="local", exchange="compact",
                     max_supersteps=3).run(checkpointer=ck,
                                           checkpoint_every=1)
        latest = ck.latest_step()
        with open(os.path.join(d, f"step_{latest}", "host_0.npz"),
                  "r+b") as f:
            f.seek(200)
            f.write(b"\xde\xad\xbe\xef")
        assert not ck.verify_step(latest)
        good = ck.latest_good_step()
        assert good is not None and good < latest
        state, _ = GopherEngine(pg, prog, backend="local",
                                exchange="compact").run(
            checkpointer=ck, checkpoint_every=1, resume=True)
    assert _eq(state, ref)


# --------------------------------------------------- mesh-shrink failover

def test_largest_divisor_clamp():
    assert _largest_divisor_at_most(8, 3) == 2
    assert _largest_divisor_at_most(8, 4) == 4
    assert _largest_divisor_at_most(12, 5) == 4
    assert _largest_divisor_at_most(7, 6) == 1


def test_failover_device_loss_subprocess():
    """Mid-run device loss on a real 4-device host mesh: the engine is
    rebuilt on the shrunken mesh (announce-floor plan), resumes from the
    snapshot, and finishes bit-identical — then serves a plain run too."""
    prog = r"""
import tempfile
import numpy as np
import jax
from repro.core import (GopherEngine, PhasedTierPlan, SemiringProgram,
                        compat, host_graph_block, make_sssp_init)
from repro.gofs.formats import partition_graph
from repro.gofs.generators import random_graph
from repro.gofs.partition import bfs_grow_partition
from repro.resilience import faults, run_with_failover
from repro.resilience.checkpoint import Checkpointer
g = random_graph(120, avg_degree=4.0, seed=3, weighted=True)
pg = partition_graph(g, bfs_grow_partition(g, 8, seed=0), 8)
prog = SemiringProgram(semiring="min_plus",
                       init_fn=make_sssp_init(int(pg.part_of[0]),
                                              int(pg.local_of[0])))
ref, _ = GopherEngine(pg, prog, backend="local", exchange="compact").run()
def eq(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(la, lb))
hb = host_graph_block(pg)
eng = GopherEngine(pg, prog, backend="shard_map",
                   mesh=compat.make_mesh((4,), ("parts",)),
                   exchange="phased", tier_plan=PhasedTierPlan.from_block(hb))
plan = faults.FaultPlan([faults.FaultSpec("engine.superstep", "device_loss",
                                          at=2, payload={"lost": [1]})])
with tempfile.TemporaryDirectory() as d:
    with faults.inject(plan):
        eng2, state, tele, rep = run_with_failover(eng, Checkpointer(d),
                                                   every=1, host_gb=hb)
    assert eq(state, ref), "failover parity"
    assert rep.old_num_devices == 4 and rep.new_num_devices == 2, rep
    assert rep.lost_partitions == [2, 3], rep
    assert int(eng2.mesh.shape["parts"]) == 2
    st2, _ = eng2.run()
    assert eq(st2, ref), "post-failover run parity"
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


# --------------------------------------------------- serving degradation

def _svc(**kw):
    _, pg = _pg(parts=4)
    kw.setdefault("retry_base_s", 0.001)
    return pg, GraphQueryService({"g": pg}, **kw)


def test_serving_delta_fault_keeps_answering_and_recovers():
    """The serving degradation gate: a delta-apply fault never reaches a
    client — the service retries with backoff, installs v+1, and reports
    the recovery in svc.stats()."""
    pg, svc = _svc()
    r0 = svc.query("sssp", "g", [0])
    v0 = svc.graphs["g"].version
    plan = faults.FaultPlan(
        [faults.FaultSpec("svc.apply_delta", "failed_delta", at=0)])
    with faults.inject(plan):
        svc.apply_delta("g", EdgeDelta.of(insert_src=[1], insert_dst=[50],
                                          insert_wgt=[0.5]))
    r1 = svc.query("sssp", "g", [1])
    st = svc.stats()
    assert r0.error is None and r1.error is None
    assert svc.graphs["g"].version == v0 + 1
    assert st["delta_retries"] == 1 and st["recoveries"] == 1


def test_serving_delta_exhaustion_serves_stale_then_heals():
    pg, svc = _svc()
    svc.query("sssp", "g", [0])
    v0 = svc.graphs["g"].version
    delta = EdgeDelta.of(insert_src=[2], insert_dst=[60], insert_wgt=[0.3])
    plan = faults.FaultPlan(
        [faults.FaultSpec("svc.apply_delta", "failed_delta", prob=1.0,
                          times=10)])
    with faults.inject(plan):
        with pytest.raises(faults.DeltaApplyFault):
            svc.apply_delta("g", delta)
    # degraded, not down: version-v answers keep flowing, flagged stale
    r = svc.query("sssp", "g", [3])
    st = svc.stats()
    assert r.error is None and svc.graphs["g"].version == v0
    assert st["delta_failures"] == 1 and st["stale_served"] >= 1
    assert st["stale_graphs"] == ["g"]
    svc.apply_delta("g", delta)                 # heal
    st = svc.stats()
    assert svc.graphs["g"].version == v0 + 1
    assert st["recoveries"] >= 1 and "stale_graphs" not in st


def test_serving_corrupt_block_patch_cold_rebuilds():
    """verify_host_block catches a corrupted zero-repack patch; the retry
    cold-rebuilds and the served result matches an independent service at
    the same version."""
    pg, svc = _svc()
    svc.query("sssp", "g", [0])                 # build the patchable twin
    delta = EdgeDelta.of(insert_src=[4, 9], insert_dst=[70, 33],
                         insert_wgt=[0.7, 1.1])
    plan = faults.FaultPlan(
        [faults.FaultSpec("blocks.patch", "corrupt_block", at=0)])
    with faults.inject(plan):
        svc.apply_delta("g", delta)
    got = svc.query("sssp", "g", [5])
    ref_pg = apply_delta(pg, delta, directed=False).pg
    ref = GraphQueryService({"g": ref_pg}).query("sssp", "g", [5])
    st = svc.stats()
    assert got.error is None and np.array_equal(got.result, ref.result)
    assert st["delta_retries"] >= 1 and st["recoveries"] >= 1


def test_serving_poisoned_query_retries_then_breaker_opens():
    _, svc = _svc()
    plan = faults.FaultPlan(
        [faults.FaultSpec("svc.query", "poisoned_query", at=0)])
    with faults.inject(plan):
        r = svc.query("sssp", "g", [7])
    st = svc.stats()
    assert r.error is None
    assert st["query_retries"] >= 1 and st["recoveries"] >= 1

    _, svc2 = _svc(max_retries=1, breaker_threshold=2,
                   breaker_cooldown_s=1e9)
    plan2 = faults.FaultPlan(
        [faults.FaultSpec("svc.query", "poisoned_query", prob=1.0,
                          times=99)])
    with faults.inject(plan2):
        r2 = svc2.query("sssp", "g", [9])
    assert r2.error and r2.error.startswith("degraded:")
    st2 = svc2.stats()
    assert st2["degraded_batches"] == 1 and st2["breaker_opens"] == 1
    assert st2["breakers"]["g"] == "open"
    r3 = svc2.query("sssp", "g", [11])          # open breaker: cheap refusal
    assert r3.error and "circuit open" in r3.error


def test_serving_deadline_is_a_typed_error():
    _, svc = _svc(deadline_s=0.0)
    t = svc.submit("sssp", "g", [0])
    import time
    time.sleep(0.01)
    r = svc.drain()[t]
    assert r.error == "deadline exceeded" and r.result is None
    assert svc.stats()["deadline_misses"] >= 1


def test_circuit_breaker_state_machine_and_backoff():
    now = [0.0]
    br = CircuitBreaker(threshold=2, cooldown_s=10.0, clock=lambda: now[0])
    assert br.allow() and br.state == "closed"
    br.record_failure()
    assert br.state == "closed"
    br.record_failure()
    assert br.state == "open" and br.opens == 1 and not br.allow()
    now[0] = 10.0                               # cooldown elapsed
    assert br.allow() and br.state == "half_open"
    br.record_failure()                         # trial failed -> reopen
    assert br.state == "open" and br.opens == 2
    now[0] = 20.0
    assert br.allow()
    br.record_ok()                              # trial succeeded -> close
    assert br.state == "closed" and br.allow()
    assert backoff_delays(0.05, 4) == [0.05, 0.1, 0.2, 0.4]
    assert backoff_delays(3.0, 3, cap_s=5.0) == [3.0, 5.0, 5.0]
    assert backoff_delays(0.05, 0) == []


# --------------------------------------------------- delta validation

def _delta_pg():
    _, pg = _pg(n=40, parts=4)
    return pg


def test_validate_delta_rejects_out_of_range_ids():
    pg = _delta_pg()
    with pytest.raises(DeltaValidationError, match="out of range"):
        validate_delta(pg, EdgeDelta.of(insert_src=[pg.n_global],
                                        insert_dst=[0]))
    with pytest.raises(DeltaValidationError, match="out of range"):
        validate_delta(pg, EdgeDelta.of(remove_src=[0], remove_dst=[-1]))


def test_validate_delta_rejects_nan_and_negative_weights():
    pg = _delta_pg()
    with pytest.raises(DeltaValidationError, match="NaN"):
        validate_delta(pg, EdgeDelta.of(insert_src=[0], insert_dst=[1],
                                        insert_wgt=[np.nan]))
    with pytest.raises(DeltaValidationError, match="negative"):
        validate_delta(pg, EdgeDelta.of(insert_src=[0], insert_dst=[1],
                                        insert_wgt=[-2.0]))
    # the "any" domain admits negative weights (min_plus over ℝ)
    validate_delta(pg, EdgeDelta.of(insert_src=[0], insert_dst=[1],
                                    insert_wgt=[-2.0]),
                   weight_domain="any")
    with pytest.raises(DeltaValidationError, match="weight_domain"):
        validate_delta(pg, EdgeDelta.of(insert_src=[0], insert_dst=[1]),
                       weight_domain="bogus")


def test_validate_delta_rejects_contradictory_batches():
    pg = _delta_pg()
    # undirected: (7, 3) insert collides with (3, 7) removal
    bad = EdgeDelta.of(insert_src=[7], insert_dst=[3], insert_wgt=[1.0],
                      remove_src=[3], remove_dst=[7])
    with pytest.raises(DeltaValidationError, match="both inserted and"):
        validate_delta(pg, bad)
    with pytest.raises(DeltaValidationError):
        apply_delta(pg, bad, directed=False)    # strict by default
    # directed: opposite arcs are DIFFERENT edges -> fine
    validate_delta(pg, bad, directed=True)


def test_apply_delta_fires_validation_before_any_work():
    pg = _delta_pg()
    bad = EdgeDelta.of(insert_src=[pg.n_global + 5], insert_dst=[0])
    with pytest.raises(DeltaValidationError):
        apply_delta(pg, bad, directed=False)
    assert pg.version == 0                      # nothing was installed


# --------------------------------------------------- host block verifier

def test_verify_host_block_clean_and_corrupt():
    _, pg = _pg(n=60, parts=4)
    hb = host_graph_block(pg)
    assert verify_host_block(hb) == []
    # out-of-bounds neighbor id on a live lane
    bad = dict(hb)
    nbr = np.array(hb["nbr"], copy=True)
    live = np.argwhere(nbr != PAD)
    i = tuple(live[0])
    nbr[i] = pg.v_max + 5
    bad["nbr"] = nbr
    assert any("nbr" in p for p in verify_host_block(bad))
    # NaN weight on a live lane
    bad2 = dict(hb)
    wgt = np.array(hb["wgt"], np.float32, copy=True)
    wgt[i] = np.nan
    bad2["wgt"] = wgt
    assert any("non-finite" in p for p in verify_host_block(bad2))
    # truncated block
    bad3 = dict(hb)
    del bad3["ob_inv"]
    assert any("ob_inv" in p for p in verify_host_block(bad3))


# --------------------------------------------- Gopher Balance: migration

def _strip_pg(rows=6, cols=12, weighted=True, seed=0):
    """road_grid in 2-column vertical strips; strips 0 and 3 (NOT adjacent)
    fold into partition 0, so it holds TWO local sub-graphs with real cut
    edges, while partitions 1 and 2 run half-full — v_max slack to migrate
    into (bfs_grow layouts are single-sub-graph and slack-free, useless for
    migration tests)."""
    g = road_grid(rows, cols, drop_frac=0.0, seed=seed, weighted=weighted)
    strip = (np.arange(rows * cols) % cols) // 2
    assign = np.asarray([0, 1, 2, 0, 3, 3], np.int32)[strip]
    return partition_graph(g, assign, 4)


_MREF = {}


def _strip_ref(algo):
    """Fault-free, migration-free reference in GLOBAL vertex order."""
    if algo not in _MREF:
        pg = _strip_pg()
        state, _ = GopherEngine(pg, _prog(algo, pg), backend="local",
                                exchange="dense").run()
        _MREF[algo] = to_global(state, pg)
    return _MREF[algo]


def _geq(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return (len(la) == len(lb)
            and all(np.array_equal(np.asarray(x), np.asarray(y))
                    for x, y in zip(la, lb)))


def test_rebalance_hint_threshold_and_hysteresis():
    base = dict(imbalance=1.3, straggler=0, time_imbalance=0.0,
                time_straggler=-1)
    assert rebalance_hint(base) is None             # under the trip point
    h = rebalance_hint(dict(base, imbalance=1.8))
    assert h["migrate_from"] == 0 and h["signal"] == "iters"
    # hysteresis: while acting, the band between floor and threshold still
    # hints, so a heal drains fully instead of re-tripping next window
    assert rebalance_hint(base, acting=True)["migrate_from"] == 0
    # balanced mesh (at/below floor): ALWAYS None, even while acting
    assert rebalance_hint(dict(base, imbalance=1.05), acting=True) is None
    assert rebalance_hint(dict(base, imbalance=1.0)) is None
    # the worse channel wins: wall-clock straggler beats flat iterations
    h2 = rebalance_hint(dict(base, time_imbalance=2.5, time_straggler=3))
    assert h2["migrate_from"] == 3 and h2["signal"] == "time"
    # tripped but no victim named -> no hint
    assert rebalance_hint(dict(imbalance=9.9, straggler=-1)) is None


def test_targeted_straggler_lands_in_part_seconds():
    """The upgraded straggler fault: a {'part': p} payload stalls delay_s
    per live vertex of p, and the checkpointed driver charges the stall to
    p's wall-clock channel — visible in Telemetry.skew()."""
    pg = _strip_pg()
    eng = GopherEngine(pg, _prog("cc", pg), backend="local",
                       exchange="compact")
    plan = faults.FaultPlan(
        [faults.FaultSpec("engine.superstep", "straggler", prob=1.0,
                          times=9999, delay_s=0.001, payload={"part": 2})])
    with tempfile.TemporaryDirectory() as d:
        with faults.inject(plan):
            state, tele = eng.run(checkpointer=Checkpointer(d),
                                  checkpoint_every=1)
    assert tele.part_seconds is not None
    assert int(np.argmax(tele.part_seconds)) == 2
    skew = tele.skew()
    assert skew["time_straggler"] == 2 and skew["time_imbalance"] > 1.5
    assert _geq(to_global(state, pg), _strip_ref("cc"))
    assert len(plan.fired) == tele.supersteps  # one stall per superstep


def test_plan_migration_budget_and_capacity():
    pg = _strip_pg()                    # sub-graphs of 12; parts 1,2 half-full
    assert plan_migration(pg, src=0, budget=11) is None   # atomic sub-graph
    p = plan_migration(pg, src=0, budget=12)
    assert p is not None and p.verts == 12 and len(p.subgraphs) == 1
    assert p.dst in (1, 2)              # lightest partitions with free slots
    # budget 24 but only 12 free slots at any dst: still one sub-graph
    assert plan_migration(pg, src=0, budget=24).verts == 12
    # a FULL destination can absorb nothing
    assert plan_migration(pg, src=0, budget=12, dst=3) is None
    assert plan_migration(pg, src=0, budget=12, dst=0) is None
    assert plan_migration(pg, src=9, budget=12) is None   # no such partition


def test_apply_migration_audits_and_moves_only_planned():
    """Non-adjacent destination: out-edges re-allocate at dst, in-edges
    retarget in place, and ONLY the planned sub-graph's vertices change
    owner. The patched block passes the structural audit and both cc and
    sssp converge bit-identical in global order."""
    pg = _strip_pg()
    hb = host_graph_block(pg)
    plan = plan_migration(pg, src=0, budget=12, dst=2)
    res = apply_migration(pg, plan, host_gb=hb)
    assert verify_host_block(res.block) == []
    assert res.stats["out_moved"] > 0 and res.stats["in_retargeted"] > 0
    changed = np.flatnonzero(np.asarray(pg.part_of)
                             != np.asarray(res.pg.part_of))
    assert set(changed.tolist()) == set(res.moved_gids.tolist())
    assert res.pg.version == pg.version + 1
    # fresh runs on the migrated layout: sssp's init bakes the source
    # vertex's (part, slot), so the program is RE-DERIVED from res.pg
    for algo in ("cc", "sssp"):
        state, _ = GopherEngine(res.pg, _prog(algo, res.pg),
                                backend="local", exchange="compact").run()
        assert _geq(to_global(state, res.pg), _strip_ref(algo))


def _migration_case(algo, mode, backend, k, budget, dst):
    """Run to superstep k, migrate (when a bounded plan exists), resume —
    the final state must be bit-identical IN GLOBAL ORDER to the
    migration-free run."""
    pg = _strip_pg()
    kw = {}
    if backend == "shard_map":
        kw = dict(mesh=compat.make_mesh((1,), ("parts",)))
    eng = GopherEngine(pg, _prog(algo, pg), backend=backend, exchange=mode,
                       **kw)
    plan = plan_migration(pg, src=0, budget=budget, dst=dst)
    with tempfile.TemporaryDirectory() as d:
        ck = Checkpointer(d)
        eng.run(checkpointer=ck, checkpoint_every=1, superstep_budget=k)
        if plan is not None:
            eng, res, at = migrate_and_resume(eng, ck, plan)
        state, tele = eng.run(checkpointer=ck, checkpoint_every=1,
                              resume=True)
    assert _geq(to_global(state, eng.pg), _strip_ref(algo))
    return plan


@pytest.mark.parametrize("algo,mode,backend,k,budget,dst", [
    ("cc", "dense", "local", 1, 12, None),
    ("cc", "compact", "shard_map", 3, 12, 2),
    ("cc", "megastep", "local", 2, 24, None),
    ("cc", "tiered", "local", 4, 12, 1),
    ("sssp", "compact", "local", 2, 12, 2),
    ("sssp", "tiered", "shard_map", 1, 12, None),
    ("sssp", "megastep", "local", 5, 12, 1),
])
def test_migration_superstep_corners(algo, mode, backend, k, budget, dst):
    """Deterministic corners of the migrate-at-any-superstep property —
    always run, even without hypothesis installed."""
    plan = _migration_case(algo, mode, backend, k, budget, dst)
    assert plan is not None             # corners are chosen to really move


@pytest.mark.skipif(not HAVE_HYPOTHESIS,
                    reason="property sweep needs hypothesis "
                           "(requirements-dev.txt)")
def test_migration_at_any_superstep_is_bit_identical():
    """Gopher Balance acceptance property: ANY bounded migration plan at
    ANY superstep, across exchange modes and backends, converges
    bit-identical (global order) to the migration-free run."""

    @settings(max_examples=12, deadline=None)
    @given(algo=st.sampled_from(["cc", "sssp"]),
           mode=st.sampled_from(["dense", "compact", "megastep", "tiered"]),
           backend=st.sampled_from(["local", "shard_map"]),
           k=st.integers(1, 6),
           budget=st.integers(8, 24),
           dst=st.sampled_from([None, 1, 2, 3]))
    def prop(algo, mode, backend, k, budget, dst):
        assume(not (mode == "megastep" and backend == "shard_map"))
        _migration_case(algo, mode, backend, k, budget, dst)

    prop()


def test_run_with_rebalance_heals_straggler_bit_identical():
    """The closed loop: a load-proportional straggler on partition 0 trips
    the hint, the actuator migrates sub-graphs off it between segments, and
    the final state still matches the fault-free run bit-identically."""
    pg = _strip_pg()
    eng = GopherEngine(pg, _prog("cc", pg), backend="local",
                       exchange="compact")
    plan = faults.FaultPlan(
        [faults.FaultSpec("engine.superstep", "straggler", prob=1.0,
                          times=9999, delay_s=0.002, payload={"part": 0})])
    with tempfile.TemporaryDirectory() as d:
        with faults.inject(plan):
            eng2, state, tele, rep = run_with_rebalance(
                eng, Checkpointer(d), every=1,
                policy=BalancePolicy(threshold=1.3, floor=1.05,
                                     max_verts_per_step=12, check_every=2))
    assert _geq(to_global(state, eng2.pg), _strip_ref("cc"))
    assert rep.migrations and rep.rollbacks == 0
    assert all(m["src"] == 0 for m in rep.migrations)
    assert rep.final_step == tele.supersteps
    # the migrated engine serves fresh runs on the new layout too
    st2, _ = eng2.run()
    assert _geq(to_global(st2, eng2.pg), _strip_ref("cc"))


def test_migration_rollback_on_corrupt_patch():
    """An injected corrupt patch rolls back for free: nothing installs, the
    pre-migration engine finishes from its own snapshot, parity holds."""
    pg = _strip_pg()
    eng = GopherEngine(pg, _prog("cc", pg), backend="local",
                       exchange="compact")
    plan = faults.FaultPlan(
        [faults.FaultSpec("engine.superstep", "straggler", prob=1.0,
                          times=9999, delay_s=0.002, payload={"part": 0}),
         faults.FaultSpec("blocks.patch", "corrupt_block", prob=1.0,
                          times=9999)])
    with tempfile.TemporaryDirectory() as d:
        with faults.inject(plan):
            eng2, state, tele, rep = run_with_rebalance(
                eng, Checkpointer(d), every=1,
                policy=BalancePolicy(threshold=1.3, floor=1.05,
                                     max_verts_per_step=12, check_every=2))
    assert rep.rollbacks >= 1 and not rep.migrations
    assert all(f["kind"] == "corrupt_block" for f in rep.faults)
    assert eng2 is eng and eng2.pg.version == pg.version
    assert _geq(to_global(state, eng2.pg), _strip_ref("cc"))


def test_service_rebalance_rides_stale_serving():
    """svc.rebalance: a skewed tracker triggers a live migration behind the
    serving path — answers are identical across the move, a corrupt patch
    rolls back (version v keeps serving), and the counters tick."""
    from repro.obs.skew import SkewTracker

    pg = _strip_pg()
    svc = GraphQueryService({"g": pg}, retry_base_s=0.001)
    r0 = svc.query("sssp", "g", [0])
    assert r0.error is None
    skewed = type("T", (), {})()
    skewed.local_iters = np.array([40.0, 10.0, 10.0, 10.0])
    skewed.pair_slots = None
    skewed.part_seconds = np.array([4.0, 0.5, 0.5, 0.5])
    tr = svc.skew.setdefault("g", SkewTracker(num_parts=4))
    tr.observe(skewed)
    # corrupt patch first: rollback, version unchanged, still answering
    fplan = faults.FaultPlan(
        [faults.FaultSpec("blocks.patch", "corrupt_block", at=0)])
    with faults.inject(fplan):
        assert svc.rebalance("g") is None
    st = svc.stats()
    assert st["migration_rollbacks"] == 1 and st["migrations"] == 0
    assert svc.graphs["g"].version == pg.version
    assert svc.query("sssp", "g", [0]).error is None
    # clean attempt installs; answers match bit-for-bit across the move
    res = svc.rebalance("g")
    assert res is not None and svc.graphs["g"].version == pg.version + 1
    st = svc.stats()
    assert st["migrations"] == 1
    r1 = svc.query("sssp", "g", [0])
    assert r1.error is None and np.array_equal(r0.result, r1.result)
    # tracker was reset to the post-move layout: balanced -> no-op
    assert svc.rebalance("g") is None
