"""Gopher Phases: frontier-phased tier schedules.

Contract under test:
  - phase bands derive deterministically from the changed-histogram EWMA
    (suffix-max thresholds: a frontier that briefly dips doesn't end a
    band), and the expected-horizon helper reads the same history;
  - PhasedTierPlan: cold blocks degenerate to ONE structural phase (same
    geometry as the static TierPlan — never overflows); taught blocks give
    monotone boundaries, a wide phase at least as wide as the static plan,
    and a narrow tail strictly under it; the plan is hashable (the
    compiled-loop cache keys on it);
  - the phased engine is BIT-IDENTICAL to the dense mailbox for idempotent
    ⊕ on both backends, single and query-batched; PageRank matches to
    allclose (⊕ = float sum reassociates across fused loops);
  - the DEMOTION trigger jumps to the next segment after DEMOTE_STREAK
    supersteps whose observed counts fit the next phase's caps — well
    before a wrong predicted boundary;
  - quiescing EXACTLY at the predicted switch superstep runs zero
    supersteps of the next phase (the boundary off-by-one regression);
  - per-superstep overflow falls back to the dense route INSIDE the loop
    (results exact unconditionally, no whole-run retry) and escalates only
    the spilling phase;
  - update_changed_profile zero-extends past convergence and the announce
    floor warms only pairs within the expected superstep horizon;
  - the landmark tier tracks re-selection drift and the service
    re-bootstraps when it crosses the threshold.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (GopherEngine, PageRankProgram, PhasedTierPlan,
                        SemiringProgram, TierPlan, compat, device_block,
                        expected_horizon, host_graph_block, init_max_vertex,
                        make_sssp_init, update_changed_profile,
                        update_profile)
from repro.core.tiers import (COLD, DEMOTE_STREAK, EXCLUDED, PHASE_HIST_LEN,
                              _NO_BOUNDARY, occupancy_from_graph, phase_bands)
from repro.gofs import EdgeDelta, apply_delta, bfs_grow_partition, road_grid
from repro.gofs.formats import partition_graph


@pytest.fixture(scope="module")
def road():
    g = road_grid(22, 22, drop_frac=0.08, seed=3, weighted=True)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    return g, pg


@pytest.fixture(scope="module")
def taught(road):
    """A host block whose pair + changed profiles were taught by one cold
    compact CC run (the version-k history a deployment accumulates)."""
    g, pg = road
    hb = host_graph_block(pg)
    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    _, tele = GopherEngine(pg, prog, gb=device_block(hb),
                           exchange="compact").run()
    update_profile(hb, tele.pair_slots, tele.pair_rounds)
    update_changed_profile(hb, tele.count_hist)
    return hb


def _mesh1():
    return compat.make_mesh((1,), ("parts",))


def _structural_two_phase(pg, boundaries):
    """Two phases at the SAME structural (never-overflowing) table — the
    harness for boundary/demotion tests where geometry must not interfere."""
    base = TierPlan.from_graph(pg)
    return PhasedTierPlan(num_parts=base.num_parts, cap=base.cap,
                          warm_cap=base.warm_cap,
                          phase_tier_bytes=(base.tier_bytes, base.tier_bytes),
                          boundaries=boundaries)


# ---------------- derivation ----------------

def test_phase_bands_deterministic():
    ch = np.array([100.0, 80.0, 30.0, 10.0, 2.0, 0.3, 0.0, 0.0])
    bands = phase_bands(ch, max_phases=3)
    # wide ends where the suffix max stays under 25% of peak, mid under 5%;
    # the horizon ends at the last superstep >= CHANGED_EPS (index 4)
    assert bands == ((3, 3, pytest.approx(70.0)),
                     (4, 1, pytest.approx(10.0)),
                     (_NO_BOUNDARY, 1, pytest.approx(2.0)))
    # a dip-and-rebound does NOT end the wide band early
    ch2 = np.array([100.0, 3.0, 90.0, 1.0, 0.0])
    b2 = phase_bands(ch2, max_phases=3)
    assert b2[0][0] == 3
    # no usable history -> one unbounded band
    assert phase_bands(None) == ((_NO_BOUNDARY, _NO_BOUNDARY, 1.0),)
    assert phase_bands(np.zeros(8)) == ((_NO_BOUNDARY, _NO_BOUNDARY, 1.0),)


def test_expected_horizon():
    assert expected_horizon(None) is None
    assert expected_horizon(np.zeros(16)) is None
    assert expected_horizon(np.array([3.0, 1.0, 0.2, 0.0])) == 2
    assert expected_horizon(np.array([0.0, 0.0, 7.0])) == 3


def test_update_changed_profile_zero_extends(road):
    g, pg = road
    hb = host_graph_block(pg)
    assert np.all(hb["changed_ewma"] == 0.0)
    out = update_changed_profile(hb, [40, 8], decay=0.25)
    assert out.shape == (PHASE_HIST_LEN,)
    assert out[0] == pytest.approx(30.0) and out[1] == pytest.approx(6.0)
    assert np.all(out[2:] == 0.0)
    # a quiesced run (empty histogram) decays the whole profile
    out2 = update_changed_profile(hb, [], decay=0.25)
    assert out2[0] == pytest.approx(7.5)
    # blocks without the field are left untouched
    assert update_changed_profile({}, [1, 2]) is None


def test_phased_plan_cold_block_is_single_structural_phase(road):
    g, pg = road
    hb = host_graph_block(pg)
    plan = PhasedTierPlan.from_block(hb)
    assert plan.num_phases == 1
    assert plan.boundaries == (_NO_BOUNDARY,)
    static = TierPlan.from_block(hb)
    assert plan.phase_plans()[0] == static
    assert PhasedTierPlan.from_graph(pg).phase_plans()[0] == \
        TierPlan.from_graph(pg)


def test_phased_plan_from_taught_block(road, taught):
    g, pg = road
    plan = PhasedTierPlan.from_block(taught)
    assert plan.num_phases >= 2
    bounds = np.asarray(plan.boundaries)
    assert np.all(np.diff(bounds) > 0) and bounds[-1] == _NO_BOUNDARY
    phases = plan.phase_plans()
    static = TierPlan.from_block(taught)
    # the wide phase covers at least the static plan's widths; the narrow
    # tail routes strictly less geometry
    assert np.all(phases[0].limits() >= static.limits())
    assert (phases[-1].schedule(1).round_slots()
            < phases[0].schedule(1).round_slots())
    # excluded pairs are structural — identical across phases
    for p in phases:
        assert np.array_equal(p.tiers == EXCLUDED,
                              phases[0].tiers == EXCLUDED)
    # hashable: equal plans are one compiled-loop cache key
    assert {plan: 1}[PhasedTierPlan.from_block(taught)] == 1


def test_plan_mode_normalization(road):
    """Plan/mode mismatches normalize instead of crashing at trace time: a
    PhasedTierPlan under exchange='tiered' (e.g. a narrow_resume plan handed
    to an auto engine that resolved tiered) upgrades the mode to 'phased';
    a plain TierPlan under 'phased' wraps as a single phase."""
    g, pg = road
    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    sd, _ = GopherEngine(pg, prog, exchange="dense").run()
    up = GopherEngine(pg, prog, exchange="tiered",
                      tier_plan=PhasedTierPlan.from_graph(pg))
    assert up.exchange == "phased"
    st, tt = up.run()
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(st["x"]))
    assert tt.exchange == "phased"
    wrapped = GopherEngine(pg, prog, exchange="phased",
                           tier_plan=TierPlan.from_graph(pg))
    assert wrapped.tier_plan.num_phases == 1
    s2, _ = wrapped.run()
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(s2["x"]))


def test_narrow_resume_plan(road, taught):
    g, pg = road
    # no announce pending: the narrow plan is the profile plan's tail
    full = PhasedTierPlan.from_block(taught)
    narrow = PhasedTierPlan.narrow_resume(taught)
    assert narrow.num_phases == 1
    assert narrow.boundaries == (_NO_BOUNDARY,)
    assert narrow.phase_tier_bytes[0] == full.phase_tier_bytes[-1]


def test_for_resume_announce_informed(road, taught):
    """After a delta, for_resume builds phase 0 from the EXACT announced
    prime-round expectation — on an UNTAUGHT replica that is orders of
    magnitude narrower than the structural prior, and the restart provably
    fits it (prime counts are the announce), so a cold block's restart
    rides narrow geometry with zero spills."""
    g, pg = road
    hb = host_graph_block(pg)                    # fresh replica: structural
    update_changed_profile(hb, np.asarray(taught["changed_ewma"]))
    rng = np.random.default_rng(4)
    iu = rng.integers(0, g.n, 6)
    iv = rng.integers(0, g.n, 6)
    keep = iu != iv
    res = apply_delta(pg, EdgeDelta.inserts(
        iu[keep], iv[keep],
        rng.uniform(40.0, 50.0, int(keep.sum())).astype(np.float32)),
        directed=False, block=hb)
    assert np.any(res.block["announce_ewma"] > 0)
    plan = PhasedTierPlan.for_resume(res.block)
    static = TierPlan.from_block(res.block)      # structural on a replica
    assert (plan.phase_plans()[0].schedule(1).round_slots()
            < static.schedule(1).round_slots())
    # the restart itself: exact + spill-free on the announce-informed plan
    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    prev, _ = GopherEngine(pg, prog, exchange="dense").run()
    x0 = np.where(res.pg.vmask, np.asarray(prev["x"], np.float32), -np.inf)
    extra = {"x0": x0, "frontier0": res.dirty_insert & res.pg.vmask}
    gbd = device_block(res.block)
    rprog = SemiringProgram(semiring="max_first", resume=True)
    sd, td = GopherEngine(res.pg, rprog, gb=gbd, exchange="dense").run(
        extra=extra)
    sp_, tp = GopherEngine(res.pg, rprog, gb=gbd, exchange="phased",
                           tier_plan=plan).run(extra=extra)
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(sp_["x"]))
    assert tp.spills == 0 and tp.dense_retry_steps == 0
    assert tp.wire_slots < td.wire_slots
    # a run's profile fold CONSUMES the pending announce
    update_profile(res.block, tp.pair_slots, tp.pair_rounds)
    assert not np.any(res.block["announce_ewma"] > 0)
    assert PhasedTierPlan.narrow_resume(res.block).num_phases == 1


# ---------------- engine: phased == dense ----------------

def _programs(pg, n):
    return [
        ("cc", SemiringProgram(semiring="max_first", init_fn=init_max_vertex),
         "x", True),
        ("sssp", SemiringProgram(
            semiring="min_plus",
            init_fn=make_sssp_init(int(pg.part_of[0]), int(pg.local_of[0]))),
         "x", True),
        ("pagerank", PageRankProgram(n_global=n, num_iters=12), "r", False),
    ]


@pytest.mark.parametrize("backend", ["local", "shard_map"])
def test_phased_matches_dense(backend, road, taught):
    g, pg = road
    mesh = _mesh1() if backend == "shard_map" else None
    plan = PhasedTierPlan.from_block(taught)
    K = plan.num_phases
    for name, prog, key, exact in _programs(pg, g.n):
        sd, td = GopherEngine(pg, prog, backend=backend, mesh=mesh,
                              exchange="dense").run()
        sp_, tp = GopherEngine(pg, prog, backend=backend, mesh=mesh,
                               exchange="phased", tier_plan=plan).run()
        a, b = np.asarray(sd[key]), np.asarray(sp_[key])
        if exact:
            assert np.array_equal(a, b), name
        else:
            assert np.allclose(a, b, rtol=1e-6, atol=1e-9), name
        assert td.supersteps == tp.supersteps
        assert tp.exchange == "phased" and not tp.retried
        P = pg.num_parts
        assert tp.phase_hist is not None
        # round-indexed: supersteps + 1 entries, round 0 = the inbox prime
        assert tp.phase_hist.shape == (tp.supersteps + 1,)
        assert tp.phase_hist[0] == 0                     # prime rides phase 0
        assert np.all(np.diff(tp.phase_hist) >= 0)       # phases only advance
        assert tp.phase_hist.max() < K if tp.supersteps else True
        assert tp.count_hist is not None
        assert tp.phase_pair_slots.shape == (K, P, P)
        assert tp.pair_slots.shape == (P, P)
        assert tp.phase_wire.shape == (K,)
        assert tp.phase_wire.sum() == tp.wire_slots
        # the run rode the contraction: total routed geometry under dense
        assert tp.wire_slots < td.wire_slots, name
        assert tp.bytes_on_wire < td.bytes_on_wire, name


def test_phased_query_batched_matches_dense(road, taught):
    from repro.serving.batched import (BatchedSemiringProgram,
                                       gather_query_results, sssp_query_init)
    g, pg = road
    sources = [0, 5, g.n // 2, g.n - 1]
    prog = BatchedSemiringProgram(semiring="min_plus",
                                  num_queries=len(sources))
    extra = {"qinit": sssp_query_init(pg, sources)}
    sd, td = GopherEngine(pg, prog, exchange="dense").run_queries(extra=extra)
    plan = PhasedTierPlan.from_block(taught)
    sp_, tp = GopherEngine(pg, prog, exchange="phased",
                           tier_plan=plan).run_queries(extra=extra)
    assert np.array_equal(gather_query_results(pg, sd["x"]),
                          gather_query_results(pg, sp_["x"]))
    assert np.array_equal(td.query_supersteps, tp.query_supersteps)
    assert tp.wire_slots < td.wire_slots


# ---------------- segment control flow ----------------

def test_demotion_trigger_jumps_to_next_segment(road):
    """A wildly wrong predicted boundary must not pin the run in the wide
    phase: observed counts fitting the next phase's caps for DEMOTE_STREAK
    consecutive supersteps jump the segment immediately. (Both phases use
    the structural table, so counts always fit and results can't differ.)"""
    g, pg = road
    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    sd, td = GopherEngine(pg, prog, exchange="dense").run()
    plan = _structural_two_phase(pg, boundaries=(1000, _NO_BOUNDARY))
    st, tt = GopherEngine(pg, prog, exchange="phased", tier_plan=plan).run()
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(st["x"]))
    assert tt.supersteps == td.supersteps
    if tt.supersteps > DEMOTE_STREAK:
        assert np.array_equal(tt.phase_switch_steps, [DEMOTE_STREAK])
        # rounds 0..DEMOTE_STREAK (prime + the streak supersteps) ride the
        # wide phase; every later round is in the demoted segment
        assert np.all(tt.phase_hist[:DEMOTE_STREAK + 1] == 0)
        assert np.all(tt.phase_hist[DEMOTE_STREAK + 1:] == 1)


def test_quiesce_exactly_at_predicted_switch(road):
    """The boundary off-by-one regression: a run that quiesces EXACTLY at
    the predicted switch superstep must run ZERO supersteps of the next
    phase. The next phase is all-width-1 here, so a single leaked
    superstep would truncate and show up as a dense-retry/spill."""
    g, pg = road
    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    sd, td = GopherEngine(pg, prog, exchange="dense").run()
    S = td.supersteps
    base = TierPlan.from_graph(pg)
    allcold = np.where(base.tiers == EXCLUDED, EXCLUDED, COLD).astype(np.int8)
    # boundaries are in ROUND units: the run's last exchange is round S
    # (superstep S - 1 ships it), so the wide band must cover rounds < S + 1
    plan = PhasedTierPlan(num_parts=base.num_parts, cap=base.cap,
                          warm_cap=base.warm_cap,
                          phase_tier_bytes=(base.tier_bytes,
                                            allcold.tobytes()),
                          boundaries=(S + 1, _NO_BOUNDARY))
    st, tt = GopherEngine(pg, prog, exchange="phased", tier_plan=plan).run()
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(st["x"]))
    assert tt.supersteps == S                      # no leaked supersteps
    assert np.all(tt.phase_hist == 0)              # phase 1 never ran
    assert tt.spills == 0 and tt.dense_retry_steps == 0
    # one round earlier and the LAST live superstep crosses into the
    # all-cold phase: the in-loop dense retry absorbs it, results exact
    plan2 = dataclasses.replace(plan, boundaries=(S, _NO_BOUNDARY))
    st2, tt2 = GopherEngine(pg, prog, exchange="phased",
                            tier_plan=plan2).run()
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(st2["x"]))
    assert tt2.supersteps == S
    assert tt2.phase_hist[-1] == 1


def test_overflow_dense_retry_escalates_only_spilling_phase(road):
    """Sabotage ONLY the tail phase (busiest pair demoted to cold). The
    overflowing supersteps route dense inside the loop — results exact,
    no whole-run retry — and the escalation promotes the tail phase's
    pair while the wide phase keeps its geometry."""
    g, pg = road
    prog = SemiringProgram(
        semiring="min_plus",
        init_fn=make_sssp_init(int(pg.part_of[0]), int(pg.local_of[0])))
    sd, _ = GopherEngine(pg, prog, exchange="dense").run()
    base = TierPlan.from_graph(pg)
    occ = occupancy_from_graph(pg)
    s, d = np.unravel_index(np.argmax(occ), occ.shape)
    assert occ[s, d] > 1
    t = base.tiers.copy()
    t[s, d] = COLD
    plan = PhasedTierPlan(num_parts=base.num_parts, cap=base.cap,
                          warm_cap=base.warm_cap,
                          phase_tier_bytes=(base.tier_bytes, t.tobytes()),
                          boundaries=(1, _NO_BOUNDARY))
    eng = GopherEngine(pg, prog, exchange="phased", tier_plan=plan)
    st, tt = eng.run()
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(st["x"]))
    assert not tt.retried                          # no whole-run retry
    assert tt.dense_retry_steps > 0 and tt.spills > 0
    assert tt.pair_overflow[s, d] > 0
    assert tt.escalations >= 1
    new = eng.tier_plan.phase_plans()
    assert new[0] == base                          # wide phase untouched
    assert new[1].tiers[s, d] > COLD               # tail phase promoted
    # escalation converges: the repaired plan goes back to pure phased runs
    for _ in range(3):
        st, tt = eng.run()
        if tt.dense_retry_steps == 0:
            break
    assert tt.dense_retry_steps == 0 and tt.spills == 0
    assert np.array_equal(np.asarray(sd["x"]), np.asarray(st["x"]))


def test_phased_multi_device_collectives_static():
    """Gopher Sentinel replaces the old D=4 subprocess collective check:
    trace the phased shard_map loop on an ABSTRACT 4-device mesh (no real
    devices, no subprocess) and statically verify the SPMD invariants the
    subprocess run could only sample — the per-superstep lax.cond picks
    between two genuinely DIFFERENT collective routes (dense all_to_all
    vs tiered all_to_all + ppermute), which is deadlock-free only because
    its predicate is replicated by a full mesh-axis psum."""
    from repro.analysis import verify_collectives
    # P=8 over D=4 so the tier schedule has warm (ppermute) lanes, not
    # just the hot all_to_all — same shape as the subprocess smoke below
    g = road_grid(10, 10, drop_frac=0.05, seed=1, weighted=True)
    pg = partition_graph(g, bfs_grow_partition(g, 8, seed=0), 8)
    mesh = compat.abstract_mesh((4,), ("parts",))
    prog = SemiringProgram(semiring="min_plus",
                           init_fn=make_sssp_init(int(pg.part_of[0]),
                                                  int(pg.local_of[0])))
    eng = GopherEngine(pg, prog, backend="shard_map", mesh=mesh,
                       exchange="phased",
                       tier_plan=_structural_two_phase(pg, (2, _NO_BOUNDARY)))
    summary, violations = verify_collectives(eng)
    assert violations == [], [str(v) for v in violations]
    # both routes' collectives are present in the traced loop
    counts = summary.counts
    assert counts.get("all_to_all", 0) > 0
    assert counts.get("ppermute", 0) > 0
    assert counts.get("psum", 0) > 0
    # every retry cond has mismatched branch traces (the two routes) yet is
    # proven safe by predicate replication — the exact property the old
    # subprocess test could only witness indirectly via bit-parity
    assert summary.conds, "phased loop must contain the retry conds"
    for cond in summary.conds:
        assert not cond.branches_equal
        assert cond.predicate_uniform and cond.safe


def test_phased_multi_device_smoke():
    """One end-to-end D=4 subprocess smoke (the static sentinel check above
    covers the collective structure): a sabotaged narrow phase forces the
    replicated cond to flip to the dense route mid-loop on every device at
    once, and the result stays bit-identical to dense."""
    import os
    import subprocess
    import sys
    prog = r"""
import numpy as np
from repro.core import (GopherEngine, PhasedTierPlan, SemiringProgram,
                        TierPlan, compat, init_max_vertex, make_sssp_init)
from repro.core.tiers import COLD, _NO_BOUNDARY, occupancy_from_graph
from repro.gofs import bfs_grow_partition, road_grid
from repro.gofs.formats import partition_graph
g = road_grid(10, 10, drop_frac=0.05, seed=1, weighted=True)
pg = partition_graph(g, bfs_grow_partition(g, 8, seed=0), 8)
mesh = compat.make_mesh((4,), ("parts",))
prog = SemiringProgram(semiring="min_plus",
                       init_fn=make_sssp_init(int(pg.part_of[0]),
                                              int(pg.local_of[0])))
sd, td = GopherEngine(pg, prog, backend="shard_map", mesh=mesh,
                      exchange="dense").run()
base = TierPlan.from_graph(pg)
# sabotaged tail: busiest pair at width 1 -> replicated cond flips to the
# dense all_to_all mid-loop on every device at once
occ = occupancy_from_graph(pg)
s, d = np.unravel_index(np.argmax(occ), occ.shape)
t = base.tiers.copy(); t[s, d] = COLD
bad = PhasedTierPlan(num_parts=base.num_parts, cap=base.cap,
                     warm_cap=base.warm_cap,
                     phase_tier_bytes=(base.tier_bytes, t.tobytes()),
                     boundaries=(1, _NO_BOUNDARY))
st2, tt2 = GopherEngine(pg, prog, backend="shard_map", mesh=mesh,
                        exchange="phased", tier_plan=bad).run()
assert np.array_equal(np.asarray(sd["x"]), np.asarray(st2["x"]))
assert tt2.dense_retry_steps > 0 and not tt2.retried
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


# ---------------- announce-floor horizon ----------------

def test_announce_floor_bounded_by_horizon():
    """On a partition chain, a 1-hop horizon warms only the dirty
    partition's neighborhood; the unbounded (no-history) floor warms the
    whole meta-closure."""
    from repro.gofs.formats import PAD
    # a 2x80 strip partitions into a CHAIN-shaped meta-graph (partition 0
    # touches only partition 3), so depth actually bounds the closure
    g = road_grid(2, 80, drop_frac=0.0, seed=0, weighted=False)
    pg = partition_graph(g, bfs_grow_partition(g, 4, seed=0), 4)
    # a partition-0-LOCAL edge straight off the ELL rows (guaranteed local)
    lu = int(np.flatnonzero((pg.nbr[0] != PAD).any(1))[0])
    lv = int(pg.nbr[0][lu][pg.nbr[0][lu] != PAD][0])
    u = int(pg.global_id[0][lu])
    v = int(pg.global_id[0][lv])

    def floor_pairs(horizon_hist):
        hb = host_graph_block(pg)
        # silence the taught profile so only the announce floor shows
        P = pg.num_parts
        update_profile(hb, np.zeros((P, P)), rounds=1, decay=0.0)
        if horizon_hist is not None:
            hb["changed_ewma"][:len(horizon_hist)] = horizon_hist
        res = apply_delta(pg, EdgeDelta.inserts([u], [v]), directed=False,
                          block=hb)
        return (res.block["wire_ewma"] > 0).sum(), res.block["wire_ewma"]

    warmed_full, _ = floor_pairs(None)                   # unbounded closure
    warmed_h1, ew1 = floor_pairs([10.0])                 # horizon = 1 hop
    assert warmed_h1 < warmed_full
    # far partitions' pairs stayed cold under the bounded floor
    occ = occupancy_from_graph(pg)
    far = [p for p in range(pg.num_parts) if occ[0, p] == 0 and p != 0]
    assert far, "chain fixture must have non-adjacent partitions"
    for p in far:
        assert np.all(ew1[p] == 0.0)


# ---------------- landmark drift (serving) ----------------

def test_landmark_drift_tracks_and_rebootstraps(road):
    from repro.serving.service import GraphQueryService
    g, pg = road
    svc = GraphQueryService({"rn": pg})
    lc = svc.enable_landmarks("rn", num_landmarks=4)
    assert lc.stale_frac_ewma == 0.0 and not lc.drifted()
    rng = np.random.default_rng(0)
    # low-weight inserts relax every landmark vector -> stale fraction 1.0
    for _ in range(2):
        iu = rng.integers(0, g.n, 4)
        iv = rng.integers(0, g.n, 4)
        keep = iu != iv
        svc.apply_delta("rn", EdgeDelta.inserts(
            iu[keep], iv[keep],
            np.full(int(keep.sum()), 0.01, np.float32)),
            rebuild_landmarks=True)
    tele = svc.landmark_telemetry("rn")
    assert tele["refreshes"] == 2 and tele["stale_frac_ewma"] > 0.6
    assert tele["drifted"]
    # the next maintained delta re-bootstraps with fresh selection
    iu = rng.integers(0, g.n, 2)
    iv = (iu + 1) % g.n
    svc.apply_delta("rn", EdgeDelta.inserts(iu, iv), rebuild_landmarks=True)
    tele = svc.landmark_telemetry("rn")
    assert tele["rebootstraps"] == 1
    assert tele["refreshes"] == 0 and tele["stale_frac_ewma"] == 0.0
    # results still served correctly after the re-bootstrap
    resp = svc.query("sssp", "rn", [0])
    assert resp.error is None
    # re-inserting EXISTING edges at a huge weight provably relaxes nothing
    # (min duplicate policy; endpoints share every landmark's component), so
    # quiet versions keep the drift EWMA at/below its level
    lc2 = svc.landmark_caches["rn"]
    coo = g.undirected_csr().tocoo()
    pick = rng.integers(0, coo.nnz, 2)
    for _ in range(2):
        svc.apply_delta("rn", EdgeDelta.inserts(
            coo.row[pick], coo.col[pick],
            np.full(2, 900.0, np.float32)), rebuild_landmarks=True)
    lc3 = svc.landmark_caches["rn"]
    assert lc3.stale_frac_ewma <= lc2.stale_frac_ewma + 1e-9
