"""Gopher Hot gates: the fused superstep megakernel (kernels.megastep).

Parity contract under test — the same one the exchange stack already
promises: idempotent-⊕ programs (CC/BFS/SSSP, scalar and query-batched)
are BIT-IDENTICAL across the fused route, its Pallas embodiment
(interpret mode on CPU), the resident narrow-phase schedule, and the
staged dense/compact paths; PageRank (⊕ = sum) is allclose. Telemetry's
logical frontier observation (pair_slots / count_hist / messages_sent)
must match the compact path exactly so the tier-profile EWMAs keep
learning from fused runs, while wire_slots/bytes_on_wire are zero.
"""
import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (GopherEngine, PageRankProgram, PhasedTierPlan,
                        SemiringProgram, graph_block, init_max_vertex,
                        make_sssp_init)
from repro.algorithms.incremental import _boundary_sources, resume_seed
from repro.gofs import EdgeDelta, apply_delta, bfs_grow_partition, road_grid
from repro.gofs.formats import PAD, partition_graph
from repro.kernels import megastep as mega, ops


def _road(parts=4):
    g = road_grid(10, 11, drop_frac=0.06, seed=3, weighted=True)
    return g, partition_graph(g, bfs_grow_partition(g, parts, seed=0), parts)


@pytest.fixture(scope="module")
def road():
    return _road()


def _source(pg):
    return int(pg.part_of[0]), int(pg.local_of[0])


def _programs(pg):
    sp, sl = _source(pg)
    return {
        "cc": SemiringProgram(semiring="max_first", init_fn=init_max_vertex),
        "sssp": SemiringProgram(semiring="min_plus",
                                init_fn=make_sssp_init(sp, sl)),
    }


# ---------------- auto resolution ----------------

def test_auto_resolves_megastep_on_local(road):
    _, pg = road
    eng = GopherEngine(pg, _programs(pg)["cc"], exchange="auto")
    assert eng.exchange == "megastep"
    # bounded local fixpoints have no fused embodiment: auto keeps dense
    bounded = SemiringProgram(semiring="max_first", init_fn=init_max_vertex,
                              max_local_iters=1)
    assert GopherEngine(pg, bounded, exchange="auto").exchange == "dense"
    # fixed-iteration PageRank fuses; tolerance-halted stays dense
    pr = PageRankProgram(n_global=pg.n_global, num_iters=8)
    assert GopherEngine(pg, pr, exchange="auto").exchange == "megastep"
    pr_tol = PageRankProgram(n_global=pg.n_global, num_iters=8, tol=1e-6)
    assert GopherEngine(pg, pr_tol, exchange="auto").exchange == "dense"


def test_megastep_requires_eligible_program(road):
    _, pg = road
    bounded = SemiringProgram(semiring="max_first", init_fn=init_max_vertex,
                              max_local_iters=1)
    with pytest.raises(AssertionError, match="eligible"):
        GopherEngine(pg, bounded, exchange="megastep")


# ---------------- engine-level parity ----------------

def test_fused_bit_identity_and_telemetry(road):
    _, pg = road
    for name, prog in _programs(pg).items():
        s_ref, t_ref = GopherEngine(pg, prog, exchange="dense").run()
        _, t_cmp = GopherEngine(pg, prog, exchange="compact").run()
        s, t = GopherEngine(pg, prog, exchange="megastep").run()
        assert np.array_equal(np.asarray(s["x"]), np.asarray(s_ref["x"])), name
        assert t.supersteps == t_ref.supersteps, name
        assert np.array_equal(t.local_iters, t_ref.local_iters), name
        assert np.array_equal(t.changed_hist, t_ref.changed_hist), name
        # the LOGICAL frontier observation matches compact exactly ...
        assert np.array_equal(t.pair_slots, t_cmp.pair_slots), name
        assert np.array_equal(t.count_hist, t_cmp.count_hist), name
        assert t.messages_sent == t_cmp.messages_sent, name
        # ... but nothing ships through a routed buffer
        assert t.wire_slots == 0 and t.bytes_on_wire == 0, name


def test_pagerank_fused_allclose(road):
    _, pg = road
    prog = PageRankProgram(n_global=pg.n_global, num_iters=15)
    s_ref, t_ref = GopherEngine(pg, prog, exchange="dense").run()
    s, t = GopherEngine(pg, prog, exchange="megastep").run()
    assert t.supersteps == t_ref.supersteps
    np.testing.assert_allclose(np.asarray(s["r"]), np.asarray(s_ref["r"]),
                               rtol=1e-5, atol=1e-7)
    assert t.wire_slots == 0


def test_batched_queries_fused_parity(road):
    from repro.serving.batched import (QUERY_INIT_KEY, BatchedSemiringProgram,
                                       sssp_query_init)
    _, pg = road
    Q = 3
    prog = BatchedSemiringProgram(semiring="min_plus", num_queries=Q)
    extra = {QUERY_INIT_KEY: sssp_query_init(pg, [0, 7, 19])}
    s_ref, t_ref = GopherEngine(pg, prog,
                                exchange="compact").run_queries(extra=extra)
    s, t = GopherEngine(pg, prog,
                        exchange="megastep").run_queries(extra=extra)
    assert np.array_equal(np.asarray(s["x"]), np.asarray(s_ref["x"]))
    assert np.array_equal(t.query_supersteps, t_ref.query_supersteps)
    assert np.array_equal(t.pair_slots, t_ref.pair_slots)
    assert t.wire_slots == 0


def test_incremental_resume_rides_fused_route(road):
    """resume=True ships x0/frontier0 through ``extra`` — the merge branch
    of _gb_for_run (run-specific entries layered over the pre-composed
    mcm_* block). Parity vs the dense staged resume, and the quiesced
    resume must still halt in one superstep with zero sweeps."""
    _, pg = road
    sp, sl = _source(pg)
    fix, _ = GopherEngine(pg, SemiringProgram(
        semiring="min_plus", init_fn=make_sssp_init(sp, sl)),
        exchange="dense").run()
    x_fix = np.asarray(fix["x"])
    prog = SemiringProgram(semiring="min_plus", resume=True)
    # invalidate a patch of vertices and re-relax from the stale fixpoint.
    # The seed meets the frontier invariant of the masked sweep: the patch,
    # its local in-neighbours and the remote sources into it, so every
    # vertex left out has relaxed its out-edges
    x0 = np.where(pg.vmask, x_fix, np.inf).astype(np.float32)
    patch = np.zeros_like(pg.vmask)
    patch[1, :8] = True
    x0[patch] = np.inf
    fr0 = patch | _boundary_sources(pg, patch)
    nbr = pg.nbr[1][:8]
    fr0[1, nbr[nbr != PAD]] = True
    extra = {"x0": x0, "frontier0": fr0}
    s_ref, _ = GopherEngine(pg, prog, exchange="dense").run(extra=extra)
    eng = GopherEngine(pg, prog, exchange="megastep")
    s, t = eng.run(extra=extra)
    assert np.array_equal(np.asarray(s["x"]), np.asarray(s_ref["x"]))
    # ... and the re-relaxed patch lands back on the fixpoint
    assert np.array_equal(np.asarray(s["x"])[pg.vmask], x_fix[pg.vmask])
    # quiesced resume: one superstep, zero local iterations, state unchanged
    s2, t2 = eng.run(extra={"x0": np.asarray(s["x"]),
                            "frontier0": np.zeros_like(pg.vmask)})
    assert t2.supersteps == 1
    assert t2.local_iters.sum() == 0
    assert np.array_equal(np.asarray(s2["x"]), np.asarray(s["x"]))


def test_checkpointed_run_falls_back_to_staged(road, tmp_path):
    from repro.training.checkpoint import Checkpointer
    _, pg = road
    prog = _programs(pg)["sssp"]
    s_ref, t_ref = GopherEngine(pg, prog, exchange="dense").run()
    eng = GopherEngine(pg, prog, exchange="megastep")
    s, t = eng.run(checkpointer=Checkpointer(str(tmp_path)),
                   checkpoint_every=2)
    assert np.array_equal(np.asarray(s["x"]), np.asarray(s_ref["x"]))
    assert t.supersteps == t_ref.supersteps
    assert eng.exchange == "megastep"   # the fallback must not stick


# ---------------- resident narrow-phase mode ----------------

def test_resident_mode_bit_identity(road):
    _, pg = road
    plan = PhasedTierPlan.from_graph(pg)
    for name, prog in _programs(pg).items():
        s_ref, _ = GopherEngine(pg, prog, exchange="dense").run()
        s, t = GopherEngine(pg, prog, exchange="megastep",
                            tier_plan=plan).run()
        assert np.array_equal(np.asarray(s["x"]), np.asarray(s_ref["x"])), \
            name
        assert t.wire_slots == 0, name


def test_resident_pallas_kernel_quiescence_early_exit(road):
    """The multi-superstep resident launch (interpret mode on CPU) must
    exit on quiescence well before the iteration bound and land on the
    staged fixpoint bit for bit, with the BSP state contract intact."""
    _, pg = road
    sp, sl = _source(pg)
    prog = SemiringProgram(semiring="min_plus",
                           init_fn=make_sssp_init(sp, sl))
    gb = graph_block(pg)
    cm = mega.compose_mailbox(gb)
    st0 = jax.vmap(prog.init)(gb)
    x = st0["x"].reshape(-1)
    ch = st0["changed_v"].reshape(-1)
    fr = st0["frontier"].reshape(-1)
    x2, ch2, fr2, it, li = mega.resident_megastep_pallas(
        x, ch, fr, cm, "min_plus", max_steps=200, interpret=True)
    s_ref, _ = GopherEngine(pg, prog, exchange="dense").run()
    assert np.array_equal(np.asarray(x2).reshape(pg.num_parts, -1),
                          np.asarray(s_ref["x"]))
    assert int(it) < 200              # quiesced, not bound-limited
    assert not np.asarray(ch2).any()  # ... and the exit state shows it
    assert not np.asarray(fr2).any()


def test_resident_enter_round_suffix_rule():
    B = mega.MEGASTEP_VMEM_BUDGET
    # every band fits -> enter at superstep 0
    assert mega.resident_enter_round([B - 1, B // 2], [4]) == 0
    # only the tail band fits -> enter at its boundary
    assert mega.resident_enter_round([B + 1, B // 2], [4]) == 4
    # a non-monotone profile blocks the earlier fitting band
    assert mega.resident_enter_round([B // 2, B + 1, B // 2], [3, 7]) == 7
    # no suffix fits
    assert mega.resident_enter_round([B // 2, B + 1], [5]) is None


# ---------------- kernel-level parity (Pallas interpret vs jnp oracle) ----

def test_pallas_megastep_matches_oracle(road):
    _, pg = road
    gb = graph_block(pg)
    cm = mega.compose_mailbox(gb)
    for name, prog in _programs(pg).items():
        semiring = prog.semiring
        st0 = jax.vmap(prog.init)(gb)
        x = st0["x"].reshape(-1)
        ch = st0["changed_v"].reshape(-1)
        fr = st0["frontier"].reshape(-1)
        for _ in range(3):   # walk a few supersteps, compare each
            xo, cho, fo, lo, so = mega.megastep_semiring(
                x, ch, fr, cm, semiring, backend="jnp")
            xp, chp, fp, lp, sp = mega.megastep_semiring_pallas(
                x, ch, fr, cm, semiring, interpret=True)
            assert np.array_equal(np.asarray(xo), np.asarray(xp)), name
            assert np.array_equal(np.asarray(cho), np.asarray(chp)), name
            assert np.array_equal(np.asarray(fo), np.asarray(fp)), name
            assert np.array_equal(np.asarray(lo), np.asarray(lp)), name
            assert int(so) == int(sp), name
            x, ch, fr = xo, cho, fo


# ---------------- one gather per sweep: frontier values vs flags ----------

def _two_gather_megastep(x, changed, frontier, cm, semiring, unroll):
    """The fused superstep with the sweep that gathers the state and the
    frontier flags apart, and reduces every lane of a row that has an
    active in-neighbour (kernels.ref.semiring_spmv_frontier_ref's math)."""
    combine = "min" if semiring == "min_plus" else "max"
    red = jnp.min if combine == "min" else jnp.max
    ident = mega._IDENT[combine]
    batched = x.ndim == 2
    vm = cm["vmask"][:, None] if batched else cm["vmask"]
    P = cm["num_parts"]

    def masked(xc, f, idx, ok, w):
        okb, wb = (ok[..., None], w[..., None]) if batched else (ok, w)
        act = jnp.any(okb & f[idx], axis=1)
        g = xc[idx] + wb if semiring == "min_plus" else xc[idx]
        return jnp.where(act, red(jnp.where(okb, g, ident), axis=1), ident)

    def sweep(xc, f):
        if not batched:
            return masked(xc, f, cm["nbr"], cm["nbr_ok"], cm["wgt"])
        y = masked(xc, f, cm["nbr_lo"], cm["nbr_lo_ok"], cm["wgt_lo"])
        yh = masked(xc, f, cm["ahub_nbr"], cm["ahub_ok"], cm["ahub_wgt"])
        ref = y.at[cm["ahub_dst"]]
        return (ref.min if combine == "min" else ref.max)(yh, mode="drop")

    inbox = mega.deliver_flat(x, changed, cm, combine,
                              semiring == "min_plus")
    x1 = mega._ew(combine, x, inbox)
    f0 = frontier | ((x1 != x) & vm)

    def body(c):
        xc, f, it, li = c
        li = li + jnp.int32(unroll) * jnp.any(f.reshape(P, -1), axis=1)
        for _ in range(unroll):
            x2 = mega._ew(combine, xc, sweep(xc, f))
            f = (x2 != xc) & vm
            xc = x2
        return xc, f, it + jnp.int32(unroll), li

    x2, f_left, sweeps, liters = jax.lax.while_loop(
        lambda c: jnp.any(c[1]), body,
        (x1, f0, jnp.int32(0), jnp.zeros((P,), jnp.int32)))
    return x2, (x2 != x) & vm, f_left, liters, sweeps


def _megastep_case(case, road):
    """(cm, semiring, unroll, x, changed, frontier) of the case's first
    superstep, flat as the fused loop carries them."""
    g, pg = road
    if case in ("sssp_cold", "cc_cold"):
        prog = _programs(pg)[case[:-5]]
        gb = graph_block(pg)
        st = jax.vmap(prog.init)(gb)
        return (mega.compose_mailbox(gb), prog.semiring, 1,
                *(st[k].reshape(-1) for k in ("x", "changed_v", "frontier")))
    if case == "sssp_batched":
        from repro.serving.batched import sssp_query_init
        x = sssp_query_init(pg, [0, 7, 19, 60]).reshape(-1, 4)
        seed = np.broadcast_to(pg.vmask.reshape(-1, 1), x.shape)
        cm = mega.compose_mailbox(graph_block(pg), adjacency="binned")
        return cm, "min_plus", 2, jnp.asarray(x), seed, seed
    pg2, prog, x0, fr = _resume(case, g, pg)
    return (mega.compose_mailbox(graph_block(pg2)), prog.semiring, 1,
            jnp.asarray(x0).reshape(-1), fr.reshape(-1), fr.reshape(-1))


def _resume(case, g, pg):
    """(pg2, prog, x0, frontier0): the previous fixpoint of ``pg``'s cold
    program, seeded for a restart on the delta's graph ``pg2``."""
    rng = np.random.default_rng(4)
    a = g.csr().tocoo()
    if case == "sssp_insert":
        delta = EdgeDelta.inserts(rng.integers(0, g.n, 6),
                                  rng.integers(0, g.n, 6),
                                  rng.uniform(0.5, 2.0, 6))
        prog = _programs(pg)["sssp"]
    else:                                           # cc_delete
        und = np.flatnonzero(a.col < a.row)
        pick = rng.choice(und, 12, replace=False)
        delta = EdgeDelta.of(insert_src=[1], insert_dst=[90],
                             insert_wgt=[1.0], remove_src=a.col[pick],
                             remove_dst=a.row[pick])
        prog = _programs(pg)["cc"]
    res = apply_delta(pg, delta, directed=False)
    assert res.dirty_remove.any() == (case == "cc_delete")
    prev, _ = GopherEngine(pg, prog, exchange="dense").run()
    init = np.asarray(jax.vmap(prog.init)(graph_block(res.pg))["x"])
    x0, fr = resume_seed(res.pg, np.asarray(prev["x"]), res, init)
    return res.pg, prog, x0, fr


@pytest.mark.parametrize("case", ["sssp_cold", "cc_cold", "sssp_insert",
                                  "cc_delete", "sssp_batched"])
def test_one_gather_sweep_matches_two_gather_superstep(case, road):
    """The fused superstep whose sweep gathers only the frontier's values
    equals, superstep by superstep, the one that gathers state and flags
    apart: state, send set, leftover frontier, per-partition sweep counts
    and lockstep sweeps, from cold starts, incremental resumes and a
    query batch."""
    cm, semiring, unroll, x, ch, fr = _megastep_case(case, road)
    fused = (mega.megastep_semiring_batched if x.ndim == 2
             else mega.megastep_semiring)
    new = jax.jit(lambda x, ch, fr: fused(x, ch, fr, cm, semiring,
                                          unroll=unroll))
    old = jax.jit(lambda x, ch, fr: _two_gather_megastep(
        x, ch, fr, cm, semiring, unroll))
    total = 0
    for step in range(200):
        a, b = new(x, ch, fr), old(x, ch, fr)
        for name, u, v in zip(("x", "changed", "frontier", "liters",
                               "sweeps"), a, b):
            assert np.array_equal(np.asarray(u), np.asarray(v)), \
                (case, step, name)
        total += int(a[4])
        x, ch, fr = a[:3]
        if not np.asarray(ch).any():
            break
    assert step > 0 and total > 0, case        # the case did real work
    assert not np.asarray(ch).any(), case      # ... and quiesced


def _nbr_table_gathers(hlo: str, rows: int) -> int:
    """Gathers of ``rows`` elements (one per lane of the (n, D) neighbour
    table) in the fixpoint's while body and what it calls, from compiled
    HLO text of a module with that one loop."""
    comps, lines = {}, None
    for line in hlo.splitlines():
        m = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if m:
            comps[m.group(1)] = lines = []
        elif lines is not None:
            lines.append(line)
    body = re.findall(r" while\(.*body=%([\w.\-]+)", hlo)
    assert len(body) == 1, body
    seen, todo, count = set(), body, 0
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for ln in comps[c]:
            todo += re.findall(r"(?:calls|to_apply)=%([\w.\-]+)", ln)
            m = re.search(r"= \w+\[([\d,]+)\][^ ]* gather\(", ln)
            if m and np.prod([int(v) for v in m.group(1).split(",")]) \
                    == rows:
                count += 1
    return count


def test_fixpoint_sweep_gathers_the_neighbour_table_once(road):
    """Compiled on the CPU, the scalar megastep's fixpoint loop gathers
    over the (n, D) neighbour table once per sweep: the frontier's values,
    not the state and the flags apart (XLA must not fuse the mask back in
    front of the gather)."""
    _, pg = road
    gb = graph_block(pg)
    cm = mega.compose_mailbox(gb)
    st = jax.vmap(_programs(pg)["sssp"].init)(gb)
    args = [st[k].reshape(-1) for k in ("x", "changed_v", "frontier")]
    statics = {k: cm[k] for k in mega.MAILBOX_STATICS}
    arrays = {k: v for k, v in cm.items() if k not in mega.MAILBOX_STATICS}

    def gathers(step):
        hlo = jax.jit(lambda x, ch, fr, a: step(
            x, ch, fr, {**a, **statics}, "min_plus", 1)).lower(
                *args, arrays).compile().as_text()
        return _nbr_table_gathers(hlo, cm["nbr"].size)

    assert gathers(mega.megastep_semiring) == 1
    assert gathers(_two_gather_megastep) == 2   # the count sees both


# ---------------- one gather per staged sweep ----------------

class _TwoGatherProgram(SemiringProgram):
    """The program with the staged sweep that gathers the state and the
    frontier flags apart (ops.semiring_spmv_frontier on the jnp backend)."""

    def _masked_sweep(self, x, f, gb):
        y, _ = ops.semiring_spmv_frontier(x, f, gb["nbr"], gb["wgt"],
                                          self.semiring)
        x2 = jnp.minimum(x, y) if self.combine == "min" else jnp.maximum(x, y)
        return x2, (x2 != x) & gb["vmask"]


def _two_gather(prog):
    return _TwoGatherProgram(**{f.name: getattr(prog, f.name)
                                for f in dataclasses.fields(prog)})


def test_staged_fixpoint_gathers_the_neighbour_table_once(road):
    """Compiled on the CPU, the staged superstep's fixpoint loop (the
    vmapped SemiringProgram.superstep that every shard_map run takes)
    gathers over the (P, v_max, D) neighbour table once per sweep on the
    jnp backend; the sweep that gathers the flags too counts 2."""
    _, pg = road
    gb = graph_block(pg)
    prog = _programs(pg)["sssp"]
    st = jax.vmap(prog.init)(gb)

    def gathers(p):
        step = jax.jit(jax.vmap(lambda s, i, g: p.superstep(s, i, g, 0)))
        hlo = step.lower(st, st["x"], gb).compile().as_text()
        return _nbr_table_gathers(hlo, gb["nbr"].size)

    assert gathers(prog) == 1
    assert gathers(_two_gather(prog)) == 2      # the count sees both


def _relax_ref(pg, x, semiring):
    """Plain float32 relaxation to quiescence over every edge of ``pg``,
    local (ELL in-neighbour rows) and remote, in (P, v_max) slot
    coordinates, from the state ``x``."""
    P, V = pg.vmask.shape
    p, v, j = np.nonzero(pg.nbr != PAD)
    q, k = np.nonzero(pg.re_src != PAD)
    src = np.concatenate([p * V + pg.nbr[p, v, j], q * V + pg.re_src[q, k]])
    dst = np.concatenate([p * V + v,
                          pg.re_dst_part[q, k] * V + pg.re_dst_local[q, k]])
    w = np.concatenate([pg.wgt[p, v, j], pg.re_wgt[q, k]]).astype(np.float32)
    red = np.minimum if semiring == "min_plus" else np.maximum
    x = np.asarray(x, np.float32).reshape(-1)
    while True:
        nxt = x.copy()
        red.at(nxt, dst, x[src] + w if semiring == "min_plus" else x[src])
        if np.array_equal(nxt, x):
            return x.reshape(P, V)
        x = nxt


STAGED_CASES = ("sssp_bounded", "cc_bounded", "sssp_insert", "cc_delete")


def check_staged_route(case, g, pg, **engine):
    """The staged route (``engine``: its backend, mesh and exchange) with
    the one-gather sweep equals, bit for bit, the same route with the
    sweep that gathers the flags apart (state, supersteps, per-partition
    sweeps), the fused megastep route (state; supersteps and sweeps where
    the fused route runs the same program) and a plain relaxation."""
    if case.endswith("_cold"):
        prog = fused = _programs(pg)[case[:-5]]
        cold, extra = prog, None
    elif case.endswith("_bounded"):
        cold = fused = _programs(pg)[case[:-8]]   # no fused bounded route
        prog, extra = dataclasses.replace(cold, max_local_iters=3), None
    else:
        pg, cold, x0, fr = _resume(case, g, pg)
        prog = fused = dataclasses.replace(cold, resume=True)
        extra = {"x0": x0, "frontier0": fr}
    s, t = GopherEngine(pg, prog, **engine).run(extra=extra)
    s2, t2 = GopherEngine(pg, _two_gather(prog), **engine).run(extra=extra)
    x = np.asarray(s["x"])
    assert np.array_equal(x, np.asarray(s2["x"])), case
    assert t.supersteps == t2.supersteps, case
    assert np.array_equal(t.local_iters, t2.local_iters), case
    assert t.local_iters.sum() > 0, case           # the sweeps did work
    sf, tf = GopherEngine(pg, fused, exchange="megastep").run(extra=extra)
    assert np.array_equal(x, np.asarray(sf["x"])), case
    if fused is prog:
        assert t.supersteps == tf.supersteps, case
        assert np.array_equal(t.local_iters, tf.local_iters), case
    init = np.asarray(jax.vmap(cold.init)(graph_block(pg))["x"])
    assert np.array_equal(x, _relax_ref(pg, init, prog.semiring)), case


@pytest.mark.parametrize("case", STAGED_CASES)
def test_staged_one_gather_sweep_is_bit_equal(case, road):
    """Bounded fixpoints and incremental resumes on the local backend's
    staged dense exchange (cold starts: test_fused_bit_identity_and_telemetry)."""
    check_staged_route(case, *road, exchange="dense")


def test_staged_one_gather_sweep_is_bit_equal_over_four_devices():
    """The same cases and a cold CC on D=4 CPU devices (forced in a
    subprocess, before jax initializes), 2 partitions per device, over the
    tiered exchange that the shard_map runs take (cold SSSP there:
    test_mesh.test_partitioned_sssp_over_four_devices)."""
    import os
    import subprocess
    import sys
    prog = r"""
from repro.core import compat
import test_megastep as tm
g, pg = tm._road(8)
mesh = compat.make_mesh((4,), ("parts",))
for case in ("cc_cold",) + tm.STAGED_CASES:
    tm.check_staged_route(case, g, pg, backend="shard_map", mesh=mesh,
                          exchange="tiered")
print("OK")
"""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(here, os.pardir, "src"), here,
                    env.get("PYTHONPATH", "")) if p)
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


def test_engine_dispatches_pallas_backend(road):
    """Ask for the Pallas backend (interpreted, off the chip) and run the
    whole engine loop through the megakernel embodiment."""
    _, pg = road
    prog = _programs(pg)["cc"]
    s_ref, t_ref = GopherEngine(pg, prog, exchange="dense").run()
    pallas = dataclasses.replace(prog, spmv_backend="pallas", interpret=True)
    s, t = GopherEngine(pg, pallas, exchange="megastep").run()
    assert np.array_equal(np.asarray(s["x"]), np.asarray(s_ref["x"]))
    assert t.supersteps == t_ref.supersteps


# ---------------- composed-mailbox observations ----------------

def test_round_stats_matches_slot_table(road):
    """The einsum contraction must reproduce the slot-table observation
    exactly: pairs[p, j] counts active ob_inv slots p->j (== the compact
    path's active_slots), nsent counts replicated edges in the send set."""
    _, pg = road
    cm = mega.compose_mailbox(graph_block(pg))
    P, cap, n = cm["num_parts"], cm["cap"], cm["n"]
    so = np.asarray(cm["slot_ok"]).reshape(P, P, cap)
    ss = np.asarray(cm["slot_src"]).reshape(P, P, cap)
    eo = np.asarray(cm["edge_ok"])
    es = np.asarray(cm["edge_src"])
    rng = np.random.default_rng(0)
    for changed in [None,
                    rng.random(n) < 0.2,
                    rng.random(n) < 0.8,
                    np.zeros(n, bool),
                    rng.random((n, 3)) < 0.15]:        # batched send set
        pairs, nsent = mega.round_stats(
            None if changed is None else jnp.asarray(changed), cm)
        send_v = (np.ones(n, bool) if changed is None
                  else changed if changed.ndim == 1
                  else changed.any(axis=1))
        ref_pairs = (so & send_v[ss]).sum(axis=2)
        assert np.array_equal(np.asarray(pairs), ref_pairs)
        if changed is None or changed.ndim == 1:
            ref_sent = int((eo & send_v[es]).sum())
        else:   # batched: messages counted per query lane
            ref_sent = int((eo[..., None] & changed[es]).sum())
        assert int(nsent) == ref_sent


def test_service_warm_precompiles_fused_loop(road):
    from repro.serving import GraphQueryService
    _, pg = road
    svc = GraphQueryService({"road": pg}, max_batch=8)
    assert svc.warm("road") >= 1
    r = svc.query("bfs", "road", 0)
    assert r.result[0] == 0.0
