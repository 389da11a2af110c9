"""Gopher Sentinel: the three passes must (a) pass clean on the real
engine/kernels across the exchange matrix, and (b) catch each seeded
violation — a mismatched-collective cond branch, a tracer-leaked tier
table, an unmasked partial Pallas block — with a diagnostic that NAMES the
offending equation/field/kernel, not just a boolean."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (
    REGISTRY,
    SentinelError,
    Violation,
    assert_clean,
    check_plan_static,
    check_program,
    check_semiring,
    errors,
    lint_kernels,
    lint_source,
    probe_laws,
    verify_collectives,
    verify_jaxpr,
)
from repro.core import (
    GopherEngine,
    PageRankProgram,
    PhasedTierPlan,
    SemiringProgram,
    TierPlan,
    compat,
    init_max_vertex,
    make_sssp_init,
)
from repro.core.tiers import _NO_BOUNDARY
from repro.gofs import bfs_grow_partition, road_grid
from repro.gofs.formats import partition_graph

P = jax.sharding.PartitionSpec


@pytest.fixture(scope="module")
def pg8():
    g = road_grid(10, 10, drop_frac=0.05, seed=1, weighted=True)
    return partition_graph(g, bfs_grow_partition(g, 8, seed=0), 8)


def _phased_plan(pg):
    base = TierPlan.from_graph(pg)
    return PhasedTierPlan(
        num_parts=base.num_parts, cap=base.cap, warm_cap=base.warm_cap,
        phase_tier_bytes=(base.tier_bytes, base.tier_bytes),
        boundaries=(3, _NO_BOUNDARY))


# ---------------- Pass 1: positives ----------------

@pytest.mark.parametrize("mode", ["dense", "compact", "tiered", "phased"])
def test_collectives_clean_on_real_engine(pg8, mode):
    mesh = compat.abstract_mesh((4,), ("parts",))
    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    plan = _phased_plan(pg8) if mode == "phased" else None
    eng = GopherEngine(pg8, prog, backend="shard_map", mesh=mesh,
                       exchange=mode, tier_plan=plan)
    summary, violations = verify_collectives(eng)
    assert errors(violations) == [], [str(v) for v in violations]
    assert summary.mesh_axes == {"parts": 4}
    # every mode moves data across the 4-device mesh — but the two-level
    # hot schedule sizes the uniform all_to_all block to the MINIMUM
    # per-device-pair hot count, so a skewed mesh (zero hot rows on some
    # pair, as here) may route everything through residual ppermutes
    moved = (summary.counts.get("all_to_all", 0)
             + summary.counts.get("ppermute", 0))
    assert moved > 0
    if mode in ("dense", "compact"):
        assert summary.counts.get("all_to_all", 0) > 0


def test_local_backend_has_no_collectives(pg8):
    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    eng = GopherEngine(pg8, prog, backend="local", exchange="compact")
    summary, violations = verify_collectives(eng)
    assert violations == []
    assert summary.counts == {}


def test_engine_validate_hook_runs_clean(pg8):
    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    eng = GopherEngine(pg8, prog, exchange="compact", validate=True)
    state, _ = eng.run()
    ref = GopherEngine(pg8, prog, exchange="dense").run()[0]
    assert np.array_equal(np.asarray(state["x"]), np.asarray(ref["x"]))


# ---------------- Pass 1 negative: mismatched cond branches ----------------

def test_cond_collective_mismatch_caught():
    """Branches issuing different collectives under a NON-replicated
    predicate (derived from axis_index) is the SPMD deadlock shape — the
    diagnostic must name the cond equation and show both branch traces."""
    mesh = compat.abstract_mesh((4,), ("parts",))

    def body(x):
        i = jax.lax.axis_index("parts")

        def with_psum(v):
            return jax.lax.psum(v, "parts")

        def without(v):
            return v * 2.0

        return jax.lax.cond(i > 0, with_psum, without, x)

    f = compat.shard_map(body, mesh=mesh, in_specs=(P("parts"),),
                         out_specs=P("parts"))
    jaxpr = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4, 8), jnp.float32))
    _, violations = verify_jaxpr(jaxpr)
    errs = errors(violations)
    assert len(errs) == 1
    v = errs[0]
    assert v.code == "COND_COLLECTIVE_MISMATCH"
    assert "cond" in v.where                      # names the equation path
    assert "psum" in v.detail and "deadlock" in v.detail
    with pytest.raises(SentinelError) as ei:
        assert_clean(violations)
    assert "COND_COLLECTIVE_MISMATCH" in str(ei.value)


def test_cond_mismatch_allowed_when_predicate_replicated():
    """The phased dense-retry shape: branches differ but the predicate
    rides a full mesh-axis psum — provably uniform, so no violation."""
    mesh = compat.abstract_mesh((4,), ("parts",))

    def body(x):
        flag = jax.lax.psum((x.sum() > 0).astype(jnp.int32), "parts")

        def with_psum(v):
            return jax.lax.psum(v, "parts")

        def without(v):
            return v * 2.0

        return jax.lax.cond(flag > 0, with_psum, without, x)

    f = compat.shard_map(body, mesh=mesh, in_specs=(P("parts"),),
                         out_specs=P(None))
    jaxpr = jax.make_jaxpr(f)(jax.ShapeDtypeStruct((4, 8), jnp.float32))
    summary, violations = verify_jaxpr(jaxpr)
    assert violations == []
    assert len(summary.conds) == 1
    assert not summary.conds[0].branches_equal
    assert summary.conds[0].predicate_uniform


def test_phased_engine_retry_conds_proven_safe(pg8):
    mesh = compat.abstract_mesh((4,), ("parts",))
    prog = SemiringProgram(
        semiring="min_plus",
        init_fn=make_sssp_init(int(pg8.part_of[0]), int(pg8.local_of[0])))
    eng = GopherEngine(pg8, prog, backend="shard_map", mesh=mesh,
                       exchange="phased", tier_plan=_phased_plan(pg8))
    summary, violations = verify_collectives(eng)
    assert violations == []
    assert summary.conds and all(c.predicate_uniform and not c.branches_equal
                                 for c in summary.conds)


# ---------------- Pass 1 negative: non-static tier plans ----------------

def test_tracer_leaked_plan_caught():
    base = TierPlan(num_parts=2, cap=4, warm_cap=2, tier_bytes=bytes(4))
    captured = {}

    def build_inside_jit(t):
        bad = dataclasses.replace(base)
        object.__setattr__(bad, "cap", t)      # a tracer smuggled in
        captured["violations"] = check_plan_static(bad)
        return t

    jax.make_jaxpr(build_inside_jit)(1)
    errs = errors(captured["violations"])
    assert len(errs) == 1
    v = errs[0]
    assert v.code == "PLAN_TRACER_LEAK"
    assert v.where == "tier_plan.cap"             # names the field
    assert "tracer" in v.detail and "cache" in v.detail


def test_array_valued_plan_field_caught():
    base = TierPlan(num_parts=2, cap=4, warm_cap=2, tier_bytes=bytes(4))
    bad = dataclasses.replace(base)
    object.__setattr__(bad, "tier_bytes", np.zeros(4, np.uint8))
    errs = errors(check_plan_static(bad))
    assert [v.code for v in errs] == ["PLAN_UNHASHABLE_FIELD"]
    assert "tier_bytes" in errs[0].where
    assert "unhashable" in errs[0].detail


def test_plan_geometry_checked():
    bad = TierPlan(num_parts=3, cap=4, warm_cap=2, tier_bytes=bytes(4))
    errs = errors(check_plan_static(bad))
    assert [v.code for v in errs] == ["PLAN_BAD_GEOMETRY"]
    ok = TierPlan(num_parts=2, cap=4, warm_cap=2, tier_bytes=bytes(4))
    assert check_plan_static(ok) == []


def test_validate_hook_rejects_bad_plan(pg8):
    """engine.validate=True refuses to compile a loop whose plan cannot
    key the cache — raised before tracing, naming the field."""
    plan = TierPlan.from_graph(pg8)
    bad = dataclasses.replace(plan)
    object.__setattr__(bad, "tier_bytes", np.frombuffer(plan.tier_bytes,
                                                        np.uint8).copy())
    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    eng = GopherEngine(pg8, prog, exchange="tiered", tier_plan=bad,
                       validate=True)
    with pytest.raises(SentinelError) as ei:
        eng.run()
    assert "tier_bytes" in str(ei.value)


# ---------------- Pass 2: semiring laws ----------------

def test_registered_semirings_clean():
    for name in REGISTRY:
        assert check_semiring(name) == [], name


def test_overclaimed_idempotence_caught():
    bad = dataclasses.replace(REGISTRY["plus_times"], name="bad_sum",
                              declares_idempotent=True)
    errs = errors(probe_laws(bad))
    assert any(v.code == "PLUS_NOT_IDEMPOTENT" for v in errs)
    v = next(v for v in errs if v.code == "PLUS_NOT_IDEMPOTENT")
    # the diagnostic carries the counterexample and the retry consequence
    assert "⊕" in v.detail and "a=" in v.detail
    assert "dense-retry" in v.detail


def test_wrong_identity_caught():
    bad = dataclasses.replace(REGISTRY["min_plus"], plus_identity=0.0)
    codes = {v.code for v in errors(probe_laws(bad))}
    assert "PLUS_IDENTITY_WRONG" in codes
    assert "IDENTITY_NOT_ANNIHILATING" in codes


def test_pagerank_flagged_allclose_only(pg8):
    prog = PageRankProgram(n_global=pg8.n_global, num_iters=5)
    vs = check_program(prog, "phased")
    assert errors(vs) == []
    infos = [v for v in vs if v.code == "ALLCLOSE_ONLY"]
    assert len(infos) == 1 and infos[0].severity == "info"
    # on the dense path there is no retry, so no flag
    assert check_program(prog, "dense") == []


def test_idempotent_programs_not_flagged():
    prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    assert check_program(prog, "phased") == []


# ---------------- Pass 3: Pallas kernel linter ----------------

def test_repo_kernels_lint_clean():
    assert lint_kernels() == [], [str(v) for v in lint_kernels()]


_UNMASKED_PARTIAL_BLOCK = '''
import jax, jax.numpy as jnp
from jax.experimental import pallas as pl

def _half_masked_kernel(x_ref, y_ref):
    x = x_ref[...]
    cond = jnp.any(x > 0)
    @pl.when(cond)
    def _go():
        y_ref[...] = x * 2.0

def wrapper(x, block=8):
    r, = x.shape
    grid = (r // block,)
    return pl.pallas_call(_half_masked_kernel, grid=grid,
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((r,), x.dtype))(x)
'''


def test_unmasked_partial_block_caught():
    vs = lint_source(_UNMASKED_PARTIAL_BLOCK, "seeded.py")
    codes = {v.code for v in errors(vs)}
    assert codes == {"PALLAS_UNMASKED_STORE", "PALLAS_GRID_DIVISIBILITY"}
    store = next(v for v in vs if v.code == "PALLAS_UNMASKED_STORE")
    # names the kernel AND the output ref, with the actionable fix
    assert "_half_masked_kernel" in store.where
    assert "y_ref" in store.where
    assert "complementary" in store.detail
    grid = next(v for v in vs if v.code == "PALLAS_GRID_DIVISIBILITY")
    assert "wrapper" in grid.where
    assert "r // block" in grid.detail


def test_mask_multiply_on_ref_values_caught():
    src = '''
import jax.numpy as jnp
def _mul_kernel(v_ref, m_ref, o_ref):
    vals = v_ref[...]
    mask = m_ref[...] > 0
    o_ref[...] = jnp.sum(mask * vals, axis=-1)
'''
    vs = lint_source(src, "seeded.py")
    errs = errors(vs)
    assert [v.code for v in errs] == ["PALLAS_MASK_MULTIPLY"]
    assert "_mul_kernel" in errs[0].where
    assert "jnp.where" in errs[0].detail          # tells you the fix
    # the unselected reduction is also flagged, as a warning
    assert any(v.code == "REDUCE_UNMASKED" and v.severity == "warning"
               for v in vs)


def test_mask_multiply_iota_exempt():
    """The real pack kernels multiply masks into IOTA-derived slot ids —
    finite by construction, must stay clean."""
    src = '''
import jax, jax.numpy as jnp
def _plan_kernel(a_ref, o_ref):
    act = a_ref[...] > 0
    slot = jax.lax.broadcasted_iota(jnp.float32, (8, 8), 1)
    o_ref[...] = jnp.sum(act * slot, axis=-1)
'''
    assert errors(lint_source(src, "ok.py")) == []


def test_io_alias_race_caught():
    src = '''
import jax
from jax.experimental import pallas as pl
def _alias_kernel(a_ref, o_ref):
    o_ref[...] = a_ref[...] * 2.0
    o_ref[...] = o_ref[...] + a_ref[...]
def wrapper(x):
    return pl.pallas_call(_alias_kernel, grid=(4,),
        in_specs=[pl.BlockSpec((8,), lambda i: (i,))],
        out_specs=pl.BlockSpec((8,), lambda i: (i,)),
        input_output_aliases={0: 0},
        out_shape=jax.ShapeDtypeStruct((32,), x.dtype))(x)
'''
    errs = errors(lint_source(src, "seeded.py"))
    assert [v.code for v in errs] == ["IO_ALIAS"]
    assert "_alias_kernel" in errs[0].where
    assert "clobbered" in errs[0].detail


def test_complementary_when_and_ceil_pad_clean():
    """The repo's own idiom (mirrored): complementary pl.when branches +
    ceil-pad grid must produce zero findings."""
    src = '''
import jax, jax.numpy as jnp
from jax.experimental import pallas as pl
def _ok_kernel(x_ref, y_ref):
    x = x_ref[...]
    cond = jnp.any(x > 0)
    @pl.when(cond)
    def _go():
        y_ref[...] = x * 2.0
    @pl.when(~cond)
    def _skip():
        y_ref[...] = jnp.zeros_like(x)
def wrapper(x, block=8):
    r, = x.shape
    br = min(block, r)
    r_pad = -(-r // br) * br
    grid = (r_pad // br,)
    return pl.pallas_call(_ok_kernel, grid=grid,
        in_specs=[pl.BlockSpec((br,), lambda i: (i,))],
        out_specs=pl.BlockSpec((br,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((r_pad,), x.dtype))(x)[:r]
'''
    assert lint_source(src, "ok.py") == []


# ---------------- report plumbing ----------------

def test_violation_json_roundtrip():
    v = Violation(pass_name="kernels", code="X", where="w", detail="d")
    assert v.to_json() == {"pass_name": "kernels", "code": "X", "where": "w",
                           "detail": "d", "severity": "error"}


def test_sentinel_cli_quick_matrix(tmp_path):
    """The CLI end to end (quick matrix, no HLO compile): report written,
    zero errors on the real engine."""
    import json

    from repro.launch.sentinel import main
    out = tmp_path / "report.json"
    rc = main(["--matrix", "quick", "--no-hlo", "--out", str(out)])
    assert rc == 0
    rep = json.loads(out.read_text())
    assert rep["summary"]["errors"] == 0
    assert rep["summary"]["configs"] > 0
    assert all(c["errors"] == 0 for c in rep["configs"])
