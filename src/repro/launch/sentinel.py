"""Gopher Sentinel CLI — the full static-verification matrix.

    PYTHONPATH=src python -m repro.launch.sentinel --matrix full \
        [--devices 1,2,4] [--out sentinel_report.json] [--no-hlo]

Runs the three sentinel passes (see repro.analysis) over the whole
exchange × algorithm × mesh matrix:

  * **Pass 1** (SPMD collective verifier) traces every engine
    configuration's compiled BSP loop on :class:`jax.sharding.AbstractMesh`
    shapes — 5 shard_map exchange modes × {cc, bfs, sssp, pagerank} ×
    D ∈ {1,2,4} with NO subprocess and no real devices, plus the LOCAL
    backend where ``exchange='auto'`` resolves eligible programs to the
    Gopher Hot megastep route — and checks cond-branch collective
    agreement, axis binding, tier-plan staticness, and that the fused
    megastep loop issues no collectives at all.
  * **Pass 2** (semiring laws) probes each program's ⊕/⊗ algebra.
  * **Pass 3** (Pallas linter) lints the kernel modules (megastep.py
    included).
  * **HLO cross-check**: for every tiered/phased loop at D > 1 the loop is
    actually compiled (host platform forced to the max requested device
    count) and the post-compile collective instructions parsed by
    launch/hloparse must agree with the jaxpr-level trace — kind sets
    strictly (error on mismatch), per-kind counts recorded and compared
    (warning on mismatch, to stay robust across XLA versions), and every
    wire collective's byte size checked against the tier plan's predicted
    per-device round geometry (error past the budget).

Emits a machine-readable JSON report and exits non-zero on any
error-severity violation — the CI ``sentinel-gate`` job runs exactly this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _parse(argv=None):
    ap = argparse.ArgumentParser(description="Gopher Sentinel static checks")
    ap.add_argument("--matrix", choices=("full", "quick"), default="full")
    ap.add_argument("--devices", default="1,2,4",
                    help="comma-separated mesh sizes to verify")
    ap.add_argument("--parts", type=int, default=8)
    ap.add_argument("--rows", type=int, default=10)
    ap.add_argument("--cols", type=int, default=10)
    ap.add_argument("--out", default="sentinel_report.json")
    ap.add_argument("--hlo", dest="hlo", action="store_true", default=True)
    ap.add_argument("--no-hlo", dest="hlo", action="store_false",
                    help="skip the post-compile HLO cross-check")
    return ap.parse_args(argv)


_ALGOS = ("cc", "bfs", "sssp", "pagerank")
_MODES = ("dense", "compact", "tiered", "phased", "auto")


def _build_graph(args):
    from repro.gofs import bfs_grow_partition, road_grid
    from repro.gofs.formats import partition_graph
    g = road_grid(args.rows, args.cols, drop_frac=0.05, seed=1,
                  weighted=True)
    return partition_graph(g, bfs_grow_partition(g, args.parts, seed=0),
                           args.parts)


def _program(algo: str, pg):
    from repro.core import (PageRankProgram, SemiringProgram,
                            init_max_vertex, make_bfs_init, make_sssp_init)
    sp, sl = int(pg.part_of[0]), int(pg.local_of[0])
    if algo == "cc":
        return SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    if algo == "bfs":
        return SemiringProgram(semiring="min_plus",
                               init_fn=make_bfs_init(sp, sl))
    if algo == "sssp":
        return SemiringProgram(semiring="min_plus",
                               init_fn=make_sssp_init(sp, sl))
    return PageRankProgram(n_global=pg.n_global, num_iters=12)


def _plan(mode: str, pg):
    from repro.core import PhasedTierPlan, TierPlan
    from repro.core.tiers import _NO_BOUNDARY
    if mode == "tiered":
        return TierPlan.from_graph(pg)
    if mode == "phased":
        base = TierPlan.from_graph(pg)
        return PhasedTierPlan(
            num_parts=base.num_parts, cap=base.cap, warm_cap=base.warm_cap,
            phase_tier_bytes=(base.tier_bytes, base.tier_bytes),
            boundaries=(3, _NO_BOUNDARY))
    return None


def _jaxpr_hlo_counts(summary) -> dict:
    """jaxpr collective counts folded onto HLO opcodes (psum/pmax/pmin all
    lower to all-reduce)."""
    from repro.analysis import HLO_KIND
    out: dict = {}
    for kind, n in summary.counts.items():
        hk = HLO_KIND[kind]
        out[hk] = out.get(hk, 0) + n
    return out


def _hlo_cross_check(entry, eng, summary, violations):
    """Compile the loop for real and demand the HLO collective trace agree
    with the jaxpr-level one."""
    import jax

    from repro.analysis import ERROR, WARNING, Violation
    from repro.core import graph_block
    from repro.launch.hloparse import Analyzer

    D = entry["D"]
    if jax.device_count() < D:
        entry["hlo"] = {"skipped": f"needs {D} devices, have "
                                   f"{jax.device_count()}"}
        return
    from repro.core import GopherEngine, compat
    mesh = compat.make_mesh((D,), ("parts",))
    real = GopherEngine(eng.pg, eng.program, backend="shard_map", mesh=mesh,
                        exchange=eng.exchange_requested,
                        tier_plan=eng.tier_plan)
    text = real._sharded_fn().lower(graph_block(eng.pg, as_spec=True)) \
        .compile().as_text()
    rep = Analyzer(text).collective_report()
    hlo_counts = {k: v["count"] for k, v in rep.items()}
    hlo_bytes = {k: v["bytes"] for k, v in rep.items()}
    # per-KIND byte budgets: no wire collective may ship more than the tier
    # plan's predicted per-device geometry FOR ITS OWN KIND — the hot
    # uniform block bounds every all_to_all, the round's summed shifts
    # bound every ppermute (summed, not per-shift, so the ceiling holds
    # when XLA combines a round's ppermutes into one instruction). An
    # instruction over its kind budget means the compiled loop ships
    # traffic the plan's wire geometry never predicted.
    from repro.core import PhasedTierPlan
    plan = eng.tier_plan
    plans = (plan.phase_plans() if isinstance(plan, PhasedTierPlan)
             else (plan,))
    budgets: dict = {}
    for p in plans:
        for k, b in p.schedule(D).kind_byte_budgets(None).items():
            budgets[k] = max(budgets.get(k, 0), b)
    if isinstance(plan, PhasedTierPlan):
        # the phased loop carries a per-superstep dense-retry cond branch;
        # its all_to_all legitimately ships the DENSE round, so the
        # all-to-all ceiling for a phased loop is the dense per-device
        # geometry
        P = plan.num_parts
        budgets["all-to-all"] = max(budgets.get("all-to-all", 0),
                                    (P // D) * P * plan.cap * 4)
    over = [(ci.name, ci.result_bytes, k, budgets[k])
            for k in ("all-to-all", "collective-permute") if k in rep
            for ci in rep[k]["instrs"] if ci.result_bytes > budgets.get(k, 0)]
    if over:
        violations.append(Violation(
            pass_name="collectives", code="HLO_BYTE_BUDGET",
            where=f"{entry['algo']}/{entry['exchange']}/D={D}",
            detail=(f"wire collectives {over} exceed the tier plan's "
                    "per-device per-kind byte budgets (name, bytes, kind, "
                    "budget) — the compiled loop ships traffic the plan's "
                    "wire geometry never predicted"),
            severity=ERROR))
    want_kinds = set(summary.expected_hlo_kinds())
    got_kinds = set(rep)
    want_counts = _jaxpr_hlo_counts(summary)
    agrees_kinds = want_kinds == got_kinds
    agrees_counts = want_counts == hlo_counts
    where = (f"{entry['algo']}/{entry['exchange']}/D={D}")
    if not agrees_kinds:
        violations.append(Violation(
            pass_name="collectives", code="HLO_KIND_MISMATCH", where=where,
            detail=(f"post-compile HLO collectives {sorted(got_kinds)} "
                    "disagree with the jaxpr-level trace "
                    f"{sorted(want_kinds)}: either the walker missed a "
                    "collective or XLA synthesized one the sentinel "
                    "never verified"),
            severity=ERROR))
    elif not agrees_counts:
        violations.append(Violation(
            pass_name="collectives", code="HLO_COUNT_MISMATCH", where=where,
            detail=(f"per-kind HLO collective counts {hlo_counts} != "
                    f"jaxpr-level {want_counts} (kind sets agree; XLA may "
                    "have split/merged collectives — verify manually)"),
            severity=WARNING))
    entry["hlo"] = {
        "kinds": sorted(got_kinds), "counts": hlo_counts,
        "bytes": hlo_bytes, "jaxpr_counts": want_counts,
        "byte_budgets": dict(budgets), "within_byte_budget": not over,
        "agrees_kinds": agrees_kinds, "agrees_counts": agrees_counts,
    }


def run_matrix(args) -> dict:
    from repro.analysis import (check_program, check_semiring, errors,
                                lint_kernels, verify_collectives)
    from repro.analysis.semiring import REGISTRY
    from repro.core import GopherEngine, compat

    pg = _build_graph(args)
    devices = tuple(int(d) for d in str(args.devices).split(",") if d)
    algos = _ALGOS if args.matrix == "full" else ("cc", "pagerank")
    modes = _MODES if args.matrix == "full" else ("dense", "tiered",
                                                  "phased")
    violations = []
    configs = []

    kern = lint_kernels()
    violations += kern
    semi = {}
    for name in REGISTRY:
        vs = check_semiring(name)
        violations += vs
        semi[name] = {"violations": [v.to_json() for v in vs]}

    checked_programs = set()
    for D in devices:
        mesh = compat.abstract_mesh((D,), ("parts",))
        for algo in algos:
            for mode in modes:
                prog = _program(algo, pg)
                eng = GopherEngine(pg, prog, backend="shard_map", mesh=mesh,
                                   exchange=mode, tier_plan=_plan(mode, pg))
                pkey = (algo, eng.exchange)
                if pkey not in checked_programs:
                    checked_programs.add(pkey)
                    violations += check_program(prog, eng.exchange)
                summary, vs = verify_collectives(eng)
                violations += vs
                entry = {
                    "algo": algo, "requested_exchange": mode,
                    "exchange": eng.exchange, "D": D,
                    "counts": summary.counts,
                    "expected_hlo_kinds": list(summary.expected_hlo_kinds()),
                    "conds": summary.to_json()["conds"],
                    "errors": len(errors(vs)),
                }
                if (args.hlo and D > 1
                        and eng.exchange in ("tiered", "phased")):
                    _hlo_cross_check(entry, eng, summary, violations)
                configs.append(entry)

    # local-backend coverage: exchange='auto' resolves the eligible
    # programs to the Gopher Hot megastep route there. Pass 1 walks the
    # fused loop like any other — and a megastep loop that issues ANY
    # collective is broken by construction (the whole point of the route
    # is that nothing crosses the wire)
    from repro.analysis import ERROR, Violation
    for algo in algos:
        prog = _program(algo, pg)
        eng = GopherEngine(pg, prog, exchange="auto")
        pkey = (algo, eng.exchange)
        if pkey not in checked_programs:
            checked_programs.add(pkey)
            violations += check_program(prog, eng.exchange)
        summary, vs = verify_collectives(eng)
        violations += vs
        if eng.exchange == "megastep" and summary.counts:
            violations.append(Violation(
                pass_name="collectives", code="MEGASTEP_COLLECTIVE",
                where=f"{algo}/megastep/local",
                detail=(f"fused megastep loop issues collectives "
                        f"{summary.counts} — the single-launch route must "
                        "never touch the wire"),
                severity=ERROR))
        configs.append({
            "algo": algo, "requested_exchange": "auto",
            "exchange": eng.exchange, "D": 1, "backend": "local",
            "counts": summary.counts,
            "expected_hlo_kinds": list(summary.expected_hlo_kinds()),
            "conds": summary.to_json()["conds"],
            "errors": len(errors(vs)),
        })

    # Gopher Shield coverage — Pass 1 over (a) the STAGED STEPPED DRIVER
    # (init/sweep/pack/route), the loop every checkpoint/replay recovery
    # resumes through, per mesh size; (b) the batched multi-query SERVING
    # loops a GraphQueryService pools, at the exact query shapes drain()
    # dispatches
    from repro.analysis import (ERROR, SentinelError, Violation,
                                validate_service, validate_stage_fns)
    staged = []
    for D in devices:
        mesh = compat.abstract_mesh((D,), ("parts",))
        eng = GopherEngine(pg, _program("sssp", pg), backend="shard_map",
                           mesh=mesh, exchange="compact")
        entry = {"driver": "staged", "D": D}
        try:
            summaries, vs = validate_stage_fns(eng)
            violations += vs
            entry["stages"] = {k: s.counts for k, s in summaries.items()}
            entry["errors"] = len(errors(vs))
        except SentinelError as e:
            violations.append(Violation(
                pass_name="collectives", code="STAGED_DRIVER",
                where=f"staged/D={D}", detail=str(e), severity=ERROR))
            entry["errors"] = 1
        staged.append(entry)

    from repro.serving.service import GraphQueryService
    svc = GraphQueryService({"sentinel": pg})
    families = ("reach", "ppr") if args.matrix == "full" else ("reach",)
    qs = (1, 2) if args.matrix == "full" else (1,)
    serving = {}
    try:
        res = validate_service(svc, families=families, qs=qs)
        serving = {f"{g}/{fam}/Q={q}": len(vs)
                   for (g, fam, q), vs in res.items()}
    except SentinelError as e:
        violations.append(Violation(
            pass_name="collectives", code="SERVING_LOOP",
            where="serving", detail=str(e), severity=ERROR))

    errs = errors(violations)
    return {
        "matrix": args.matrix,
        "devices": list(devices),
        "configs": configs,
        "staged_driver": staged,
        "serving": serving,
        "kernel_lint": [v.to_json() for v in kern],
        "semirings": semi,
        "violations": [v.to_json() for v in violations],
        "summary": {
            "configs": len(configs),
            "violations": len(violations),
            "errors": len(errs),
            "warnings_infos": len(violations) - len(errs),
            "hlo_checked": sum(1 for c in configs
                               if c.get("hlo", {}).get("agrees_kinds")),
        },
    }


def main(argv=None) -> int:
    args = _parse(argv)
    report = run_matrix(args)
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    s = report["summary"]
    print(f"# gopher sentinel — matrix={report['matrix']} "
          f"configs={s['configs']} hlo_checked={s['hlo_checked']}")
    for v in report["violations"]:
        sev = v["severity"]
        print(f"  [{v['pass_name']}:{v['code']}] ({sev}) {v['where']}: "
              f"{v['detail']}")
    print(f"# errors={s['errors']} warnings/infos={s['warnings_infos']} "
          f"-> {args.out}")
    return 1 if s["errors"] else 0


if __name__ == "__main__":
    _args = _parse()
    if _args.hlo:
        _dmax = max(int(d) for d in str(_args.devices).split(",") if d)
        if _dmax > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={_dmax}"
            ).strip()
    sys.exit(main(sys.argv[1:]))
