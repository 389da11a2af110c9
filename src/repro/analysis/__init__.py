"""Gopher Sentinel — static analysis over the engine's riskiest constructs.

Three passes (see each module's docstring for the invariants):

- :mod:`repro.analysis.collectives` — Pass 1, the SPMD collective
  verifier: cond-branch collective agreement (or proven-replicated
  predicates), mesh axis binding, trace-time-constant tier plans.
- :mod:`repro.analysis.semiring` — Pass 2, the semiring law checker:
  ⊕/⊗ laws the sweep and the dense-retry exactness claim assume.
- :mod:`repro.analysis.kernel_lint` — Pass 3, the Pallas kernel linter:
  grid divisibility, store masking, ±inf-safe selects, aliasing races.

``GopherEngine(..., validate=True)`` runs Passes 1–2 on every compiled-loop
cache MISS (a hit means an identical configuration already passed);
``python -m repro.launch.sentinel`` runs the whole matrix plus Pass 3 and
the HLO cross-check in CI.
"""
from repro.analysis.collectives import (
    HLO_KIND,
    CollectiveOp,
    CollectiveSummary,
    CondReport,
    check_plan_static,
    trace_loop,
    verify_collectives,
    verify_jaxpr,
)
from repro.analysis.kernel_lint import (
    lint_kernel_file,
    lint_kernels,
    lint_source,
)
from repro.analysis.report import (
    ERROR,
    INFO,
    WARNING,
    SentinelError,
    Violation,
    assert_clean,
    errors,
    split_severity,
)
from repro.analysis.semiring import (
    REGISTRY,
    SemiringSpec,
    check_program,
    check_semiring,
    probe_laws,
)

__all__ = [
    "ERROR", "INFO", "WARNING", "HLO_KIND", "REGISTRY",
    "CollectiveOp", "CollectiveSummary", "CondReport", "SemiringSpec",
    "SentinelError", "Violation",
    "assert_clean", "check_plan_static", "check_program", "check_semiring",
    "errors", "lint_kernel_file", "lint_kernels", "lint_source",
    "probe_laws", "split_severity", "trace_loop", "validate_engine",
    "validate_service", "validate_stage_fns",
    "verify_collectives", "verify_jaxpr",
]


def validate_engine(engine, num_queries=None, gb_example=None):
    """Passes 1–2 for one engine configuration: collective verification
    over the exact loop about to be compiled, plan staticness, and the
    program's semiring laws. Raises :class:`SentinelError` naming every
    offending equation/field/law on error-severity findings; returns the
    full violation list (incl. warnings/infos) when clean."""
    violations = list(check_program(engine.program, engine.exchange))
    summary, vs = verify_collectives(engine, num_queries=num_queries,
                                     gb_example=gb_example)
    violations += vs
    assert_clean(violations)
    return violations


def validate_stage_fns(engine, num_queries=None, gb_example=None):
    """Pass 1 over the STAGED STEPPED DRIVER's stage programs — the
    init/sweep/pack/route jits a checkpointed or recovery run dispatches
    per superstep, under the engine's ``stepped_exchange(checkpointed=
    True)`` (Gopher Shield replays ride these, so their collectives must
    verify exactly like the fused loops'). Each
    stage is traced with shape-only inputs chained through ``eval_shape``
    (state from init, payload from pack, inbox from route) and walked by
    the Pass 1 verifier. Raises :class:`SentinelError` on error-severity
    findings; returns ({stage: CollectiveSummary}, [Violation])."""
    import jax
    import jax.numpy as jnp
    from repro.core.blocks import graph_block
    if gb_example is not None:
        gb_shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                     for k, v in gb_example.items()}
    else:
        gb_shapes = graph_block(engine.pg, as_spec=True)
    fns = engine.stage_fns(num_queries, None,
                           engine.stepped_exchange(checkpointed=True))
    state_s = jax.eval_shape(fns["init"], gb_shapes)
    pack_s = jax.eval_shape(fns["pack"], gb_shapes, state_s)
    payload_s = pack_s[0]
    inbox_s, _ = jax.eval_shape(fns["route"], gb_shapes, payload_s)
    step_s = jax.ShapeDtypeStruct((), jnp.int32)
    mesh_axes = (dict(engine.mesh.shape)
                 if engine.backend == "shard_map" and engine.mesh is not None
                 else {})
    violations = list(check_plan_static(getattr(engine, "tier_plan", None)))
    summaries = {}
    for name, jaxpr in (
            ("init", jax.make_jaxpr(fns["init"])(gb_shapes)),
            ("sweep", jax.make_jaxpr(fns["sweep"])(
                gb_shapes, state_s, inbox_s, step_s)),
            ("pack", jax.make_jaxpr(fns["pack"])(gb_shapes, state_s)),
            ("route", jax.make_jaxpr(fns["route"])(gb_shapes, payload_s))):
        summary, vs = verify_jaxpr(jaxpr, mesh_axes=mesh_axes)
        summaries[name] = summary
        violations += vs
    assert_clean(violations)
    return summaries, violations


def validate_service(svc, graphs=None, families=("reach",), qs=(1,),
                     stage_fns: bool = True):
    """Sentinel over a GraphQueryService's pooled BATCHED serving loops:
    for every (graph, family, Q-bucket) the exact query-batched engine
    configuration ``drain()`` would dispatch is validated (collective
    agreement, plan staticness, semiring laws), with the real query-array
    entries (``qseed``/``qinit``) in the traced block so the jaxpr matches
    the serving shapes bit-for-bit. With ``stage_fns=True`` the staged
    stepped driver each engine's recovery replay would use is verified
    too. Raises :class:`SentinelError` on any error-severity finding;
    returns {(graph, family, Q): [Violation]}."""
    import jax.numpy as jnp
    from repro.serving.batched import (ppr_query_seed,
                                       reachability_query_init)
    out = {}
    for name in (sorted(svc.graphs) if graphs is None else graphs):
        pg = svc.graphs[name]
        for family in families:
            for Q in qs:
                eng = svc._engine(name, family, Q)
                gb = dict(svc._graph_block(name))
                if family == "ppr":
                    gb["qseed"] = jnp.asarray(ppr_query_seed(pg, [0] * Q))
                else:
                    gb["qinit"] = jnp.asarray(
                        reachability_query_init(pg, [[0]] * Q))
                vs = validate_engine(eng, num_queries=Q, gb_example=gb)
                if stage_fns:
                    _, svs = validate_stage_fns(eng, num_queries=Q,
                                                gb_example=gb)
                    vs = vs + svs
                out[(name, family, Q)] = vs
    return out
