"""Gopher Scope CLI: trace a BSP run and render the observability report.

    PYTHONPATH=src python -m repro.launch.scope [--algo cc|sssp] \
        [--rows 40 --cols 40] [--parts 4] [--exchange auto|dense|compact| \
        tiered|phased] [--backend local|shard_map] [--devices 4] \
        [--boundary-sync] [--profile-dir DIR] [--out DIR]

Self-contained demo: builds a road-grid graph, runs CC or SSSP with the
Gopher Scope tracer enabled, then

  * prints the TEXT TIMELINE — the nested run -> phase -> superstep ->
    {plan, pack, exchange, sweep, halt-vote} spans with wall-clock;
  * prints the metrics snapshot (engine counters, tier-plan builds,
    profile drift) and the per-partition skew report;
  * writes scope_trace.json (load in Perfetto / chrome://tracing),
    scope_trace.jsonl and scope_metrics.json into --out.

``--backend shard_map`` forces ``--devices`` host devices via XLA_FLAGS,
so it must take effect before jax initializes — this module therefore
parses argv at import time when run as __main__.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _parse(argv=None):
    ap = argparse.ArgumentParser(description="Gopher Scope trace report")
    ap.add_argument("--algo", choices=("cc", "sssp"), default="cc")
    ap.add_argument("--rows", type=int, default=40)
    ap.add_argument("--cols", type=int, default=40)
    ap.add_argument("--parts", type=int, default=4)
    ap.add_argument("--backend", choices=("local", "shard_map"),
                    default="local")
    ap.add_argument("--devices", type=int, default=4)
    ap.add_argument("--exchange", default="auto",
                    choices=("auto", "dense", "compact", "tiered", "phased"))
    ap.add_argument("--boundary-sync", action="store_true",
                    help="block_until_ready per stage: honest per-stage "
                         "wall-clock instead of dispatch time")
    ap.add_argument("--profile-dir", default=None,
                    help="also capture a device-side jax.profiler trace")
    ap.add_argument("--out", default=".",
                    help="directory for scope_trace.json[l] + "
                         "scope_metrics.json")
    return ap.parse_args(argv)


def text_timeline(tracer, file=None) -> None:
    """Indented span tree with wall-clock — the terminal half of the
    Perfetto file."""
    file = file or sys.stdout
    show = ("supersteps", "wire_slots", "step", "phase", "nchanged",
            "spills", "dispatches")
    for s in sorted(tracer.spans, key=lambda s: (s.t0_ns, -s.dur_ns)):
        args = " ".join(f"{k}={s.args[k]}" for k in show if k in s.args)
        print(f"{'  ' * s.depth}{s.name:<{24 - 2 * min(s.depth, 8)}} "
              f"{s.dur_ns / 1e6:9.3f} ms  {args}", file=file)


def _build(args):
    from repro.core import (GopherEngine, PhasedTierPlan, SemiringProgram,
                            init_max_vertex, make_sssp_init)
    from repro.core import compat
    from repro.gofs import bfs_grow_partition, road_grid
    from repro.gofs.formats import partition_graph
    from repro.obs import Tracer

    g = road_grid(args.rows, args.cols, seed=1)
    pg = partition_graph(g, bfs_grow_partition(g, args.parts, seed=0),
                         args.parts)
    if args.algo == "cc":
        prog = SemiringProgram(semiring="max_first", init_fn=init_max_vertex)
    else:
        prog = SemiringProgram(
            semiring="min_plus",
            init_fn=make_sssp_init(int(pg.part_of[0]), int(pg.local_of[0])))
    mesh = None
    if args.backend == "shard_map":
        mesh = compat.make_mesh((args.devices,), ("parts",))
    plan = (PhasedTierPlan.from_graph(pg)
            if args.exchange == "phased" else None)
    tracer = Tracer(enabled=True, boundary_sync=args.boundary_sync,
                    jax_profiler_dir=args.profile_dir)
    eng = GopherEngine(pg, prog, backend=args.backend, mesh=mesh,
                       exchange=args.exchange, tier_plan=plan, tracer=tracer)
    return eng, tracer


def main(argv=None) -> None:
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    args = _parse(argv)
    eng, tracer = _build(args)
    state, tele = eng.run()
    from repro.obs import metrics as obs_metrics

    print(f"# gopher scope — {args.algo} on {args.rows}x{args.cols} road "
          f"grid, {args.parts} parts, backend={args.backend} "
          f"exchange={eng.exchange}")
    print(f"# supersteps={tele.supersteps} wire_slots={tele.wire_slots} "
          f"messages={tele.messages_sent}\n")
    text_timeline(tracer)
    print("\n# skew")
    print(json.dumps(tele.skew(), indent=1))
    print("\n# metrics")
    snap = obs_metrics.default_registry().snapshot()
    print(json.dumps(snap, indent=1))

    os.makedirs(args.out, exist_ok=True)
    tp = tracer.write_chrome_trace(os.path.join(args.out, "scope_trace.json"))
    lp = tracer.write_jsonl(os.path.join(args.out, "scope_trace.jsonl"))
    mp = obs_metrics.default_registry().write_json(
        os.path.join(args.out, "scope_metrics.json"))
    print(f"\n# wrote {tp}  {lp}  {mp}", file=sys.stderr)


if __name__ == "__main__":
    _args = _parse()
    if _args.backend == "shard_map":
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={_args.devices}"
        ).strip()
    main(sys.argv[1:])
