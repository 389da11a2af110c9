#!/usr/bin/env python3
"""Bring-up smoke run of the Gopher engine and the query service on a TPU.

    python chip_smoke.py [--seed N] [--side 512] [--four-chips]

One process, one chip (``--four-chips``: one host with four). It refuses to
run anywhere but a TPU: with no TPU it exits non-zero and prints no result.

Phases, each checked against a plain reference of the same semantics and
each fatal on failure:

  build    ``road_grid(side, side, seed)``, 16 BFS-grown partitions, the GoFS
           build; prints vertices, edges, host graph-block bytes, seconds.
  engine   ``connected_components``, ``sssp`` from a seeded source and
           ``pagerank`` through ``repro.algorithms`` with ``exchange='auto'``;
           CC and SSSP must equal scipy exactly, PageRank must match a numpy
           power iteration (same damping and dangling-mass rule) to
           ``PR_RTOL``/``PR_ATOL``.
  serving  ``GraphQueryService`` over the same graph: warm, then one drain of
           sssp/bfs/ppr queries in batches of at most 8; every response must
           be non-degraded and match the reference.
  mesh     (``--four-chips`` only, and then the only phase after build) CC
           and SSSP on a ``("parts", 4)`` mesh with ``exchange='auto'``
           (tiered) and ``'phased'``, each equal to ``'dense'`` on the same
           mesh and to scipy; prints per-device bytes in use.

The last line of stdout is the JSON verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.cache import use_compile_cache  # noqa: E402

CACHE_DIR = use_compile_cache()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import scipy.sparse.csgraph as csgraph  # noqa: E402

PARTS = 16
DAMPING = 0.85
PR_ITERS = 30
PR_RTOL, PR_ATOL = 1e-4, 1e-10
SERVE_BATCH = 8


class CompileClock:
    """Sums JAX's own compile events: the XLA compile (or its load from the
    persistent cache), one event per executable, and tracing + lowering
    (whose spans may nest, so ``trace_s`` can overcount)."""

    TRACE = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")
    XLA = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.trace_s = 0.0
        self.xla_s = 0.0
        self.xla_compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.TRACE:
            self.trace_s += secs
        elif event == self.XLA:
            self.xla_s += secs
            self.xla_compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snap(self):
        return (self.trace_s, self.xla_s, self.xla_compiles, self.cache_hits)


CLOCK = None


def timed(fn):
    """Run fn and wait for its device work; return (result, wall seconds,
    compile-clock deltas). ``run_s`` is the wall time less XLA
    compilation: host block build, upload, tracing, device run and
    download."""
    before = CLOCK.snap()
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    wall = time.perf_counter() - t0
    d = [a - b for a, b in zip(CLOCK.snap(), before)]
    return out, wall, dict(wall_s=wall, compile_s=d[1], run_s=wall - d[1],
                           trace_s=d[0], xla_compiles=d[2], cache_hits=d[3])


def require(cond, what):
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def say(tag, **kw):
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kw.items()),
          flush=True)


def gather(pg, per_part):
    """(P, v_max[, ...]) per-partition values -> global vertex order."""
    per_part = np.asarray(per_part)
    out = np.zeros((pg.n_global,) + per_part.shape[2:], per_part.dtype)
    for p in range(pg.num_parts):
        m = pg.vmask[p]
        out[pg.global_id[p][m]] = per_part[p][m]
    return out


# ---------------- plain references ----------------

def ref_components(g):
    """scipy WCC, relabelled to the engine's rule: max vertex id per
    component."""
    ncc, lab = csgraph.connected_components(g.undirected_csr(),
                                            directed=False)
    top = np.full(ncc, -1, np.int64)
    np.maximum.at(top, lab, np.arange(g.n, dtype=np.int64))
    return ncc, lab, top[lab]


def ref_hops(g, sources):
    """Unit-weight shortest paths (BFS) from each source, float32."""
    d = csgraph.shortest_path(g.csr().T, unweighted=True, indices=sources)
    return d.astype(np.float32)


def ref_pagerank(g, teleport, iters=PR_ITERS, damping=DAMPING):
    """float64 pull power iteration; dangling mass is redistributed by the
    teleport distribution (columns of ``teleport`` are independent runs)."""
    a = g.csr()
    outdeg = g.out_degree.astype(np.float64)[:, None]
    sink = (g.out_degree == 0)
    r = teleport.copy()
    for _ in range(iters):
        contrib = np.where(outdeg > 0, r / np.maximum(outdeg, 1), 0.0)
        mass = r[sink].sum(axis=0, keepdims=True)
        r = (1 - damping) * teleport + damping * (a @ contrib
                                                  + mass * teleport)
    return r


# ---------------- phases ----------------

def build(side, seed):
    from repro.core import host_graph_block
    from repro.gofs import bfs_grow_partition, road_grid
    from repro.gofs.formats import partition_graph
    t0 = time.perf_counter()
    g = road_grid(side, side, seed=seed)
    pg = partition_graph(g, bfs_grow_partition(g, PARTS, seed=seed), PARTS)
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    host = host_graph_block(pg)
    block_s = time.perf_counter() - t0
    say("build", vertices=g.n, edges=g.nnz, parts=PARTS, v_max=pg.v_max,
        d_max=pg.d_max, mailbox_cap=pg.mailbox_cap,
        host_block_bytes=sum(v.nbytes for v in host.values()),
        build_s=f"{build_s:.3f}", host_block_s=f"{block_s:.3f}")
    return g, pg


def engine_phase(g, pg, rng, ncc_true, labels_true, comp_of):
    from repro.algorithms import connected_components, pagerank, sssp
    (labels, ncc, tele), _, t = timed(lambda: connected_components(pg))
    ours = gather(pg, labels)
    require(ncc == ncc_true, f"CC count {ncc} != scipy {ncc_true}")
    require(np.array_equal(ours, labels_true), "CC labels != scipy")
    say("engine.cc", exchange=tele.exchange, supersteps=tele.supersteps,
        **_sweeps(tele), components=ncc, match="exact", **_fmt(t))

    big = np.bincount(comp_of).argmax()
    src = int(rng.choice(np.flatnonzero(comp_of == big)))
    (dist, tele), _, t = timed(lambda: sssp(pg, src))
    want = ref_hops(g, [src])[0]
    require(np.array_equal(gather(pg, dist), want), "SSSP != scipy")
    say("engine.sssp", source=src, exchange=tele.exchange,
        supersteps=tele.supersteps, **_sweeps(tele),
        reached=int(np.isfinite(want).sum()),
        match="exact", **_fmt(t))

    (r, tele), _, t = timed(lambda: pagerank(pg, num_iters=PR_ITERS,
                                             damping=DAMPING))
    ours = gather(pg, r)
    want = ref_pagerank(g, np.full((g.n, 1), 1.0 / g.n))[:, 0]
    err = np.abs(ours - want)
    require(np.allclose(ours, want, rtol=PR_RTOL, atol=PR_ATOL),
            f"PageRank max abs err {err.max():.3e} beyond "
            f"rtol={PR_RTOL} atol={PR_ATOL}")
    say("engine.pagerank", exchange=tele.exchange, supersteps=tele.supersteps,
        **_sweeps(tele), max_abs_err=f"{err.max():.3e}", rtol=PR_RTOL,
        atol=PR_ATOL, **_fmt(t))
    say("engine.memory", device_kind=jax.devices()[0].device_kind,
        peak_bytes_in_use=_peak())


def serving_phase(g, pg, rng, comp_of):
    from repro.serving import GraphQueryService
    svc = GraphQueryService({"road": pg}, max_batch=SERVE_BATCH)
    n_warm, _, t = timed(lambda: svc.warm("road",
                                          families=("traversal", "ppr"),
                                          qs=(SERVE_BATCH,)))
    say("serving.warm", loops=n_warm, **_fmt(t))

    big = np.bincount(comp_of).argmax()
    pool = np.flatnonzero(comp_of == big)
    trav = [int(s) for s in rng.choice(pool, SERVE_BATCH, replace=False)]
    ppr = [int(s) for s in rng.choice(pool, SERVE_BATCH, replace=False)]
    kinds = ["sssp", "bfs"] * (SERVE_BATCH // 2)
    tickets = {svc.submit(k, "road", s): (k, s) for k, s in zip(kinds, trav)}
    tickets.update({svc.submit("ppr", "road", s): ("ppr", s) for s in ppr})
    responses, _, t = timed(svc.drain)
    say("serving.drain", queries=len(tickets), **_fmt(t))

    hops = dict(zip(trav, ref_hops(g, trav)))
    tele = np.zeros((g.n, len(ppr)))
    tele[ppr, np.arange(len(ppr))] = 1.0
    pr = ref_pagerank(g, tele)
    worst = 0.0
    for ticket, (kind, s) in tickets.items():
        resp = responses[ticket]
        require(resp.error is None, f"{kind}({s}) answered {resp.error!r}")
        require(resp.result is not None, f"{kind}({s}) has no result")
        if kind == "ppr":
            want = pr[:, ppr.index(s)]
            require(np.allclose(resp.result, want, rtol=PR_RTOL,
                                atol=PR_ATOL), f"ppr({s}) != reference")
            worst = max(worst, float(np.abs(resp.result - want).max()))
        else:
            # the road grid is unit-weight: sssp and bfs are both hop counts
            require(np.array_equal(resp.result, hops[s]),
                    f"{kind}({s}) != scipy")
    say("serving.check", traversal="exact", ppr_max_abs_err=f"{worst:.3e}",
        rtol=PR_RTOL, atol=PR_ATOL)
    say("serving.stats", **svc.stats.summary())


def mesh_phase(g, pg, rng, labels_true, comp_of):
    from repro.core import (GopherEngine, SemiringProgram, compat,
                            init_max_vertex, make_sssp_init)
    devs = jax.devices()[:4]
    mesh = compat.make_mesh((4,), ("parts",), devices=devs)
    big = np.bincount(comp_of).argmax()
    src = int(rng.choice(np.flatnonzero(comp_of == big)))
    progs = {
        "cc": (SemiringProgram(semiring="max_first",
                               init_fn=init_max_vertex), labels_true),
        "sssp": (SemiringProgram(semiring="min_plus", init_fn=make_sssp_init(
            int(pg.part_of[src]), int(pg.local_of[src]))),
            ref_hops(g, [src])[0]),
    }
    for name, (prog, want) in progs.items():
        results = {}
        for mode in ("dense", "auto", "phased"):
            eng = GopherEngine(pg, prog, backend="shard_map", mesh=mesh,
                               exchange=mode)
            (state, tele), _, t = timed(eng.run)
            x = gather(pg, state["x"])
            if name == "cc":
                x = x.astype(np.int64)
            results[mode] = x
            require(np.array_equal(x, want), f"{name}/{mode} != scipy")
            require(np.array_equal(x, results["dense"]),
                    f"{name}/{mode} != dense")
            used = bytes_in_use(devs)
            say(f"mesh.{name}", requested=mode, exchange=tele.exchange,
                supersteps=tele.supersteps, **_sweeps(tele),
                spills=tele.spills,
                match="scipy+dense", bytes_in_use=used, **_fmt(t))
            require(all(u > 0 for u in used), "a mesh device holds nothing")
            del eng, state


def _sweeps(tele):
    """Local-fixpoint sweeps of a run (``Telemetry.local_iters``): summed
    over partitions, and the most any one partition ran."""
    li = np.asarray(tele.local_iters)
    return dict(sweeps_total=int(li.sum()), sweeps_max_part=int(li.max()))


def bytes_in_use(devs):
    return [d.memory_stats()["bytes_in_use"] for d in devs]


def _peak():
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


def _fmt(t):
    return {k: (f"{v:.3f}" if isinstance(v, float) else v)
            for k, v in t.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--side", type=int, default=512)
    ap.add_argument("--four-chips", action="store_true")
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX sees {devs[0].platform}); refusing "
              "to run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devs)}",
              file=sys.stderr)
        return 2
    global CLOCK
    CLOCK = CompileClock()
    say("device", platform=devs[0].platform, kind=devs[0].device_kind,
        count=len(devs), jax=jax.__version__, compile_cache=CACHE_DIR)

    rng = np.random.default_rng(args.seed)
    t_all = time.perf_counter()
    g, pg = build(args.side, args.seed)
    ncc_true, comp_of, labels_true = ref_components(g)
    if args.four_chips:
        mesh_phase(g, pg, rng, labels_true, comp_of)
    else:
        engine_phase(g, pg, rng, ncc_true, labels_true, comp_of)
        serving_phase(g, pg, rng, comp_of)
    say("done", total_s=f"{time.perf_counter() - t_all:.3f}",
        peak_bytes_in_use=_peak(),
        compile_s=f"{CLOCK.xla_s:.3f}", trace_s=f"{CLOCK.trace_s:.3f}",
        xla_compiles=CLOCK.xla_compiles, cache_hits=CLOCK.cache_hits)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
