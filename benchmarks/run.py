"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
    fig4a_*   makespan, Gopher vs vertex-centric (paper Fig 4a)
    fig4b_*   load time, GoFS vs monolithic (paper Fig 4b)
    fig4c_*   superstep counts + diameter correlation (paper Fig 4c, §6.3)
    fig5_*    straggler/skew distribution + partitioner fix (paper Fig 5, §7)
    blockrank_* BlockRank vs classic PageRank supersteps (paper §5.3)
    serving_* batched multi-query serving QPS vs sequential (Gopher Serve)
    incremental_* delta restart vs full recompute (Gopher Delta)
    comm_*    exchange volume, compact vs dense mailbox (Gopher Wire)
    obs_*     tracing artifacts valid + disabled-tracing overhead (Gopher
              Scope)

Every emitted row is also recorded to BENCH_paper_suite.json at the repo
root (plus BENCH_incremental.json / BENCH_comm.json from the incremental
and comm benches) so the perf trajectory is machine-readable across PRs.
"""
from __future__ import annotations

import sys


def _blockrank():
    from benchmarks.common import emit, get_pg, timed
    from repro.algorithms import blockrank, pagerank
    g, pg = get_pg("RN")
    (r1, t1), dt1 = timed(lambda: pagerank(pg, num_iters=60, tol=1e-7))
    (r2, t2, info), dt2 = timed(lambda: blockrank(pg, tol=1e-7, max_iters=60))
    emit("blockrank_classic_RN", dt1, f"supersteps={t1.supersteps}")
    emit("blockrank_seeded_RN", dt2,
         f"supersteps={t2.supersteps};blocks={info['num_meta']}")


def main() -> None:
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_comm, bench_goffish_vs_vertex,
                            bench_incremental, bench_loading, bench_obs,
                            bench_serving, bench_straggler, bench_supersteps)
    from benchmarks.common import write_bench_json
    print("name,us_per_call,derived")
    bench_goffish_vs_vertex.run()
    bench_loading.run()
    bench_supersteps.run()
    bench_straggler.run()
    _blockrank()
    bench_serving.run()
    bench_incremental.run()
    bench_comm.run()
    bench_obs.run()
    print(f"# wrote {write_bench_json('paper_suite')}", file=sys.stderr)


if __name__ == "__main__":
    main()
