"""Plain reference for single-source shortest paths, its lower-precision
control, and the comparison that decides ``correct``.

Imports nothing of the program. The configuration states float32
distances that are the exact fixpoint of min-plus relaxation, so the
reference relaxes every arc in float32 until nothing changes: the fixpoint
of ``d[v] = min(d[v], d[u] + w)`` with each sum rounded to float32 is the
least, over all paths, of the path's float32 running sum, whatever order
the relaxations take. A program that reaches that fixpoint matches it to
the bit.
"""
from __future__ import annotations

import numpy as np

# Limits of the numbers compared; the readings they were set from are in
# PERF.md ("How correct is decided").
LIMITS = {"reach_mismatch": 0, "dist_rel_gap": 1e-5}


def _arcs(src, dst, w):
    return (np.concatenate([src, dst]), np.concatenate([dst, src]),
            np.concatenate([w, w]).astype(np.float32))


def reference(n: int, src, dst, w, root: int, params: dict) -> np.ndarray:
    """float32 Bellman-Ford from ``root`` over the undirected edges,
    relaxing only the arcs whose tail improved in the last round."""
    a_src, a_dst, a_w = _arcs(src, dst, w)
    d = np.full(n, np.inf, np.float32)
    d[root] = 0.0
    active = np.zeros(n, bool)
    active[root] = True
    while active.any():
        sel = active[a_src]
        cand = d[a_src[sel]] + a_w[sel]
        best = np.full(n, np.inf, np.float32)
        np.minimum.at(best, a_dst[sel], cand)
        active = best < d
        d[active] = best[active]
    return d


def control(n: int, src, dst, w, root: int, params: dict) -> np.ndarray:
    """The reference computed in bfloat16 (the precision below the
    configuration's float32), on the default JAX device."""
    import jax
    import jax.numpy as jnp
    a_src, a_dst, a_w = _arcs(src, dst, w)

    @jax.jit
    def solve(a_src, a_dst, a_w):
        d0 = jnp.full(n, jnp.inf, jnp.bfloat16).at[root].set(0)

        def body(carry):
            d, _ = carry
            best = jax.ops.segment_min(d[a_src] + a_w, a_dst, num_segments=n)
            nd = jnp.minimum(d, best)
            return nd, jnp.any(nd < d)

        return jax.lax.while_loop(lambda c: c[1], body, (d0, True))[0]

    d = solve(jnp.asarray(a_src, jnp.int32), jnp.asarray(a_dst, jnp.int32),
              jnp.asarray(a_w, jnp.bfloat16))
    return np.asarray(d.astype(jnp.float32))


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    """``reach_mismatch``: vertices reached on one side only (a NaN counts
    as unreached). ``dist_rel_gap``: the widest gap between the two
    distances of a vertex both reach, over the reference distance (over
    the smallest normal float32 at the root, whose distance is 0)."""
    got = np.asarray(got)
    fin_g, fin_w = np.isfinite(got), np.isfinite(want)
    both = fin_g & fin_w
    gap = (np.abs(got[both].astype(np.float64) - want[both])
           / np.maximum(want[both].astype(np.float64),
                        np.finfo(np.float32).tiny))
    return {"reach_mismatch": int((fin_g != fin_w).sum()),
            "dist_rel_gap": float(gap.max()) if gap.size else 0.0}
