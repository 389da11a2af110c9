"""Plain reference for PageRank, its lower-precision control, and the
comparison that decides ``correct``.

Imports nothing of the program. The configuration states ``num_iters``
pull iterations at ``damping`` from the uniform distribution, with the
mass of vertices that have no out-arcs handed back by the uniform teleport
every iteration; edge weights play no part. The reference runs that in
float64.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp

# Limits of the numbers compared; the readings they were set from are in
# PERF.md ("How correct is decided").
LIMITS = {"rank_rel_gap": 2e-4}


def _arcs(src, dst):
    return np.concatenate([src, dst]), np.concatenate([dst, src])


def reference(n: int, src, dst, w, root, params: dict) -> np.ndarray:
    a_src, a_dst = _arcs(src, dst)
    a = sp.csr_matrix((np.ones(a_src.size), (a_dst, a_src)), shape=(n, n))
    outdeg = np.bincount(a_src, minlength=n).astype(np.float64)
    sink = outdeg == 0
    damping = float(params["damping"])
    tele = np.full(n, 1.0 / n)
    r = tele.copy()
    for _ in range(int(params["num_iters"])):
        contrib = np.where(sink, 0.0, r / np.maximum(outdeg, 1.0))
        r = (1 - damping) * tele + damping * (a @ contrib + r[sink].sum() * tele)
    return r


def control(n: int, src, dst, w, root, params: dict) -> np.ndarray:
    """The reference computed in bfloat16 (the precision below the
    configuration's float32), on the default JAX device."""
    import jax
    import jax.numpy as jnp
    a_src, a_dst = _arcs(src, dst)
    bf = jnp.bfloat16
    damping = float(params["damping"])

    @jax.jit
    def solve(a_src, a_dst, outdeg):
        tele = jnp.full(n, 1.0 / n, bf)
        sink = outdeg == 0

        def body(_, r):
            contrib = jnp.where(sink, bf(0), r / jnp.maximum(outdeg, bf(1)))
            pull = jax.ops.segment_sum(contrib[a_src], a_dst, num_segments=n)
            mass = jnp.sum(jnp.where(sink, r, bf(0)))
            return ((1 - damping) * tele
                    + damping * (pull + mass * tele)).astype(bf)

        return jax.lax.fori_loop(0, int(params["num_iters"]), body, tele)

    outdeg = np.bincount(a_src, minlength=n)
    r = solve(jnp.asarray(a_src, jnp.int32), jnp.asarray(a_dst, jnp.int32),
              jnp.asarray(outdeg, bf))
    return np.asarray(r.astype(jnp.float32))


def compare(got: np.ndarray, want: np.ndarray) -> dict:
    """``rank_rel_gap``: the widest gap between a vertex's rank and the
    reference's, over the reference's rank (infinite where a rank is not
    a finite number)."""
    got = np.asarray(got, np.float64)
    if not np.isfinite(got).all():
        return {"rank_rel_gap": float("inf")}
    return {"rank_rel_gap": float((np.abs(got - want) / want).max())}
