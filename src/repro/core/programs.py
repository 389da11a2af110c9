"""Sub-graph centric programs — the user-facing Compute abstraction.

The paper's ``Compute(Subgraph, Iterator<Message>)`` runs an arbitrary
shared-memory algorithm over the sub-graph per superstep. The TPU-idiomatic
equivalent is a *local-fixpoint sweep*: a vectorized semiring relaxation
iterated until the partition's state quiesces (information provably cannot
cross sub-graph boundaries through local edges, so the fixpoint IS the
"traverse the whole sub-graph in one superstep" semantics of §3.2).

``max_local_iters`` selects the execution model:
    None -> run to local fixpoint  (sub-graph centric, Gopher)
    1    -> one sweep per superstep (vertex centric, the Giraph baseline)
    k    -> bounded local work      (beyond-paper straggler mitigation)

Programs expose:
    init(gb)                -> state pytree of (v_max,) leaves
    superstep(state, inbox, gb, step) -> (state, changed_scalar, local_iters)
    messages(state, gb)     -> (vals (r_max,), send_mask (r_max,))
    combine                 -> inbox ⊕: 'min' | 'max' | 'sum'
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.gofs.formats import PAD
from repro.kernels import ops


def _ew_combine(combine: str, a, b):
    return jnp.minimum(a, b) if combine == "min" else jnp.maximum(a, b)


@dataclasses.dataclass(frozen=True)
class SemiringProgram:
    """Idempotent-semiring fixpoint programs: CC, SSSP, BFS, MaxVertex.

    Frontier-driven (paper §4.2 VoteToHalt, done properly): the state carries
    an active-frontier mask seeded by ``init`` — all of ``vmask`` on a cold
    start, ``gb["frontier0"]`` on an incremental resume — and the local
    fixpoint is a *masked* sweep gated on it. A partition whose frontier is
    empty runs ZERO sweep iterations that superstep (its while-loop condition
    is false on entry) instead of recomputing everything to discover nothing
    changed; within an active partition, a lane whose source is settled
    contributes the ⊕-identity. For idempotent ⊕ the masked fixpoint is
    bitwise identical to the unmasked one.

    The frontier keeps an invariant: every vertex outside it has already
    relaxed all its local out-neighbours. Each seed meets it (all of
    ``vmask``; a resume's seed from algorithms.incremental.resume_seed),
    delivery adds every vertex the inbox lowered, and each sweep adds every
    vertex it changed. Both routes' sweeps rely on it: the fused route's
    (kernels.megastep.sweep_flat) and, on the jnp backend, the staged
    superstep's (:meth:`_masked_sweep`) gather only the frontier's values,
    ⊕-identity elsewhere, so a hand-made resume seed must cover the local
    in-neighbours of every vertex whose value it raised.

    ``resume=True`` starts from a previous fixpoint: ``gb["x0"]`` is the prior
    state and ``gb["frontier0"]`` the dirty seed set (see gofs.temporal /
    algorithms.incremental); both arrive via ``GopherEngine.run(extra=...)``.
    """
    semiring: str                       # min_plus | max_first
    init_fn: Optional[Callable] = None  # gb -> x0 (v_max,); unused when resume
    max_local_iters: Optional[int] = None
    spmv_backend: str = "jnp"           # "pallas" asks for the Pallas kernels
    interpret: bool = False             # ... in interpret mode (off the chip)
    fixpoint_unroll: int = 1            # sweeps fused per loop iteration (perf knob)
    resume: bool = False                # start from gb["x0"] / gb["frontier0"]

    @property
    def combine(self) -> str:
        return "min" if self.semiring == "min_plus" else "max"

    @property
    def megastep_kind(self) -> Optional[str]:
        """Gopher Hot eligibility: the fused megastep route replays the
        run-to-local-fixpoint schedule, so only the sub-graph centric mode
        (max_local_iters=None) qualifies — a bounded fixpoint's leftover
        frontier is already exact on the staged path and the fused loop
        would have to replicate its cap bookkeeping for no win."""
        return "semiring" if self.max_local_iters is None else None

    def init(self, gb) -> dict:
        # state: x — vertex values; changed_v — the send set (messages gate on
        # it); frontier — vertices whose local consequences are NOT yet
        # settled (the seed at step 0; afterwards only nonempty when a
        # bounded fixpoint hit max_local_iters mid-propagation)
        if self.resume:
            seed = gb["frontier0"] & gb["vmask"]
            return {"x": gb["x0"], "changed_v": seed, "frontier": seed}
        x0 = self.init_fn(gb)
        return {"x": x0, "changed_v": gb["vmask"], "frontier": gb["vmask"]}

    def _sweep(self, x, gb):
        y = ops.semiring_spmv(x, gb["nbr"], gb["wgt"], self.semiring,
                              backend=self.spmv_backend,
                              interpret=self.interpret)
        return _ew_combine(self.combine, x, y)

    def _masked_sweep(self, x, f, gb):
        """One frontier-masked relaxation; the next frontier is the rows
        that actually changed.

        On the ``jnp`` backend it gathers one array: the state with the
        ⊕-identity off the frontier, ``where(f, x, ident)``, reduced by the
        plain ELL sweep. Under the frontier invariant (class docstring) a
        lane whose source is settled could not move its row, so after the
        ⊕ with the state this equals the sweep that gathers the state and
        the frontier flags apart, bit for bit, with no flag gather. The
        ``pallas`` backend keeps ops.semiring_spmv_frontier, whose row-block
        skip reads the flags."""
        if self.spmv_backend == "pallas":
            y, _ = ops.semiring_spmv_frontier(x, f, gb["nbr"], gb["wgt"],
                                              self.semiring,
                                              backend=self.spmv_backend,
                                              interpret=self.interpret)
        else:
            ident = jnp.inf if self.combine == "min" else -jnp.inf
            y = ops.semiring_spmv(jnp.where(f, x, ident), gb["nbr"],
                                  gb["wgt"], self.semiring,
                                  backend=self.spmv_backend)
        x2 = _ew_combine(self.combine, x, y)
        return x2, (x2 != x) & gb["vmask"]

    def superstep(self, state, inbox, gb, step, axes=()):
        x0 = state["x"]
        vmask = gb["vmask"]
        x = _ew_combine(self.combine, x0, inbox)
        improved = (x != x0) & vmask        # vertices the mailbox moved
        # active set = carried frontier (the seed at step 0; leftover work
        # when a bounded fixpoint hit its cap) ∪ inbox improvements. A
        # quiesced partition enters the while loop with f0 empty and runs
        # ZERO sweeps this superstep.
        f0 = state["frontier"] | improved
        max_it = self.max_local_iters
        # the local fixpoint is the engine's gopher.sweep stage
        # (repro.obs.op_stages reads it back per instruction)
        with jax.named_scope("gopher.sweep"):
            if max_it == 1:
                # vertex-centric baseline (Giraph): one full sweep, unmasked
                x2 = self._sweep(x, gb)
                iters = jnp.int32(1)
                f_left = jnp.zeros_like(vmask)
            else:
                cap = jnp.int32(max_it if max_it is not None else 2**30)

                def cond(c):
                    _, f, it = c
                    return jnp.any(f) & (it < cap)

                def body(c):
                    xc, f, it = c
                    for _ in range(self.fixpoint_unroll):
                        xc, f = self._masked_sweep(xc, f, gb)
                    return xc, f, it + self.fixpoint_unroll

                x2, f_left, iters = jax.lax.while_loop(
                    cond, body, (x, f0, jnp.int32(0)))
        # the send set: vertices with news this superstep. The SEED frontier
        # needs no step-0 override here — the engine PRIMES the first inbox
        # from the init state's messages (gated on init's changed_v = seed),
        # so seed values, including incremental boundary announcements, were
        # already delivered before this superstep ran.
        changed_v = (x2 != x0) & vmask
        changed = jnp.any(changed_v)
        return {"x": x2, "changed_v": changed_v, "frontier": f_left}, \
            changed, iters

    def messages(self, state, gb):
        src = gb["re_src"]
        valid = src != PAD
        safe = jnp.where(valid, src, 0)
        xv = state["x"][safe]
        vals = xv + gb["re_wgt"] if self.semiring == "min_plus" else xv
        send = valid & state["changed_v"][safe]
        return vals, send


@dataclasses.dataclass(frozen=True)
class PageRankProgram:
    """Classic PageRank (paper §5.3): one Jacobi iteration per superstep,
    fixed ``num_iters`` supersteps (the paper runs 30), pull formulation.
    Remote in-edges deliver contributions through the mailbox (⊕ = sum).

    Dangling vertices (global out-degree 0) cannot forward rank through
    edges; their mass is redistributed by the teleport distribution every
    iteration — the standard G = d(A + dangling·teleᵀ) + (1-d)·1·teleᵀ
    formulation — so ranks sum to 1 on graphs with sinks. The dangling mass
    and the ``tol`` halt criterion are GLOBAL sums: ``axes`` names the
    collective axes the engine runs this program under (the vmap partition
    axis, plus the mesh axis on shard_map), so every partition sees the same
    totals and the early-halt decision is graph-wide, not per-partition.
    """
    n_global: int
    num_iters: int = 30
    damping: float = 0.85
    tol: Optional[float] = None         # if set, halt early on GLOBAL L1 delta
    spmv_backend: str = "jnp"           # "pallas" asks for the Pallas kernel
    init_fn: Optional[Callable] = None  # gb -> r0 (BlockRank seeds phase 3 with this)
    teleport_fn: Optional[Callable] = None  # gb -> (v_max,) personalization
                                            # distribution; uniform when None

    combine = "sum"

    @property
    def megastep_kind(self) -> Optional[str]:
        """Fused-route eligibility: only the fixed-iteration schedule. With
        ``tol`` set the halt compares a GLOBAL float sum against a
        threshold, and the fused route's flat ⊕=sum association could flip
        that comparison on the margin — the staged and fused runs would
        disagree on the STEP COUNT, not just low-order bits."""
        return "pagerank" if self.tol is None else None

    def init(self, gb) -> dict:
        vmask = gb["vmask"]
        if self.init_fn is not None:
            r0 = jnp.where(vmask, self.init_fn(gb), 0.0)
        else:
            r0 = jnp.where(vmask, 1.0 / self.n_global, 0.0)
        return {"r": r0, "delta": jnp.float32(jnp.inf)}

    def _contrib(self, r, gb):
        deg = gb["out_degree"].astype(jnp.float32)
        return jnp.where(deg > 0, r / jnp.maximum(deg, 1.0), 0.0)

    def superstep(self, state, inbox, gb, step, axes=()):
        vmask = gb["vmask"]
        r = state["r"]
        ones = jnp.ones_like(gb["wgt"])
        with jax.named_scope("gopher.sweep"):
            pull = ops.semiring_spmv(self._contrib(r, gb), gb["nbr"], ones,
                                     "plus_times", backend=self.spmv_backend)
        tele = (self.teleport_fn(gb) if self.teleport_fn is not None
                else 1.0 / self.n_global)
        dangling = jnp.sum(jnp.where(vmask & (gb["out_degree"] == 0), r, 0.0))
        if axes:
            dangling = jax.lax.psum(dangling, axes)
        r_new = jnp.where(
            vmask,
            (1.0 - self.damping) * tele
            + self.damping * (pull + inbox + dangling * tele), 0.0)
        delta = jnp.sum(jnp.abs(r_new - r))
        if axes:
            delta = jax.lax.psum(delta, axes)
        if self.tol is not None:
            changed = (delta > self.tol) & (step + 1 < self.num_iters)
        else:
            changed = step + 1 < self.num_iters
        return {"r": r_new, "delta": delta}, changed, jnp.int32(1)

    def messages(self, state, gb):
        src = gb["re_src"]
        valid = src != PAD
        safe = jnp.where(valid, src, 0)
        vals = self._contrib(state["r"], gb)[safe]
        return vals, valid


# ---------------- init helpers ----------------

def init_max_vertex(gb):
    """MaxVertex / CC seed: each vertex starts at its own global id (paper's
    HCC: propagate the largest vertex id)."""
    return jnp.where(gb["vmask"], gb["global_id"].astype(jnp.float32), -jnp.inf)


def make_sssp_init(source_part: int, source_local: int):
    def init(gb):
        x = jnp.where(gb["vmask"], jnp.inf, jnp.inf)
        is_here = gb["part_index"] == source_part
        x = x.at[source_local].set(jnp.where(is_here, 0.0, jnp.inf))
        return x
    return init


def make_bfs_init(source_part: int, source_local: int):
    return make_sssp_init(source_part, source_local)  # BFS = SSSP with unit wgt
