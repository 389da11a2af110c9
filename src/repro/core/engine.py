"""Gopher: the sub-graph centric BSP execution engine.

Faithful mapping of the paper's §4.2 runtime onto SPMD JAX:

  paper                               here
  -----                               ----
  worker per machine                  mesh device along the 'parts' axis
  thread pool over sub-graphs         vectorized (vmap) partitions + the
                                      local-fixpoint sweep (programs.py)
  async TCP message flush             all_to_all mailbox at superstep boundary
                                      (XLA overlaps it with the sweep tail)
  manager sync/resume/terminate       psum of per-partition 'changed' flags
                                      inside a lax.while_loop — the manager
                                      degenerates to an all-reduce
  VoteToHalt + no input messages      changed == False (see programs.py for
                                      why this is equivalent for idempotent ⊕)

Two backends share every line of superstep logic:
  'local'     — all P partitions as a (P, ...) batch on one device (CPU tests,
                virtual partitions)
  'shard_map' — partitions sharded over a mesh axis; mailbox routed with a
                real all_to_all; halt via psum (multi-chip / dry-run path)

Six wire disciplines share both backends (``exchange=``, see make_exchange):
  'dense'     every pair ships its full cap row (the parity oracle; also the
              baseline where the physical wire is a single-host transpose)
  'compact'   frontier-compacted protocol payload over the dense physical
              buffer (Gopher Wire)
  'tiered'    capacity-tiered PHYSICAL buffers routed per pair tier (Gopher
              Mesh): the geometry XLA moves tracks the frontier
  'phased'    frontier-PHASED tier schedules (Gopher Phases): one segmented
              BSP loop per frontier band, so a single run's geometry rides
              the contraction — wide early rounds, narrow converged tail
  'megastep'  Gopher Hot (local backend only): the whole superstep — mailbox
              delivery, inbox combine, masked local fixpoint, halt
              reduction — fused into ONE dispatch over flat state
              (kernels.megastep); with a PhasedTierPlan whose narrow bands
              fit VMEM, multiple supersteps run resident inside one launch
  'auto'      the default: 'megastep' on 'local' when the program is
              eligible (program.megastep_kind is not None — the sub-graph
              centric fixpoint schedule, or fixed-iteration PageRank),
              'dense' otherwise on 'local' and on a 1-device shard_map mesh
              (where the "wire" is the same single-host transpose),
              'tiered' on a multi-device 'shard_map' mesh
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import compat
from repro.core import messages as msg
from repro.resilience import faults as _faults
from repro.core.blocks import (device_block, graph_block,  # noqa: F401
                               host_graph_block)
from repro.core.tiers import DEMOTE_STREAK, PhasedTierPlan, TierPlan
from repro.gofs.formats import PartitionedGraph
from repro.kernels import megastep as mega
from repro.kernels import ops
from repro.obs import metrics as obs_metrics
from repro.obs import skew as obs_skew
from repro.obs import trace as obs_trace

# the vmapped partition axis gets a collective name so programs can take
# GLOBAL reductions (PageRank dangling mass / L1 halt) with a plain psum —
# the engine hands each program the axes it runs under (this one, plus the
# mesh axis on the shard_map backend)
_VPART_AXIS = "vparts"

# compiled BSP loops shared ACROSS engine instances (see _runner); FIFO-bounded
# so a churny fleet can't pin unbounded trace closures
_RUNNER_CACHE: dict = {}
_RUNNER_CACHE_CAP = 64


@dataclasses.dataclass(frozen=True)
class _PgScalars:
    """The only pg fields the compiled BSP loop reads — cached runners hold
    these instead of a full PartitionedGraph (see _runner)."""
    num_parts: int
    v_max: int
    mailbox_cap: int


@dataclasses.dataclass
class Telemetry:
    supersteps: int
    local_iters: np.ndarray        # (P,) cumulative sweep iterations (straggler signal)
    changed_hist: np.ndarray       # (supersteps,) #partitions changed per superstep
    messages_sent: int
    # query-batched runs only: per-query superstep at which the query last
    # changed (its individual convergence point — it stops sending after this)
    query_supersteps: Optional[np.ndarray] = None
    # wire accounting, per exchange discipline:
    #   'dense'   PHYSICAL: the constant P²·cap buffer geometry per round.
    #   'tiered'  PHYSICAL: the tier schedule's routed buffer geometry per
    #             round (core.tiers.TierSchedule.round_slots) — what the
    #             interconnect actually carries; static per tier plan, and
    #             it tracks the frontier through the traffic profile.
    #   'compact' MODELED protocol payload (Σ packed counts): what a
    #             count-prefixed transport would ship. The compact mode's
    #             PHYSICAL buffers keep the dense geometry plus a slot map
    #             (that gap is exactly what the tiered mode closes).
    # Histograms are ROUND-indexed (length supersteps + 1): round 0 is the
    # pre-loop inbox PRIME (the initial state's messages) and round s + 1 is
    # the exchange at the END of superstep s — so wire_hist.sum() equals
    # wire_slots with no unaccounted round (the prime used to be counted in
    # wire_slots only, leaving the per-round histograms one short).
    wire_hist: Optional[np.ndarray] = None     # (supersteps + 1,) int
    wire_slots: int = 0                        # total slots shipped (incl. prime)
    bytes_on_wire: int = 0                     # wire bytes under the same model
    # Gopher Mesh: per-pair packed-count totals (the traffic profile's
    # observation — feed to core.tiers.update_profile) and the tiered run's
    # overflow record
    exchange: str = ""                         # resolved discipline of the run
    pair_slots: Optional[np.ndarray] = None    # (P, P) Σ packed counts
    pair_rounds: int = 0                       # exchange rounds pair_slots
                                               # covers (≠ supersteps+1 after
                                               # a dense fallback retry)
    pair_overflow: Optional[np.ndarray] = None # (P, P) #supersteps overflowed
    spills: int = 0                            # Σ pair_overflow (tier misses)
    escalations: int = 0                       # pairs promoted after spills
    retried: bool = False                      # dense fallback retry ran
    # Gopher Phases (phased runs; count_hist also on compact/tiered —
    # 'dense' measures no packed counts, so its count_hist stays None):
    count_hist: Optional[np.ndarray] = None    # (supersteps + 1,) Σ packed
                                               # counts per round — the
                                               # frontier width (feed to
                                               # tiers.update_changed_profile)
    phase_hist: Optional[np.ndarray] = None    # (supersteps + 1,) phase index
                                               # of each round's exchange
                                               # (round 0 = prime, phase 0)
    phase_switch_steps: Optional[np.ndarray] = None  # supersteps at which the
                                               # run crossed into a new phase
    phase_wire: Optional[np.ndarray] = None    # (K,) routed slots per phase
                                               # (phase 0 includes the prime)
    phase_pair_slots: Optional[np.ndarray] = None    # (K, P, P) Σ packed
                                               # counts per phase
    dense_retry_steps: int = 0                 # supersteps whose exchange
                                               # fell back to the dense route
                                               # after an in-phase overflow
    # Gopher Balance: wall-clock seconds attributed per partition by the
    # host-stepped driver (checkpointed/traced runs) — the TIME channel of
    # the skew report. Injected straggler stalls land on their targeted
    # partition; the remaining superstep time spreads evenly (one host
    # process can't see real per-partition compute splits). None on the
    # fused single-dispatch loops, which have no per-superstep host clock.
    part_seconds: Optional[np.ndarray] = None  # (P,) float64
    # the sweeps the partitions' fixpoints ran in lockstep, summed over
    # supersteps (PageRank: one per superstep) — what the slowest device
    # executed, where local_iters counts per partition. A superstep's
    # lockstep sweeps are its busiest partition's: on the megastep's flat
    # state and on one device's vmapped partitions alike, and across a
    # mesh, where every device waits at the superstep's collectives for
    # the slowest one.
    lockstep_sweeps: Optional[int] = None
    # the staged loops' sweep slots in which a device had finished its own
    # partitions' fixpoints and waited for the slowest device's: per
    # superstep Σ_d (max_d m_d − m_d), m_d a device's busiest partition's
    # sweeps, summed over supersteps. 0 on one device; None on the
    # megastep route.
    chip_wait_sweeps: Optional[int] = None

    @staticmethod
    def model_bytes(slots: int, num_parts: int, rounds: int, cap: int,
                    num_queries: Optional[int], compact: bool) -> int:
        """The dense/compact comm-volume model: per round the dense exchange
        ships every pair row — P² · cap · Q values at 4 B — while the
        compact exchange ships, per pair, a count header (4 B) plus count
        packed slots at (4·Q value bytes + 4 slot-id bytes) each; payload ∝
        |frontier|. (Tiered runs use TierSchedule.round_bytes instead.)"""
        q = num_queries or 1
        if not compact:
            return rounds * num_parts * num_parts * cap * q * 4
        return slots * (4 * q + 4) + rounds * num_parts * num_parts * 4

    def skew(self) -> dict:
        """Gopher Scope: the run's partition-imbalance report (straggler
        score off local_iters, wire skew off pair_slots) — see
        repro.obs.skew.skew_report."""
        return obs_skew.skew_report(self)


class _StepTele:
    """The host-stepped driver's telemetry: each round's and superstep's
    observation record folded on the host into exactly the keys the
    matching fused loop emits for the exchange (so _finish/_telemetry are
    shared verbatim), plus the host-only ``psec`` (Telemetry.part_seconds)
    and ``rounds`` (the exchange rounds this process ran)."""

    def __init__(self, exchange: str, num_parts: int, max_supersteps: int,
                 num_phases: int, num_queries: Optional[int],
                 num_devices: int):
        self.mode = exchange
        self.num_devices = num_devices
        self.liters = np.zeros(num_parts, np.int64)
        self.psec = np.zeros(num_parts, np.float64)
        self.hist = np.zeros(max_supersteps, np.int64)
        self.whist, self.chist, self.phist = (
            np.zeros(max_supersteps + 1, np.int64) for _ in range(3))
        # per-pair buckets parts-leading (P, K, P), as the phased loop
        # carries them; K = 1 on every other exchange
        self.pairs = np.zeros((num_parts, num_phases, num_parts), np.int64)
        self.over = np.zeros_like(self.pairs)
        self.seg_end = np.zeros(num_phases, np.int64)
        self.qsteps = (None if num_queries is None
                       else np.zeros(num_queries, np.int64))
        self.sent = self.wire = self.dsteps = self.lsweeps = self.cwait = 0

    def fold_round(self, rnd: int, phase: int, obs: dict):
        """Exchange round ``rnd`` (0 = the prime, s + 1 = superstep s's)
        shipped in ``phase``; returns its (wire, nsent) as host ints."""
        wire, nsent = int(obs.get("wire", 0)), int(obs["nsent"])
        self.whist[rnd] = wire
        self.phist[rnd] = phase
        self.wire += wire
        self.sent += nsent
        if "pairs" in obs:
            pairs = np.asarray(obs["pairs"], np.int64)
            self.chist[rnd] = pairs.sum()
            self.pairs[:, phase] += pairs
        if "over" in obs:
            self.over[:, phase] += np.asarray(obs["over"], np.int64)
        self.dsteps += int(obs.get("dstep", 0))
        return wire, nsent

    def fold_step(self, step: int, obs: dict):
        """Superstep ``step``'s sweeps and the host halt vote over its
        changed flags; returns (#partitions changed, any change)."""
        li = np.asarray(obs["li"], np.int64)
        self.liters += li
        if "sweeps" in obs:          # the flat fixpoint counts its own
            self.lsweeps += int(obs["sweeps"])
        else:
            # the busiest partition's sweeps per device (device d holds
            # the d-th contiguous run of partitions); every device waits
            # for the slowest at the superstep's collectives
            m_d = li.reshape(self.num_devices, -1).max(axis=1)
            self.lsweeps += int(m_d.max())
            self.cwait += int((m_d.max() - m_d).sum())
        ch = np.asarray(obs["changed"])
        if self.qsteps is None:
            nchanged, any_changed = int(ch.sum()), bool(ch.any())
        else:
            changed_q = ch.any(axis=0)
            nchanged = int(ch.any(axis=-1).sum())
            any_changed = bool(changed_q.any())
            self.qsteps[changed_q] = step + 1
        self.hist[step] = nchanged
        return nchanged, any_changed

    def charge(self, seconds: float, effects: Optional[dict]) -> None:
        """One superstep's wall time into the partitions' time channel:
        injected stalls land on their target partition, the rest spreads
        evenly (one host process can't see real per-partition splits)."""
        P = len(self.psec)
        stalls = [(p, s) for p, s in (effects or {}).get("stalls", [])
                  if 0 <= p < P]
        self.psec += max(seconds - sum(s for _, s in stalls), 0.0) / P
        for p, s in stalls:
            self.psec[p] += s

    def out(self, rounds: int) -> dict:
        ex = self.mode
        tele = dict(liters=self.liters, hist=self.hist, whist=self.whist,
                    sent=self.sent, wire=self.wire, lsweeps=self.lsweeps,
                    psec=self.psec, rounds=rounds)
        if ex != "megastep":
            tele["cwait"] = self.cwait
        if ex != "dense":
            tele["chist"] = self.chist
            tele["pairs"] = self.pairs if ex == "phased" else self.pairs[:, 0]
        if ex in ("tiered", "phased"):
            tele["over"] = self.over if ex == "phased" else self.over[:, 0]
        if ex == "phased":
            tele.update(phist=self.phist, seg_end=self.seg_end,
                        dsteps=self.dsteps)
        if self.qsteps is not None:
            tele["qsteps"] = self.qsteps
        return tele


class GopherEngine:
    """Runs a program over a PartitionedGraph to global quiescence."""

    def __init__(self, pg: PartitionedGraph, program, backend: str = "local",
                 mesh=None, axis_name: str = "parts",
                 max_supersteps: int = 4096, gb: Optional[dict] = None,
                 exchange: str = "auto", tier_plan: Optional[TierPlan] = None,
                 tracer: Optional["obs_trace.Tracer"] = None,
                 metrics: Optional["obs_metrics.MetricsRegistry"] = None,
                 validate: bool = False):
        with obs_trace.step("engine", tracer=tracer, metrics=metrics):
            assert backend in ("local", "shard_map")
            assert exchange in ("auto", "compact", "dense", "tiered", "phased",
                                "megastep")
            if backend == "shard_map":
                assert mesh is not None
                d = mesh.shape[axis_name]
                assert pg.num_parts % d == 0, "partitions must tile the mesh axis"
            self.pg = pg
            self.program = program
            self.backend = backend
            self.mesh = mesh
            self.axis_name = axis_name
            self.max_supersteps = max_supersteps
            # wire discipline. 'auto' resolves per backend + program:
            #   * 'local' + an ELIGIBLE program (program.megastep_kind not None,
            #     i.e. the sub-graph centric run-to-fixpoint schedule or
            #     fixed-iteration PageRank) -> 'megastep' (Gopher Hot): there is
            #     no physical wire to route, so the winning move is to stop
            #     dispatching the staged sweep/pack/route/halt stages at all and
            #     fuse the superstep into one launch — this beats even the
            #     dense single-host transpose at small frontiers (BENCH_comm's
            #     small-frontier gate holds it to that claim);
            #   * 'local' with an ineligible program — and a DEGENERATE 1-device
            #     shard_map mesh, where every partition shares one chip — the
            #     physical "wire" is a single-device transpose, so the dense
            #     path is the smallest remaining choice: any compaction plan is
            #     pure overhead there;
            #   * a multi-device 'shard_map' mesh -> 'tiered': the routed
            #     buffers track the frontier.
            # 'dense' stays the parity / benchmark oracle; 'compact' is Gopher
            # Wire's protocol-payload compaction over dense physical buffers;
            # 'phased' (Gopher Phases) is requested explicitly with a
            # PhasedTierPlan; 'megastep' may also be requested explicitly.
            self.exchange_requested = exchange
            if exchange == "auto":
                if (backend == "local"
                        and getattr(program, "megastep_kind", None) is not None):
                    exchange = "megastep"
                else:
                    local_wire = (backend == "local"
                                  or int(mesh.shape[axis_name]) == 1)
                    exchange = "dense" if local_wire else "tiered"
            self.exchange = exchange
            if self.exchange == "megastep":
                assert backend == "local", \
                    "the megastep exchange is a local-backend route (flat state " \
                    "spans every partition; shard_map meshes route tiered/phased)"
                assert getattr(program, "megastep_kind", None) is not None, \
                    "program is not megastep-eligible (megastep_kind is None)"
            # plan/mode normalization, both directions: a PhasedTierPlan under
            # 'tiered' (e.g. a narrow_resume plan handed to exchange='auto' that
            # resolved tiered) upgrades the mode to 'phased' — a K=1 phased loop
            # is the tiered exchange plus the per-superstep dense retry — and a
            # plain TierPlan under 'phased' wraps as a single phase.
            if self.exchange == "tiered" and isinstance(tier_plan, PhasedTierPlan):
                self.exchange = "phased"
            if self.exchange == "tiered" and tier_plan is None:
                # structural default plan: every pair's width covers its maximum
                # possible slot count, so it can never overflow (see TierPlan)
                tier_plan = TierPlan.from_graph(pg)
            if self.exchange == "phased":
                if tier_plan is None:
                    tier_plan = PhasedTierPlan.from_graph(pg)
                elif isinstance(tier_plan, TierPlan):
                    tier_plan = PhasedTierPlan.from_tier_plan(tier_plan)
            # the megastep route keeps a provided plan too: a PhasedTierPlan's
            # band geometry gates the resident narrow-phase mode (None = pure
            # per-superstep fused BSP, still one dispatch per superstep)
            self.tier_plan = (tier_plan
                              if self.exchange in ("tiered", "phased", "megastep")
                              else None)
            self._gb = gb                # cached device-side graph block; pass a
                                         # shared one so many engines (a serving
                                         # fleet) reuse a single device copy
            self._mega_cm = None         # lazily composed megastep mailbox
                                         # arrays (see _gb_for_run)
            self._runner_memo = {}       # per-engine front of _RUNNER_CACHE
            # Gopher Scope: host-side observability. None defers to the process
            # defaults at run time (so launch/scope can arm a tracer AFTER
            # engines were built). A disabled tracer keeps the compiled fused
            # loop untouched — the host-stepped driver only replaces it when
            # the tracer is enabled.
            self._tracer = tracer
            self._metrics = metrics
            # Gopher Sentinel: validate=True runs the static passes (SPMD
            # collective verification + semiring laws + plan staticness, see
            # repro.analysis) on every compiled-loop cache MISS, before the
            # loop enters the cache — a cache hit means an identical
            # configuration already passed, so warm paths pay nothing.
            self.validate = validate

    @property
    def tracer(self) -> "obs_trace.Tracer":
        return (self._tracer if self._tracer is not None
                else obs_trace.get_tracer())

    @property
    def metrics(self) -> "obs_metrics.MetricsRegistry":
        return (self._metrics if self._metrics is not None
                else obs_metrics.default_registry())

    def _step(self, name: str):
        """A ``gopher.<name>`` program span into this engine's tracer and
        registry (obs.trace.step)."""
        return obs_trace.step(name, tracer=self.tracer, metrics=self.metrics)

    def _graph_block(self):
        """The device graph block, built once per engine — every query batch
        served by this engine shares it (and the jit cache entries keyed on
        its shapes)."""
        if self._gb is None:
            sharding = None
            if (self.backend == "shard_map"
                    and isinstance(self.mesh, jax.sharding.Mesh)):
                # each device holds its own partitions' rows, placed once
                sharding = jax.sharding.NamedSharding(self.mesh,
                                                      P(self.axis_name))
            with self._step("layout"):
                host_gb = host_graph_block(self.pg)
            with self._step("upload"):
                self._gb = device_block(host_gb, sharding)
        return self._gb

    def _gb_for_run(self, gb):
        """The graph block a compiled run actually receives. On the megastep
        exchange this merges the COMPOSED MAILBOX (kernels.megastep
        .compose_mailbox) into the block as ``mcm_*`` entries, built once
        per engine OUTSIDE the compiled loop. The staged paths gather
        through inverse maps precomputed in blocks.py; composing the fused
        path's maps inside jit instead re-materializes them on every call —
        measured at ~⅓ of a warm small-frontier run, which is exactly the
        launch-overhead budget the megastep exists to reclaim. Python-int
        statics are NOT shipped — _run_megastep re-derives them from shapes.
        Callers that trace with a bare block (sentinel's trace_loop, the
        host-stepped driver) skip this and compose inline."""
        if self.exchange != "megastep":
            return gb
        if self._mega_cm is None:
            kind = self.program.megastep_kind
            block = self._graph_block()
            with self._step("compose_mailbox"):
                cm = mega.compose_mailbox_arrays(
                    block, adjacency=("binned" if kind == "batched_semiring"
                                      else "full"))
            self._mega_cm = {**block,
                             **{"mcm_" + k: v for k, v in cm.items()}}
        if gb is self._gb:
            return self._mega_cm
        return {**self._mega_cm,
                **{k: v for k, v in gb.items() if not k.startswith("mcm_")}}

    # ---------------- superstep body (backend-shared) ----------------
    def make_superstep(self, gb, num_queries: Optional[int] = None,
                       phase: Optional[int] = None):
        """One BSP superstep over a partition batch gb (leading axis = local
        partition count). Returns (state, inbox, changed, liters(P,), nsent,
        wire, extras) — ``wire`` is the superstep's shipped-slot count under
        the engine's exchange mode and ``extras`` carries the per-pair wire
        telemetry the mode produces (see make_exchange). ``phase`` selects
        the tier table on a phased plan (one superstep body is traced per
        loop segment).

        Its device stages carry the ``gopher.*`` named scopes that
        ``repro.obs.op_stages`` reads back: the program's local fixpoint
        is ``gopher.sweep``, and the exchange's halves are named by
        make_exchange_stages.

        With ``num_queries=Q`` the program is query-batched: state/inbox
        leaves carry a QUERY-TRAILING (v_max, Q) shape per partition (Q rides
        the contiguous lane dimension), `changed` is per-partition per-query
        (P, Q), and the mailbox carries cap*Q slots per partition pair —
        routing is identical on both backends.
        """
        prog = self.program
        Q = num_queries
        axes = ((_VPART_AXIS,) if self.backend == "local"
                else (_VPART_AXIS, self.axis_name))

        exchange = self.make_exchange(gb, num_queries=Q, phase=phase)

        def sstep(state, inbox, step):
            new_state, changed, liters = jax.vmap(
                lambda s, i, g: prog.superstep(s, i, g, step, axes=axes),
                in_axes=(0, 0, 0), axis_name=_VPART_AXIS)(state, inbox, gb)
            inbox, nsent, wire, extras = exchange(new_state)
            return new_state, inbox, changed, liters, nsent, wire, extras

        return sstep

    def make_exchange(self, gb, num_queries: Optional[int] = None,
                      phase: Optional[int] = None):
        """The mailbox half of a superstep: state -> (inbox, nsent, wire,
        extras). Split out so the BSP loop can PRIME the first inbox from the
        INITIAL state — without priming, superstep 0 computes with an empty
        inbox and treats every remote in-edge as contributing the ⊕-identity.
        For idempotent programs that only delays information one superstep,
        but for PageRank it silently dropped all remote mass from the first
        Jacobi iteration (an error that decays only as damping^k).

        Four wire disciplines (``self.exchange``; 'auto' resolved at
        construction to 'dense' on local / 1-device meshes, 'tiered' on
        multi-device shard_map):

        'dense'    every (src, dst) pair ships its full cap-slot row every
                   superstep — identity-filled when the pair is quiescent.
                   wire = P · cap per local source row, unconditionally
                   (PHYSICAL: that IS the routed buffer geometry).
        'compact'  frontier-compacted protocol (Gopher Wire): each pair row
                   is PACKED to a dense prefix of its active slots plus a
                   per-destination count vector; quiesced pairs ship
                   count = 0. The receiver rebuilds fixed slot positions
                   with a pure gather, so the combine — and every
                   downstream bit — is IDENTICAL to the dense path.
                   wire = Σ counts ∝ |frontier| — the MODELED count-prefixed
                   payload; the physical buffers keep the dense geometry
                   plus a slot map.
        'tiered'   Gopher Mesh: the PHYSICAL buffers track the frontier. A
                   static TierPlan (per-pair traffic profile, core.tiers)
                   routes hot pairs' full cap rows through one all_to_all
                   over per-device-pair row blocks, warm (cap/8) and cold
                   (width-1) pairs' packed prefixes through a ppermute
                   round-robin over only the nonzero device shifts, and
                   ships NOTHING for structurally-empty pairs. wire = the
                   routed geometry, static per plan. A pair whose active
                   slots exceed its tier width is truncated and flagged
                   (extras['over']); the run driver repairs that with a
                   dense fallback retry and escalates the pair for the next
                   version — results are bit-identical to 'dense'
                   unconditionally.
        'phased'   Gopher Phases: the tiered exchange at ONE phase's tier
                   table (``phase`` selects it from the PhasedTierPlan; the
                   segmented BSP loop traces one body per phase). Overflow
                   handling is PER-SUPERSTEP: the pack's overflow flags are
                   all-reduced BEFORE routing and the whole superstep's
                   exchange falls back to the dense route (lax.cond) when
                   any pair truncated — no messages are ever lost, so the
                   run needs no whole-run retry; the spilled phase (not the
                   whole plan) is escalated afterwards. Costs one extra
                   scalar all-reduce per superstep on shard_map.

        ``extras`` is the mode's per-pair telemetry: {} for dense,
        {'pairs': (v, P) packed counts} for compact, plus {'over': (v, P)
        overflow flags} for tiered, plus {'dstep': scalar 0/1 dense-retry
        flag} for phased. The BSP loop accumulates them into
        Telemetry.pair_slots / pair_overflow — the observations
        core.tiers.update_profile folds into the traffic profile.
        """
        pack, route = self.make_exchange_stages(gb, num_queries=num_queries,
                                                phase=phase)

        def exchange(state):
            payload, nsent, wire, extras = pack(state)
            inbox, rex = route(payload)
            if rex:
                wire = rex.get("wire", wire)
                extras = dict(extras, **{k: v for k, v in rex.items()
                                         if k != "wire"})
            return inbox, nsent, wire, extras

        return exchange

    def make_exchange_stages(self, gb, num_queries: Optional[int] = None,
                             phase: Optional[int] = None,
                             exchange: Optional[str] = None):
        """The exchange split at its NETWORK BOUNDARY into two closures —
        ``pack(state) -> (payload, nsent, wire, extras)`` (pure device-local
        message build / frontier compaction; payload is the pytree that
        would cross the wire) and ``route(payload) -> (inbox, route_extras)``
        (the collective transpose plus inbox combine). ``make_exchange``
        composes them, so the compiled fused loop's math is exactly the
        per-stage math; the host-stepped driver dispatches the stages
        individually (see stage_fns), under ``exchange`` (default: the
        engine's own) — a checkpointed run's replay exchange, see
        stepped_exchange.

        ``route_extras`` is {} except on 'phased', where the per-superstep
        dense-retry decision lives on the route side: {'wire': the corrected
        shipped-slot count, 'dstep': the 0/1 retry flag}.

        Named stages (``jax.named_scope``, HLO metadata only): ``pack`` is
        ``gopher.pack``; ``route``'s collective transpose (the dense or
        compact all_to_all, the tiered routing, the phased retry's
        ``cond``) is ``gopher.route``; its inbox ⊕-combine is
        ``gopher.deliver``.
        """
        prog = self.program
        cap = self.pg.mailbox_cap
        v_max = self.pg.v_max
        combine = prog.combine
        num_parts = self.pg.num_parts
        Q = num_queries
        mode = exchange or self.exchange
        assert mode != "megastep", \
            "the megastep route has no staged exchange (see _run_megastep)"

        if mode in ("tiered", "phased"):
            plan = self.tier_plan
            assert plan is not None
            if mode == "phased":
                assert phase is not None, "phased exchange needs a phase index"
                plan = plan.phase_plans()[phase]
            assert plan.num_parts == num_parts and plan.cap == cap, \
                "tier plan was built for a different graph geometry"
            D = (1 if self.backend == "local"
                 else int(self.mesh.shape[self.axis_name]))
            sched = plan.schedule(D)
            limits_np = plan.limits()
            axis = self.axis_name if self.backend == "shard_map" else None

        @jax.named_scope("gopher.route")
        def phys(x):
            if self.backend == "local":
                return msg.route_local(x)
            return msg.route_shard_map(x, self.axis_name)

        if Q is None:
            comb = functools.partial(msg.combine_inbox_gather,
                                     v_max=v_max, combine=combine)
        else:
            comb = functools.partial(msg.combine_inbox_gather_batched,
                                     v_max=v_max, cap=cap, combine=combine)

        @jax.named_scope("gopher.deliver")
        def finish(iv):
            return jax.vmap(comb)(iv, gb["ib_lo"], gb["ib_hub_idx"],
                                  gb["ib_hub"])

        def send_messages(state):
            vals, send = jax.vmap(prog.messages)(state, gb)
            return vals, send, jnp.sum(send).astype(jnp.int32)

        if mode == "dense":
            # gather-form dense mailbox: slots PULL through the inverse
            # routing plan — no runtime scatter, only values travel
            build = functools.partial(
                msg.build_outbox_gather if Q is None
                else msg.build_outbox_gather_batched,
                num_parts=num_parts, cap=cap, combine=combine)

            @jax.named_scope("gopher.pack")
            def pack(state):
                vals, send, nsent = send_messages(state)
                slot_vals = jax.vmap(build)(vals, send, gb["ob_inv"])
                p_local = gb["vmask"].shape[0]
                wire = jnp.int32(p_local * num_parts * cap)
                return (slot_vals,), nsent, wire, {}

            def route(payload):
                (slot_vals,) = payload
                return finish(phys(slot_vals)), {}

        elif mode == "compact":
            build = functools.partial(
                msg.build_outbox_compact if Q is None
                else msg.build_outbox_compact_batched,
                num_parts=num_parts, cap=cap, combine=combine)
            unpack = functools.partial(
                msg.unpack_slots if Q is None
                else msg.unpack_slots_batched, combine=combine)

            @jax.named_scope("gopher.pack")
            def pack(state):
                vals, send, nsent = send_messages(state)
                pvals, pinv, counts = jax.vmap(build)(vals, send,
                                                      gb["ob_inv"])
                # count-prefixed exchange: the packed prefixes and their
                # slot-position maps travel; counts[d] is the header a real
                # transport would read each prefix length from (here the
                # PAD entries of pinv mark inactivity, so the header itself
                # isn't routed — it feeds the wire telemetry and the
                # piggybacked halt vote)
                wire = jnp.sum(counts).astype(jnp.int32)
                return (pvals, pinv), nsent, wire, {"pairs": counts}

            def route(payload):
                pvals, pinv = payload
                with jax.named_scope("gopher.route"):
                    iv = jax.vmap(unpack)(phys(pvals), phys(pinv))
                return finish(iv), {}

        else:  # tiered / phased
            ident = msg.COMBINE_IDENTITY[combine]
            build = functools.partial(
                msg.build_outbox_gather if Q is None
                else msg.build_outbox_gather_batched,
                num_parts=num_parts, cap=cap, combine=combine)
            Qg = 1 if Q is None else Q

            @jax.named_scope("gopher.pack")
            def pack(state):
                vals, send, nsent = send_messages(state)
                slot_vals = jax.vmap(build)(vals, send, gb["ob_inv"])
                v_local = slot_vals.shape[0]
                sv4 = slot_vals.reshape(v_local, num_parts, cap, Qg)
                act = jax.vmap(functools.partial(
                    msg.active_slots, num_parts=num_parts,
                    cap=cap))(send, gb["ob_inv"])
                lim = jnp.asarray(limits_np)
                if axis is not None and D > 1:
                    lim = jax.lax.dynamic_slice(
                        lim, (jax.lax.axis_index(axis) * v_local, 0),
                        (v_local, num_parts))
                else:
                    lim = lim[:v_local]
                # fused pack (plan + tier truncation + spill detection) over
                # the flat row batch — rows are independent, no vmap needed
                R = v_local * num_parts
                sv_rows = (sv4.reshape(R, cap) if Q is None
                           else sv4.reshape(R, cap, Qg))
                pvals, sids, _, counts, over = ops.outbox_pack(
                    sv_rows, act.reshape(R, cap), lim.reshape(R), ident)
                extras = {"pairs": counts.reshape(v_local, num_parts),
                          "over": over.reshape(v_local, num_parts)}
                wire = jnp.int32(sched.device_round_slots())
                return (sv4, pvals, sids, over), nsent, wire, extras

            def route(payload):
                sv4, pvals, sids, over = payload
                v_local = sv4.shape[0]

                @jax.named_scope("gopher.route")
                def tier_route(sv4):
                    return msg.route_tiered(
                        sv4, pvals.reshape(v_local, num_parts, cap, Qg),
                        sids.reshape(v_local, num_parts, cap), sched,
                        combine, axis_name=axis)

                if mode == "tiered":
                    iv4 = tier_route(sv4)
                    rex = {}
                else:  # phased: per-superstep dense retry on overflow
                    with jax.named_scope("gopher.route"):
                        over_any = jnp.any(over > 0).astype(jnp.int32)
                        if axis is not None and D > 1:
                            over_any = jax.lax.psum(over_any, axis)
                        retry = over_any > 0

                        def dense_route(sv4):
                            flat = phys(sv4.reshape(v_local, num_parts,
                                                    cap * Qg))
                            return flat.reshape(v_local, num_parts, cap, Qg)

                        iv4 = jax.lax.cond(retry, dense_route, tier_route,
                                           sv4)
                        rex = {"wire": jnp.where(
                                   retry, jnp.int32(v_local * num_parts * cap),
                                   jnp.int32(sched.device_round_slots())),
                               "dstep": retry.astype(jnp.int32)}
                iv = iv4.reshape(v_local, num_parts,
                                 cap if Q is None else cap * Qg)
                return finish(iv), rex

        return pack, route

    def _reduce_stats(self, scalars, liters, changed_q=None):
        """The staged superstep's fused stats reduction: ``scalars`` (int32
        counters), this device's busiest partition's sweeps ``m_d`` in its
        own slot of a length-D vector (D devices on the mesh axis, 1 on
        'local'), and ``changed_q`` (per-query flags, or None), in ONE psum
        on shard_map. Returns (the summed scalars, the superstep's lockstep
        sweeps M = max_d m_d, its chip wait Σ_d (M − m_d), the summed
        ``changed_q``). Every device reads every m_d, so the counters cost
        no collective of their own."""
        m = jnp.max(liters).astype(jnp.int32)
        if self.backend == "shard_map":
            mvec = jnp.zeros((int(self.mesh.shape[self.axis_name]),),
                             jnp.int32).at[
                jax.lax.axis_index(self.axis_name)].set(m)
        else:
            mvec = m[None]
        n, d = len(scalars), mvec.shape[0]
        stats = jnp.concatenate([jnp.stack(scalars), mvec]
                                + ([] if changed_q is None else [changed_q]))
        if self.backend == "shard_map":
            stats = jax.lax.psum(stats, self.axis_name)
        mvec = stats[n:n + d]
        lock = jnp.max(mvec)
        return (tuple(stats[i] for i in range(n)), lock, jnp.sum(lock - mvec),
                None if changed_q is None else stats[n + d:])

    def _run_batched(self, gb, num_queries: Optional[int] = None):
        """The full BSP loop over a partition batch. Runs as-is on the local
        backend; runs per-shard (with collectives) under shard_map.

        Query-batched runs halt when NO query changed anywhere; a query whose
        own flags went quiet stops producing messages (its send mask is gated
        on per-query changed_v) while the rest of the batch keeps moving.
        """
        if self.exchange == "phased":
            return self._run_phased(gb, num_queries=num_queries)
        if self.exchange == "megastep":
            return self._run_megastep(gb, num_queries=num_queries)
        prog = self.program
        Q = num_queries
        mode = self.exchange
        sstep = self.make_superstep(gb, num_queries=Q)
        p_local = gb["vmask"].shape[0]
        state0 = jax.vmap(prog.init)(gb)
        # prime the mailbox with the INITIAL state's messages so superstep 0
        # computes against a consistent inbox (see make_exchange)
        inbox0, nsent0, wire0, ex0 = self.make_exchange(gb,
                                                        num_queries=Q)(state0)
        cnt0 = (jnp.sum(ex0["pairs"]).astype(jnp.int32)
                if "pairs" in ex0 else jnp.int32(0))
        if self.backend == "shard_map":
            s0 = jax.lax.psum(jnp.stack([nsent0, wire0, cnt0]),
                              self.axis_name)
            nsent0, wire0, cnt0 = s0[0], s0[1], s0[2]
        # histograms are ROUND-indexed (see Telemetry): slot 0 carries the
        # prime, the body writes superstep s's exchange at slot s + 1
        tele0 = dict(liters=jnp.zeros((p_local,), jnp.int32),
                     hist=jnp.zeros((self.max_supersteps,), jnp.int32),
                     whist=jnp.zeros((self.max_supersteps + 1,),
                                     jnp.int32).at[0].set(wire0),
                     sent=nsent0, wire=wire0,
                     lsweeps=jnp.int32(0), cwait=jnp.int32(0))
        if mode in ("compact", "tiered"):
            # per-round Σ packed counts — the frontier-width histogram
            # the changed-profile EWMA (Gopher Phases) learns from
            tele0["chist"] = jnp.zeros((self.max_supersteps + 1,),
                                       jnp.int32).at[0].set(cnt0)
        # per-pair wire telemetry (compact/tiered): rows stay device-local,
        # the out_specs shard them back to the full (P, P) matrices
        for k, v in ex0.items():
            tele0[k] = v
        if Q is not None:
            tele0["qsteps"] = jnp.zeros((Q,), jnp.int32)

        def cond(c):
            _, _, step, done, _ = c
            return (~done) & (step < self.max_supersteps)

        def body(c):
            state, inbox, step, _, tele = c
            state, inbox, changed, liters, nsent, wire, ex = sstep(state,
                                                                   inbox, step)
            # the halt vote rides the same reduction as the wire counters:
            # ONE fused psum per superstep carries [pairs-changed?, nsent,
            # wire, counts, each device's busiest partition's sweeps(,
            # per-query changed)] — the count vector the compact exchange
            # produces anyway — instead of a separate all-reduce round per
            # counter.
            with jax.named_scope("gopher.stats"):
                cnt = (jnp.sum(ex["pairs"]).astype(jnp.int32)
                       if "pairs" in ex else jnp.int32(0))
                changed_q = None
                if Q is None:
                    nchanged = jnp.sum(changed.astype(jnp.int32))
                else:
                    changed_q = jnp.any(changed, axis=0).astype(jnp.int32)
                    nchanged = jnp.sum(jnp.any(changed,
                                               axis=-1).astype(jnp.int32))
                (nchanged, nsent, wire, cnt), lock, wait, changed_q = \
                    self._reduce_stats([nchanged, nsent, wire, cnt], liters,
                                       changed_q)
                any_changed = (nchanged > 0 if Q is None
                               else jnp.any(changed_q > 0))
                new_tele = dict(liters=tele["liters"] + liters,
                                hist=tele["hist"].at[step].set(nchanged),
                                whist=tele["whist"].at[step + 1].set(wire),
                                sent=tele["sent"] + nsent,
                                wire=tele["wire"] + wire,
                                lsweeps=tele["lsweeps"] + lock,
                                cwait=tele["cwait"] + wait)
                if "chist" in tele:
                    new_tele["chist"] = tele["chist"].at[step + 1].set(cnt)
                for k, v in ex.items():
                    new_tele[k] = tele[k] + v
                if Q is not None:
                    new_tele["qsteps"] = jnp.where(changed_q > 0, step + 1,
                                                   tele["qsteps"])
            return state, inbox, step + 1, ~any_changed, new_tele

        state, _, steps, _, tele = jax.lax.while_loop(
            cond, body, (state0, inbox0, jnp.int32(0), jnp.bool_(False), tele0))
        return state, steps, tele

    def _run_megastep(self, gb, num_queries: Optional[int] = None):
        """Gopher Hot: the BSP loop with the whole superstep — mailbox
        delivery, inbox ⊕-combine, masked local fixpoint, halt reduction —
        fused into ONE dispatch over flat (P·v_max,) state
        (kernels.megastep). The staged loop's three routing hops are
        composed once per run into direct gather maps; delivery happens at
        the TOP of each superstep from the previous round's send set, which
        is the same message multiset one loop-carry shorter (and the prime
        falls out of init's changed_v seed with no special case). Results
        are bit-identical to the staged dense path for idempotent ⊕ and
        allclose for PageRank — the same parity classes the exchange stack
        already guarantees.

        Telemetry mirrors the compact layout: ``pairs``/``chist`` are the
        LOGICAL frontier observation (identical counts to the compact
        path's active_slots, so the tier-profile EWMAs keep learning), and
        ``wire``/``whist`` are zero — nothing ships through buffers.

        With a PhasedTierPlan whose narrow band suffix fits
        MEGASTEP_VMEM_BUDGET (scalar semiring programs), the tail runs in
        RESIDENT mode: chaotic-relaxation rounds with the mailbox held
        on chip — one sweep per delivery, every improvement rebroadcast
        next round — which converges to the same bitwise fixpoint and, on
        TPU, executes as a single multi-superstep Pallas launch
        (per-round hist/chist entries are coarse there: the launch reports
        totals, not rounds)."""
        prog = self.program
        kind = prog.megastep_kind
        Q = num_queries
        p_local = gb["vmask"].shape[0]
        v_max = self.pg.v_max
        max_s = self.max_supersteps
        if "mcm_vmask" in gb:
            # pre-composed by _gb_for_run; statics re-derived from shapes
            cm = {k[4:]: v for k, v in gb.items() if k.startswith("mcm_")}
            cm.update(num_parts=p_local, v_max=v_max,
                      cap=gb["ob_inv"].shape[1] // p_local,
                      n=p_local * v_max)
            # the flat (n,)-shaped mailbox entries must not reach the
            # per-partition vmaps below
            gb = {k: v for k, v in gb.items() if not k.startswith("mcm_")}
        else:
            cm = mega.compose_mailbox(
                gb,
                adjacency="binned" if kind == "batched_semiring" else "full")
        state0 = jax.vmap(prog.init)(gb)

        def base_tele(pairs0, nsent0):
            tele = dict(
                liters=jnp.zeros((p_local,), jnp.int32),
                hist=jnp.zeros((max_s,), jnp.int32),
                whist=jnp.zeros((max_s + 1,), jnp.int32),
                chist=jnp.zeros((max_s + 1,), jnp.int32)
                    .at[0].set(jnp.sum(pairs0).astype(jnp.int32)),
                sent=nsent0, wire=jnp.int32(0), pairs=pairs0,
                lsweeps=jnp.int32(0))
            if Q is not None:
                tele["qsteps"] = jnp.zeros((Q,), jnp.int32)
            return tele

        @jax.named_scope("gopher.stats")
        def fold(tele, step, pairs, nsent, li, nchanged, sweeps):
            new = dict(liters=tele["liters"] + li,
                       hist=tele["hist"].at[step].set(nchanged),
                       whist=tele["whist"],
                       chist=tele["chist"].at[step + 1]
                           .set(jnp.sum(pairs).astype(jnp.int32)),
                       sent=tele["sent"] + nsent,
                       wire=tele["wire"],
                       pairs=tele["pairs"] + pairs,
                       lsweeps=tele["lsweeps"] + sweeps)
            if Q is not None:
                new["qsteps"] = tele["qsteps"]
            return new

        round_stats = jax.named_scope("gopher.stats")(mega.round_stats)

        if kind == "pagerank":
            r = state0["r"].reshape(-1)
            deg = gb["out_degree"].astype(jnp.float32).reshape(-1)
            telep = (jax.vmap(prog.teleport_fn)(gb).reshape(-1)
                     if prog.teleport_fn is not None
                     else 1.0 / prog.n_global)
            pairs0, nsent0 = round_stats(None, cm)
            tele0 = base_tele(pairs0, nsent0)

            def cond(c):
                _, _, step, done, _ = c
                return (~done) & (step < max_s)

            def body(c):
                r, _, step, _, tele = c
                r2, delta, chg = mega.megastep_pagerank(
                    r, cm, deg, telep, prog.n_global, prog.damping,
                    prog.num_iters, step)
                # PageRank sends unconditionally, so every round's logical
                # observation is the full slot occupancy — including the
                # final round, matching the staged loop's last exchange
                pairs, nsent = round_stats(None, cm)
                nch = chg.astype(jnp.int32) * jnp.int32(p_local)
                tele = fold(tele, step, pairs, nsent,
                            jnp.ones((p_local,), jnp.int32), nch,
                            jnp.int32(1))
                return r2, delta, step + 1, ~chg, tele

            r, delta, steps, _, tele = jax.lax.while_loop(
                cond, body,
                (r, jnp.float32(jnp.inf), jnp.int32(0), jnp.bool_(False),
                 tele0))
            state = {"r": r.reshape(p_local, v_max),
                     "delta": jnp.full((p_local,), delta)}
            return state, steps, tele

        semiring = prog.semiring
        unroll = prog.fixpoint_unroll

        if kind == "batched_semiring":
            x = state0["x"].reshape(-1, Q)
            ch = state0["changed_v"].reshape(-1, Q)
            fr = state0["frontier"].reshape(-1, Q)
            pairs0, nsent0 = round_stats(ch, cm)
            tele0 = base_tele(pairs0, nsent0)

            def cond(c):
                _, _, _, step, done, _ = c
                return (~done) & (step < max_s)

            def body(c):
                x, ch, fr, step, _, tele = c
                x2, ch2, fl, li, sweeps = mega.megastep_semiring_batched(
                    x, ch, fr, cm, semiring, unroll=unroll)
                pairs, nsent = round_stats(ch2, cm)
                with jax.named_scope("gopher.frontier"):
                    chpq = jnp.any(ch2.reshape(p_local, v_max, Q), axis=1)
                    changed_q = jnp.any(chpq, axis=0)
                    nch = jnp.sum(jnp.any(chpq, axis=-1).astype(jnp.int32))
                tele = fold(tele, step, pairs, nsent, li, nch, sweeps)
                tele["qsteps"] = jnp.where(changed_q, step + 1,
                                           tele["qsteps"])
                return x2, ch2, fl, step + 1, ~jnp.any(changed_q), tele

            x, ch, fr, steps, _, tele = jax.lax.while_loop(
                cond, body,
                (x, ch, fr, jnp.int32(0), jnp.bool_(False), tele0))
            state = {"x": x.reshape(p_local, v_max, Q),
                     "changed_v": ch.reshape(p_local, v_max, Q),
                     "frontier": fr.reshape(p_local, v_max, Q)}
            return state, steps, tele

        # scalar semiring
        x = state0["x"].reshape(-1)
        ch = state0["changed_v"].reshape(-1)
        fr = state0["frontier"].reshape(-1)
        pairs0, nsent0 = round_stats(ch, cm)
        tele0 = base_tele(pairs0, nsent0)

        def sem_fold(tele, step, ch2, li, sweeps):
            pairs, nsent = round_stats(ch2, cm)
            with jax.named_scope("gopher.frontier"):
                nch = jnp.sum(jnp.any(ch2.reshape(p_local, v_max),
                                      axis=1).astype(jnp.int32))
            return fold(tele, step, pairs, nsent, li, nch, sweeps), nch

        def cond(c):
            _, _, _, step, done, _ = c
            return (~done) & (step < max_s)

        def bsp_body(c):
            x, ch, fr, step, _, tele = c
            x2, ch2, fl, li, sweeps = mega.megastep_semiring(
                x, ch, fr, cm, semiring, unroll=unroll,
                backend=prog.spmv_backend, interpret=prog.interpret)
            tele, nch = sem_fold(tele, step, ch2, li, sweeps)
            return x2, ch2, fl, step + 1, nch == 0, tele

        # resident narrow-phase gate: the earliest superstep from which
        # every remaining phase band's predicted round geometry fits the
        # VMEM budget (None without a PhasedTierPlan, or when no suffix
        # fits — pure per-superstep fused BSP then)
        enter = None
        if isinstance(self.tier_plan, PhasedTierPlan):
            plans = self.tier_plan.phase_plans()
            rb = [p.schedule(1).round_bytes(Q) for p in plans]
            enter = mega.resident_enter_round(rb, self.tier_plan.boundaries)

        carry = (x, ch, fr, jnp.int32(0), jnp.bool_(False), tele0)
        if enter is None or enter >= max_s:
            carry = jax.lax.while_loop(cond, bsp_body, carry)
        else:
            if enter > 0:
                def pre_cond(c, _enter=jnp.int32(enter)):
                    _, _, _, step, done, _ = c
                    return (~done) & (step < _enter)

                carry = jax.lax.while_loop(pre_cond, bsp_body, carry)
            if prog.spmv_backend == "pallas":
                # one multi-superstep launch, mailbox on chip; telemetry is
                # coarse for these rounds (totals, no per-round histograms)
                x, ch, fr, step, done, tele = carry
                x2, ch2, fr2, it, li = mega.resident_megastep_pallas(
                    x, ch, fr, cm, semiring, max_steps=max_s - enter,
                    interpret=prog.interpret)
                pairs, nsent = round_stats(ch2, cm)
                # one sweep per resident round
                tele = dict(tele, liters=tele["liters"] + li,
                            sent=tele["sent"] + nsent,
                            pairs=tele["pairs"] + pairs,
                            lsweeps=tele["lsweeps"] + it)
                carry = (x2, ch2, fr2, step + it,
                         done | ~jnp.any(ch2), tele)
            else:
                def res_body(c):
                    x, ch, fr, step, _, tele = c
                    x2, ch2, fr2, ap = mega.resident_step_semiring(
                        x, ch, fr, cm, semiring)
                    tele, nch = sem_fold(tele, step, ch2,
                                         ap.astype(jnp.int32), jnp.int32(1))
                    return x2, ch2, fr2, step + 1, nch == 0, tele

                carry = jax.lax.while_loop(cond, res_body, carry)

        x, ch, fr, steps, _, tele = carry
        state = {"x": x.reshape(p_local, v_max),
                 "changed_v": ch.reshape(p_local, v_max),
                 "frontier": fr.reshape(p_local, v_max)}
        return state, steps, tele

    def _run_phased(self, gb, num_queries: Optional[int] = None):
        """Gopher Phases: the BSP loop as K SEGMENTED while-loops, one per
        phase of the PhasedTierPlan — each segment's exchange tables are
        trace-time constants at that phase's geometry, and the (state,
        inbox, halt-vote) carry flows straight across segment boundaries,
        so the run switches geometry WITHOUT retracing or re-priming.

        A segment ends when any of three things happens:
          * the predicted switch superstep (``plan.boundaries[k]``) arrives;
          * the DEMOTION trigger fires — the observed per-pair packed
            counts fit under the NEXT phase's caps for ``DEMOTE_STREAK``
            consecutive supersteps (the frontier contracted ahead of
            prediction: jump to the narrower geometry now);
          * the global halt vote lands (a phase that quiesces before its
            boundary early-exits, and every later segment's loop runs ZERO
            iterations — the compiled segments are still traced, but cost
            nothing at run time).

        Per-superstep overflow falls back to the dense route inside the
        segment (see make_exchange 'phased'), so results are exact
        unconditionally and only the spilling phase is escalated afterwards.
        """
        prog = self.program
        Q = num_queries
        plan: PhasedTierPlan = self.tier_plan
        phases = plan.phase_plans()
        K = plan.num_phases
        bounds = plan.boundaries
        num_parts = self.pg.num_parts
        p_local = gb["vmask"].shape[0]
        ssteps = [self.make_superstep(gb, num_queries=Q, phase=k)
                  for k in range(K)]
        state0 = jax.vmap(prog.init)(gb)
        inbox0, nsent0, wire0, ex0 = self.make_exchange(
            gb, num_queries=Q, phase=0)(state0)
        cnt0 = jnp.sum(ex0["pairs"]).astype(jnp.int32)
        if self.backend == "shard_map":
            s0 = jax.lax.psum(jnp.stack([nsent0, wire0, cnt0]),
                              self.axis_name)
            nsent0, wire0, cnt0 = s0[0], s0[1], s0[2]
        # round-indexed histograms: the prime lands at slot 0 under phase 0
        tele0 = dict(
            liters=jnp.zeros((p_local,), jnp.int32),
            hist=jnp.zeros((self.max_supersteps,), jnp.int32),
            whist=jnp.zeros((self.max_supersteps + 1,),
                            jnp.int32).at[0].set(wire0),
            chist=jnp.zeros((self.max_supersteps + 1,),
                            jnp.int32).at[0].set(cnt0),
            phist=jnp.zeros((self.max_supersteps + 1,), jnp.int32),
            sent=nsent0, wire=wire0,
            # per-pair phase buckets keep the local-parts axis LEADING so
            # the shard_map out_specs reassemble them like every other
            # per-pair matrix: (v_local, K, P) -> (P, K, P)
            pairs=jnp.zeros((p_local, K, num_parts), jnp.int32
                            ).at[:, 0].add(ex0["pairs"]),
            over=jnp.zeros((p_local, K, num_parts), jnp.int32
                           ).at[:, 0].add(ex0["over"]),
            dsteps=ex0["dstep"],
            seg_end=jnp.zeros((K,), jnp.int32),
            lsweeps=jnp.int32(0), cwait=jnp.int32(0))
        if Q is not None:
            tele0["qsteps"] = jnp.zeros((Q,), jnp.int32)

        carry = (state0, inbox0, jnp.int32(0), jnp.bool_(False),
                 jnp.int32(0), tele0)
        for k in range(K):
            nlim_np = phases[k + 1].limits() if k < K - 1 else None
            sstep = ssteps[k]

            def cond(c, _k=k):
                _, _, step, done, streak, _ = c
                go = (~done) & (step < self.max_supersteps)
                if _k < K - 1:
                    # boundaries are in ROUND units (the changed-profile's
                    # index space): superstep s ships round s + 1, so the
                    # segment keeps going while that round is in-band
                    go &= (step + 1 < bounds[_k]) & (streak < DEMOTE_STREAK)
                return go

            def body(c, _k=k, _nlim=nlim_np, _sstep=sstep):
                state, inbox, step, _, streak, tele = c
                state, inbox, changed, liters, nsent, wire, ex = _sstep(
                    state, inbox, step)
                with jax.named_scope("gopher.stats"):
                    cnt = jnp.sum(ex["pairs"]).astype(jnp.int32)
                    if _nlim is None:
                        viol = jnp.int32(0)
                    else:
                        nl = jnp.asarray(_nlim)
                        v_local = ex["pairs"].shape[0]
                        if self.backend == "shard_map" and p_local < num_parts:
                            nl = jax.lax.dynamic_slice(
                                nl, (jax.lax.axis_index(self.axis_name)
                                     * v_local, 0), (v_local, num_parts))
                        else:
                            nl = nl[:v_local]
                        viol = jnp.sum((ex["pairs"] > nl).astype(jnp.int32))
                    changed_q = None
                    if Q is None:
                        nchanged = jnp.sum(changed.astype(jnp.int32))
                    else:
                        changed_q = jnp.any(changed, axis=0).astype(jnp.int32)
                        nchanged = jnp.sum(jnp.any(changed,
                                                   axis=-1).astype(jnp.int32))
                    (nchanged, nsent, wire, cnt, viol), lock, wait, \
                        changed_q = self._reduce_stats(
                            [nchanged, nsent, wire, cnt, viol], liters,
                            changed_q)
                    any_changed = (nchanged > 0 if Q is None
                                   else jnp.any(changed_q > 0))
                    # demotion streak: a dense-retried superstep's counts
                    # are real demand, so they participate like any other
                    # round
                    streak = jnp.where(viol == 0, streak + 1, jnp.int32(0))
                    new_tele = dict(
                        liters=tele["liters"] + liters,
                        hist=tele["hist"].at[step].set(nchanged),
                        whist=tele["whist"].at[step + 1].set(wire),
                        chist=tele["chist"].at[step + 1].set(cnt),
                        phist=tele["phist"].at[step + 1].set(_k),
                        sent=tele["sent"] + nsent,
                        wire=tele["wire"] + wire,
                        pairs=tele["pairs"].at[:, _k].add(ex["pairs"]),
                        over=tele["over"].at[:, _k].add(ex["over"]),
                        dsteps=tele["dsteps"] + ex["dstep"],
                        seg_end=tele["seg_end"],
                        lsweeps=tele["lsweeps"] + lock,
                        cwait=tele["cwait"] + wait)
                    if Q is not None:
                        new_tele["qsteps"] = jnp.where(changed_q > 0,
                                                       step + 1,
                                                       tele["qsteps"])
                return state, inbox, step + 1, ~any_changed, streak, new_tele

            state, inbox, step, done, streak, tele = jax.lax.while_loop(
                cond, body, carry)
            tele = dict(tele, seg_end=tele["seg_end"].at[k].set(step))
            carry = (state, inbox, step, done, jnp.int32(0), tele)

        state, _, steps, _, _, tele = carry
        return state, steps, tele

    # ---------------- drivers ----------------
    def run(self, checkpointer=None, checkpoint_every: int = 0,
            resume: bool = False, extra: Optional[dict] = None,
            superstep_budget: Optional[int] = None):
        """Run to quiescence. With a `resilience.checkpoint.Checkpointer` and
        checkpoint_every=N, the BSP loop snapshots (state, inbox, superstep)
        every N supersteps and can restart from the last committed snapshot
        after a failure (BSP makes the cut trivially consistent — paper §4.2's
        synchronization points ARE the recovery lines).

        ``extra`` carries per-run dynamic (P, ...) graph-block entries — e.g.
        ``x0`` / ``frontier0`` for an incremental resume (SemiringProgram
        with resume=True) — without invalidating the shared cached block.

        ``superstep_budget`` (checkpointed runs only) caps THIS call at N
        supersteps and snapshots at the cut, so a supervisor (Gopher
        Balance's run_with_rebalance) can interleave decisions between
        segments of one logical run and resume exactly where it stopped.
        """
        ck = checkpointer if checkpoint_every > 0 else None
        if ck is not None:
            assert not self.tracer.enabled, \
                "traced runs don't compose with checkpointing yet"
        else:
            assert superstep_budget is None, \
                "superstep_budget requires a checkpointed run"
        return self._run(None, extra, ck, checkpoint_every, resume,
                         superstep_budget)

    def run_queries(self, extra: Optional[dict] = None):
        """Run a query-batched program (``program.num_queries`` = Q) to global
        quiescence of ALL queries in ONE BSP run.

        ``extra`` carries the per-request dynamic inputs (query init values,
        PPR seed vectors, ...) as additional (P, ...) graph-block entries, so
        the compiled loop is reused across request batches of the same shape
        — only the query arrays are re-transferred.

        Returns (state, Telemetry) where state leaves are (P, v_max, Q)
        (query-trailing) and ``telemetry.query_supersteps[q]`` is the
        superstep at which query q last changed.
        """
        Q = getattr(self.program, "num_queries", None)
        assert Q is not None, "run_queries requires a query-batched program"
        return self._run(Q, extra)

    def _run(self, num_queries, extra, ck=None, every: int = 0,
             resume: bool = False, budget: Optional[int] = None):
        """One run: the cached compiled loop, or the host-stepped driver
        behind an enabled tracer or a checkpointer — closed out by _finish
        either way."""
        stepped = ck is not None or self.tracer.enabled
        gb = (self._graph_block() if stepped
              else self._gb_for_run(self._graph_block()))
        if extra:
            gb = {**gb, **{k: jnp.asarray(v) for k, v in extra.items()}}
        if stepped:
            state, steps, tele, exchange = self._run_stepped(
                gb, num_queries, ck, every, resume, budget)
        else:
            exchange = self.exchange
            with self._step("dispatch"):
                state, steps, tele = self._runner(num_queries=num_queries,
                                                  gb_example=gb)(gb)
        with self._step("download"):
            state, t = self._finish(state, steps, tele, gb, num_queries,
                                    exchange)
        self._record_run_metrics(t)
        return state, t

    def _finish(self, state, steps, tele, gb, num_queries, exchange: str):
        """Close out a run of ``exchange`` (the engine's own, or a
        checkpointed run's replay exchange, see stepped_exchange): on the
        tiered exchange, check the overflow
        record — a pair whose active slots exceeded its tier width had
        messages TRUNCATED, so the results cannot be trusted. The repair is
        a DENSE FALLBACK RETRY (bit-identical by construction) plus a tier
        escalation of the overflowed pairs, so the engine's next run — and,
        through the profile, the next graph version's plan — has the width
        this pair just demonstrated it needs.

        Phased runs never need the whole-run retry — an overflowing
        superstep already routed dense inside the loop — so the close-out
        only ESCALATES the phases that spilled (each phase's overflow
        record promotes that phase's pairs; the other phases keep their
        geometry)."""
        if exchange == "phased":
            t = self._telemetry(steps, tele, num_queries=num_queries)
            if t.spills:
                over_k = np.transpose(np.asarray(tele["over"]), (1, 0, 2))
                old = self.tier_plan
                plan = old
                for k in range(plan.num_phases):
                    if over_k[k].any():
                        plan = plan.escalate_phase(k, over_k[k] > 0)
                self.tier_plan = plan
                t.escalations = plan.escalations_from(old)
            return jax.tree.map(np.asarray, state), t
        if exchange != "tiered" or "over" not in tele:
            return (jax.tree.map(np.asarray, state),
                    self._telemetry(steps, tele, num_queries=num_queries,
                                    exchange=exchange))
        over = np.asarray(tele["over"])
        spills = int(over.sum())
        if spills == 0:
            return (jax.tree.map(np.asarray, state),
                    self._telemetry(steps, tele, num_queries=num_queries))
        old = self.tier_plan
        self.tier_plan = old.escalate(over > 0)
        tiered_wire = int(tele["wire"])
        tiered_rounds = int(steps) + 1
        with self.tracer.span("dense-retry", spills=spills):
            state2, steps2, tele2 = self._runner(num_queries=num_queries,
                                                 gb_example=gb,
                                                 exchange="dense")(gb)
        t = self._telemetry(steps2, tele2, num_queries=num_queries,
                            exchange="dense")
        t.exchange = "tiered"
        t.retried = True
        t.spills = spills
        t.escalations = self.tier_plan.escalations_from(old)
        t.pair_overflow = over
        # the profile observation comes from the ABORTED tiered attempt —
        # pair_rounds records ITS round count so consumers normalize by the
        # rounds the counts actually cover, not the dense retry's
        t.pair_slots = np.asarray(tele["pairs"])
        t.pair_rounds = tiered_rounds
        # the failed tiered attempt's geometry still crossed the wire
        t.wire_slots += tiered_wire
        D = (1 if self.backend == "local"
             else int(self.mesh.shape[self.axis_name]))
        t.bytes_on_wire += (old.schedule(D).round_bytes(num_queries)
                            * tiered_rounds)
        return jax.tree.map(np.asarray, state2), t

    def _record_run_metrics(self, t: Telemetry) -> None:
        """Gopher Scope: fold a finished run's telemetry into the metrics
        registry. Host-side and O(P²) on data the run already pulled to the
        host — it runs on every run, traced or not (there is nothing to
        disable: no compiled code is touched)."""
        m = self.metrics
        lab = {"exchange": t.exchange or self.exchange,
               "backend": self.backend}
        m.counter("engine_runs_total", lab).inc()
        m.counter("engine_supersteps_total", lab).inc(t.supersteps)
        m.counter("engine_messages_sent_total", lab).inc(t.messages_sent)
        m.counter("engine_wire_slots_total", lab).inc(t.wire_slots)
        m.counter("engine_wire_bytes_total", lab).inc(t.bytes_on_wire)
        m.counter("engine_spills_total", lab).inc(t.spills)
        m.counter("engine_escalations_total", lab).inc(t.escalations)
        if t.retried:
            m.counter("engine_dense_retries_total", lab).inc()
        m.counter("engine_dense_retry_steps_total",
                  lab).inc(t.dense_retry_steps)
        m.histogram("engine_run_supersteps", lab).observe(t.supersteps)
        if t.lockstep_sweeps is not None:
            m.histogram("engine_lockstep_sweeps").observe(t.lockstep_sweeps)
        if t.chip_wait_sweeps is not None:
            m.histogram("engine_chip_wait_sweeps").observe(
                t.chip_wait_sweeps)
        m.gauge("engine_partition_imbalance", lab).set(
            obs_skew.imbalance_score(t.local_iters))

    # ---------------- the host-stepped driver ----------------
    def stepped_exchange(self, checkpointed: bool = False) -> str:
        """The exchange a host-stepped run dispatches — the one rule every
        stepped caller reads (this engine's driver, Gopher Balance's route
        replay, Sentinel's stage verification). A traced run steps the
        engine's own exchange. A checkpointed run — and so every recovery,
        failover and migration replay — steps the COMPACT staged loop on
        'megastep'/'tiered'/'phased': the fused megastep route carries no
        staged (state, inbox) pair to snapshot, and tier-overflow repair
        and phase segmentation don't span snapshot boundaries. The results
        are the same (bitwise for idempotent ⊕) per the cross-mode
        identity tests."""
        if checkpointed and self.exchange in ("megastep", "tiered", "phased"):
            return "compact"
        return self.exchange

    def stage_fns(self, num_queries: Optional[int], phase: Optional[int],
                  exchange: str):
        """The jitted stages a host-stepped run dispatches under
        ``exchange`` (one ``phase`` of it on 'phased'), each taking the
        graph block as an argument so the jit cache keys on shapes:

          staged routes  init / sweep / pack / route — the fused loop's
                         superstep cut at make_exchange_stages' network
                         boundary (three dispatches per superstep);
          'megastep'     prep (compose the mailbox gather maps, once per
                         run) / init (flat state + the prime round's
                         logical observation) / step (the whole superstep,
                         ONE dispatch, returning its observation record) /
                         finish (flat state back to (P, v_max)).

        On shard_map every staged stage is its own shard_map'd program —
        replicated scalars (nsent, wire) are psum'd INSIDE the stage,
        per-partition arrays come back as global (P, ...) arrays — so the
        host driver sees exactly the values the fused loop's stats psum
        would have produced.

        Cached per (num_queries, phase, exchange, tier plan): repeated runs
        re-enter the same jit entries, and a tier escalation (which swaps
        self.tier_plan) rebuilds the closures."""
        plan = self.tier_plan if exchange in ("tiered", "phased") else None
        cache = self.__dict__.setdefault("_stage_cache", {})
        key = (num_queries, phase, exchange, plan)
        if key not in cache:
            cache[key] = (self._megastep_stages(num_queries)
                          if exchange == "megastep"
                          else self._staged_stages(num_queries, phase,
                                                   exchange))
        return cache[key]

    def _staged_stages(self, num_queries, phase, exchange):
        prog = self.program
        Q = num_queries
        axes = ((_VPART_AXIS,) if self.backend == "local"
                else (_VPART_AXIS, self.axis_name))

        def init_fn(gb):
            return jax.vmap(prog.init)(gb)

        def sweep_fn(gb, state, inbox, step):
            return jax.vmap(
                lambda s, i, g: prog.superstep(s, i, g, step, axes=axes),
                in_axes=(0, 0, 0), axis_name=_VPART_AXIS)(state, inbox, gb)

        def pack_fn(gb, state):
            pack, _ = self.make_exchange_stages(gb, num_queries=Q,
                                                phase=phase, exchange=exchange)
            payload, nsent, wire, extras = pack(state)
            if self.backend == "shard_map":
                s = jax.lax.psum(jnp.stack([nsent, wire]), self.axis_name)
                nsent, wire = s[0], s[1]
            return payload, nsent, wire, extras

        def route_fn(gb, payload):
            _, route = self.make_exchange_stages(gb, num_queries=Q,
                                                 phase=phase,
                                                 exchange=exchange)
            inbox, rex = route(payload)
            if self.backend == "shard_map" and "wire" in rex:
                rex = dict(rex,
                           wire=jax.lax.psum(rex["wire"], self.axis_name))
            return inbox, rex

        if self.backend == "local":
            return dict(init=jax.jit(init_fn), sweep=jax.jit(sweep_fn),
                        pack=jax.jit(pack_fn), route=jax.jit(route_fn))
        # pytree-prefix specs: parts-sharded unless provably replicated
        spec, rep = P(self.axis_name), P()
        return dict(
            init=jax.jit(compat.shard_map(
                init_fn, mesh=self.mesh, in_specs=(spec,), out_specs=spec)),
            sweep=jax.jit(compat.shard_map(
                sweep_fn, mesh=self.mesh,
                in_specs=(spec, spec, spec, rep), out_specs=spec)),
            pack=jax.jit(compat.shard_map(
                pack_fn, mesh=self.mesh, in_specs=(spec, spec),
                out_specs=(spec, rep, rep, spec))),
            route=jax.jit(compat.shard_map(
                route_fn, mesh=self.mesh, in_specs=(spec, spec),
                out_specs=(spec, rep))))

    def _megastep_stages(self, num_queries):
        # the composed-mailbox dict carries static ints (num_parts/v_max/
        # cap/n): they are stripped before crossing the jit boundary and
        # re-injected here, so the arrays flow device-to-device without
        # re-composition and the ints never become tracers
        prog = self.program
        kind = prog.megastep_kind
        Q = num_queries
        p_local = self.pg.num_parts
        v_max = self.pg.v_max
        statics = dict(num_parts=p_local, v_max=v_max,
                       cap=self.pg.mailbox_cap, n=p_local * v_max)

        if kind == "pagerank":
            def init_fn(gb, cma):
                cm = dict(cma, **statics)
                st = jax.vmap(prog.init)(gb)
                pairs0, nsent0 = mega.round_stats(None, cm)
                return ((st["r"].reshape(-1), jnp.float32(jnp.inf)),
                        dict(pairs=pairs0, nsent=nsent0))

            def step_fn(gb, cma, flat, step):
                cm = dict(cma, **statics)
                r, _ = flat
                deg = gb["out_degree"].astype(jnp.float32).reshape(-1)
                telep = (jax.vmap(prog.teleport_fn)(gb).reshape(-1)
                         if prog.teleport_fn is not None
                         else 1.0 / prog.n_global)
                r2, delta, chg = mega.megastep_pagerank(
                    r, cm, deg, telep, prog.n_global, prog.damping,
                    prog.num_iters, step)
                pairs, nsent = mega.round_stats(None, cm)
                return (r2, delta), dict(
                    changed=jnp.broadcast_to(chg, (p_local,)),
                    li=jnp.ones((p_local,), jnp.int32), pairs=pairs,
                    nsent=nsent, sweeps=jnp.int32(1))

            def finish(flat):
                return {"r": flat[0].reshape(p_local, v_max),
                        "delta": jnp.full((p_local,), flat[1])}
        else:
            semiring = prog.semiring
            unroll = prog.fixpoint_unroll
            batched = kind == "batched_semiring"
            mk = (mega.megastep_semiring_batched if batched
                  else functools.partial(mega.megastep_semiring,
                                         backend=prog.spmv_backend,
                                         interpret=prog.interpret))
            tail = (Q,) if batched else ()
            names = ("x", "changed_v", "frontier")

            def init_fn(gb, cma):
                cm = dict(cma, **statics)
                st = jax.vmap(prog.init)(gb)
                flat = tuple(st[k].reshape((-1,) + tail) for k in names)
                pairs0, nsent0 = mega.round_stats(flat[1], cm)
                return flat, dict(pairs=pairs0, nsent=nsent0)

            def step_fn(gb, cma, flat, step):
                cm = dict(cma, **statics)
                x2, ch2, fl, li, sweeps = mk(*flat, cm, semiring,
                                             unroll=unroll)
                pairs, nsent = mega.round_stats(ch2, cm)
                return (x2, ch2, fl), dict(
                    changed=jnp.any(ch2.reshape((p_local, v_max) + tail),
                                    axis=1),
                    li=li, pairs=pairs, nsent=nsent, sweeps=sweeps)

            def finish(flat):
                return {k: v.reshape((p_local, v_max) + tail)
                        for k, v in zip(names, flat)}

        return dict(prep=functools.partial(
                        mega.compose_mailbox_arrays,
                        adjacency=("binned" if kind == "batched_semiring"
                                   else "full")),
                    init=jax.jit(init_fn), step=jax.jit(step_fn),
                    finish=finish)

    def restore_cut(self, ck, gb, step: int):
        """Snapshot ``step`` of checkpointer ``ck`` — the (state, inbox)
        cut a checkpointed run saved — placed back on this engine's
        partitions (sharded over the mesh axis on shard_map). Returns
        (state, inbox, superstep)."""
        snap_like = {
            "state": jax.eval_shape(lambda g: jax.vmap(self.program.init)(g),
                                    gb),
            "inbox": jax.ShapeDtypeStruct((self.pg.num_parts, self.pg.v_max),
                                          np.float32),
        }
        shardings = None
        if self.backend == "shard_map":
            sh = jax.sharding.NamedSharding(self.mesh, P(self.axis_name))
            shardings = jax.tree.map(lambda _: sh, snap_like)
        snap, step = ck.restore(snap_like, step=step, shardings=shardings)
        return snap["state"], snap["inbox"], int(step)

    def _run_stepped(self, gb, num_queries: Optional[int], ck=None,
                     every: int = 0, resume: bool = False,
                     budget: Optional[int] = None):
        """The host-stepped BSP driver: the fused while_loop unrolled into
        per-superstep dispatches of stage_fns, for every run the compiled
        loop cannot serve —
          * an ENABLED tracer: every run → phase → superstep → {sweep,
            pack, exchange, halt-vote} span is clocked (a single
            'megastep' child on the fused route; resident narrow-phase
            mode is not entered, its launch hides its rounds);
          * a checkpointer: (state, inbox) is snapshotted every ``every``
            supersteps and at a ``budget`` cut, and ``resume`` restarts
            from the newest snapshot that passes checksum verification
            (Checkpointer.latest_good_step) with no prime. Gopher Balance's
            migrations and Shield's failover run here.
        Semantics are the fused loop's — the same stage math, the same halt
        rule (a host read of the global changed flags), the same telemetry
        layout (_StepTele) — so _finish/_telemetry are shared. Gopher
        Shield's fault sites fire here, never inside compiled code:
        ``engine.superstep`` once per superstep, ``exchange.route`` before
        every staged route, the prime's included.

        Returns (state, supersteps, tele, the exchange stepped)."""
        tr = self.tracer
        Q = num_queries
        ex = self.stepped_exchange(checkpointed=ck is not None)
        fused = ex == "megastep"
        phased = ex == "phased"
        K = self.tier_plan.num_phases if phased else 1
        bounds = self.tier_plan.boundaries if phased else ()
        # the next phase's caps: the demotion trigger's yardstick
        nlims = [np.asarray(p.limits())
                 for p in self.tier_plan.phase_plans()[1:]] if phased else []
        nlims.append(None)
        D = (1 if self.backend == "local"
             else int(self.mesh.shape[self.axis_name]))
        part_verts = tuple(int(x) for x in
                           np.asarray(self.pg.vmask, bool).sum(1))
        acc = _StepTele(ex, self.pg.num_parts, self.max_supersteps, K, Q, D)
        stages = []

        def route(fns, state, rnd, span):
            with span("pack"):
                payload, nsent, wire, pex = fns["pack"](gb, state)
                tr.sync(payload)
            _faults.fire("exchange.route", step=rnd, backend=self.backend)
            with span("exchange"):
                inbox, rex = fns["route"](gb, payload)
                tr.sync(inbox)
            return inbox, {**pex, "nsent": nsent, "wire": wire, **rex}

        def advance(carry, step, fns):
            """One superstep: (carry, its observation record)."""
            if fused:
                with tr.span("megastep"):
                    carry, obs = fns["step"](gb, cma, carry, jnp.int32(step))
                    tr.sync(obs["li"])
                return carry, obs
            state, inbox = carry
            with tr.span("sweep"):
                state, changed, li = fns["sweep"](gb, state, inbox,
                                                  jnp.int32(step))
                tr.sync(changed)
            inbox, obs = route(fns, state, step + 1, tr.span)
            return (state, inbox), dict(obs, changed=changed, li=li)

        run_span = tr.span("run", exchange=ex, backend=self.backend,
                           queries=Q or 0)
        with tr.profile_ctx(), run_span:
            for k in range(K):
                # the plan span charges stage construction to its phase
                with tr.span("plan", phase=k, exchange=ex,
                             backend=self.backend):
                    stages.append(self.stage_fns(Q, k if phased else None,
                                                 ex))
            good = ck.latest_good_step() if ck is not None and resume else None
            if good is not None:
                state, inbox, start = self.restore_cut(ck, gb, good)
                carry = (state, inbox)
            else:
                start, fns = 0, stages[0]
                with tr.span("init"):
                    if fused:
                        cma = fns["prep"](gb)
                        carry, obs = fns["init"](gb, cma)
                        tr.sync(obs["pairs"])
                    else:
                        state = tr.sync(fns["init"](gb))
                with tr.span("prime") as sp:
                    # the fused route routes no prime: the first megastep
                    # delivers round 0's sends, so the round records only
                    # the logical observation
                    if not fused:
                        inbox, obs = route(fns, state, 0,
                                           lambda _: contextlib.nullcontext())
                        carry = (state, inbox)
                    wire, nsent = acc.fold_round(0, 0, obs)
                    sp.set(wire=wire, nsent=nsent)
                tr.count("dispatches", 2 if fused else 3)

            step, done = start, False
            for k in range(K):
                streak = 0
                with tr.span("phase", index=k,
                             boundary=int(bounds[k]) if k < K - 1 else -1):
                    while not done and step < self.max_supersteps and (
                            budget is None or step - start < budget):
                        if nlims[k] is not None and (
                                step + 1 >= bounds[k]
                                or streak >= DEMOTE_STREAK):
                            break
                        with tr.span("superstep", step=step) as ss:
                            t0 = time.perf_counter()
                            eff = _faults.fire(
                                "engine.superstep", step=step,
                                backend=self.backend, part_verts=part_verts,
                                num_devices=D)
                            carry, obs = advance(carry, step, stages[k])
                            with tr.span("halt-vote"):
                                nchanged, any_changed = acc.fold_step(step,
                                                                      obs)
                                wire, nsent = acc.fold_round(step + 1, k,
                                                             obs)
                                if nlims[k] is not None:
                                    viol = (np.asarray(obs["pairs"])
                                            > nlims[k]).any()
                                    streak = 0 if viol else streak + 1
                            tr.count("dispatches", 1 if fused else 3)
                            acc.charge(time.perf_counter() - t0, eff)
                            ss.set(changed=nchanged, wire=wire, nsent=nsent)
                        step += 1
                        done = not any_changed
                        if ck is not None and (
                                done or step >= self.max_supersteps
                                or (step - start) % every == 0
                                or step - start == budget):
                            ck.save({"state": carry[0], "inbox": carry[1]},
                                    step)
                acc.seg_end[k] = step
            # after a resume the exchange rounds cover only THIS process's
            # supersteps: no prime ran, earlier ones shipped in another
            tele = acc.out(rounds=step - start + (1 if good is None else 0))
            run_span.set(supersteps=step, wire_slots=tele["wire"])
        state = stages[0]["finish"](carry) if fused else carry[0]
        return state, step, tele, ex

    def _telemetry(self, steps, tele, num_queries: Optional[int] = None,
                   exchange: Optional[str] = None) -> Telemetry:
        steps = int(steps)
        exchange = exchange or self.exchange
        wire = int(tele["wire"]) if "wire" in tele else 0
        # supersteps + inbox prime; a resumed stepped run reports its own
        rounds = int(tele.get("rounds", steps + 1))
        D = (1 if self.backend == "local"
             else int(self.mesh.shape[self.axis_name]))
        phased = exchange == "phased" and "phist" in tele
        if phased:
            # per-round geometry varies: charge the routed value slots per
            # round (wire already totals them, dense-retried rounds at
            # dense geometry) plus each phase's index lanes for its rounds
            # (a slight overcount on retried rounds — dense ships no ids).
            # phist is round-indexed, so the prime (round 0, phase 0) is
            # already in the bincount.
            K = self.tier_plan.num_phases
            phist = np.asarray(tele["phist"])[:steps + 1]
            scheds = [p.schedule(D) for p in self.tier_plan.phase_plans()]
            rounds_k = np.bincount(phist, minlength=K)
            q = num_queries or 1
            bytes_on_wire = int(
                wire * 4 * q
                + sum(scheds[k].round_index_slots() * int(rounds_k[k]) * 4
                      for k in range(K)))
        elif exchange == "tiered":
            bytes_on_wire = (self.tier_plan.schedule(D)
                             .round_bytes(num_queries) * rounds)
        elif exchange == "megastep":
            # fused route: messages move through on-chip gathers, never a
            # routed buffer — the LOGICAL observation (pairs/chist) still
            # feeds the tier profiles, but no bytes hit a wire
            bytes_on_wire = 0
        else:
            bytes_on_wire = Telemetry.model_bytes(
                wire, self.pg.num_parts, rounds=rounds,
                cap=self.pg.mailbox_cap, num_queries=num_queries,
                compact=exchange == "compact")
        pair_over = (np.asarray(tele["over"]) if "over" in tele else None)
        pair_slots = np.asarray(tele["pairs"]) if "pairs" in tele else None
        t = Telemetry(
            supersteps=steps,
            local_iters=np.asarray(tele["liters"]).reshape(-1),
            changed_hist=np.asarray(tele["hist"])[:steps],
            messages_sent=int(tele["sent"]) if np.ndim(tele["sent"]) == 0 else int(np.max(tele["sent"])),
            query_supersteps=(np.asarray(tele["qsteps"])
                              if "qsteps" in tele else None),
            wire_hist=(np.asarray(tele["whist"])[:steps + 1]
                       if "whist" in tele else None),
            wire_slots=wire,
            bytes_on_wire=bytes_on_wire,
            exchange=exchange,
            count_hist=(np.asarray(tele["chist"])[:steps + 1]
                        if "chist" in tele else None),
        )
        if "psec" in tele:
            t.part_seconds = np.asarray(tele["psec"], np.float64).reshape(-1)
        if "lsweeps" in tele:
            t.lockstep_sweeps = int(tele["lsweeps"])
        if "cwait" in tele:
            t.chip_wait_sweeps = int(tele["cwait"])
        if phased:
            # phase buckets travel parts-leading (P, K, P); report (K, P, P)
            by_phase = np.transpose(pair_slots, (1, 0, 2))
            over_k = np.transpose(pair_over, (1, 0, 2))
            t.phase_pair_slots = by_phase
            t.pair_slots = by_phase.sum(0)
            t.pair_overflow = over_k.sum(0)
            t.pair_rounds = rounds
            t.spills = int(over_k.sum())
            t.phase_hist = phist
            whist = np.asarray(tele["whist"])[:steps + 1]
            seg_end = np.asarray(tele["seg_end"])
            t.phase_switch_steps = np.unique(seg_end[:-1][seg_end[:-1] < steps])
            pw = np.zeros(K, np.int64)
            np.add.at(pw, phist, whist)          # round 0 (the prime) included
            t.phase_wire = pw
            t.dense_retry_steps = int(tele["dsteps"])
        else:
            t.pair_slots = pair_slots
            t.pair_rounds = rounds if pair_slots is not None else 0
            t.pair_overflow = pair_over
            t.spills = int(pair_over.sum()) if pair_over is not None else 0
        return t

    def _runner(self, num_queries: Optional[int] = None, gb_example=None,
                exchange: Optional[str] = None):
        """The compiled BSP loop, cached so repeated runs hit the same jit
        entry instead of re-tracing.

        The cache is MODULE-level and keyed on everything the trace depends
        on — program (frozen dataclass; init_fn compares by identity),
        backend/mesh, loop bounds, partition-batch shapes, and the gb
        entry signature (shard_map in_specs are baked from the block
        structure) — so SHORT-LIVED ENGINES SHARE COMPILED LOOPS: a
        temporal-serving fleet that rebuilds its engines after every
        apply_delta re-enters the compiled loop as long as the delta didn't
        change any padded shape, instead of paying a full XLA compile per
        graph version.

        A PER-ENGINE memo sits in front of it: the module key's signature
        walk (sorted shape/dtype tuples over ~a hundred block entries)
        costs real per-run time on millisecond-scale warm runs, and for a
        given engine the resolved runner only varies with (Q, exchange,
        block key set, tier plan) — the plan compared by IDENTITY, so a
        post-run escalation that swaps self.tier_plan misses the memo and
        re-resolves."""
        exchange = exchange or self.exchange
        tier_plan = (self.tier_plan
                     if exchange in ("tiered", "phased", "megastep")
                     else None)
        mkey = (num_queries, exchange,
                None if gb_example is None else frozenset(gb_example))
        hit = self._runner_memo.get(mkey)
        if hit is not None and hit[0] is tier_plan:
            return hit[1]
        if tier_plan is not None and getattr(self, "validate", False):
            # a non-static plan would blow up the cache-key hash below
            # with a bare TypeError — vet it first so the failure names
            # the offending field instead
            from repro.analysis import assert_clean, check_plan_static
            assert_clean(check_plan_static(tier_plan))
        gb_sig = (tuple(sorted((k, v.shape, str(v.dtype))
                               for k, v in gb_example.items()))
                  if gb_example is not None else None)
        key = (self.program, self.backend, exchange, tier_plan, num_queries,
               self.max_supersteps, self.axis_name, self.mesh,
               self.pg.num_parts, self.pg.v_max, self.pg.mailbox_cap, gb_sig)
        cached = _RUNNER_CACHE.get(key)
        if cached is None:
            # build the runner on a DETACHED engine holding only the scalars
            # the trace reads (graph data flows in through the gb argument):
            # a cached closure over `self` would pin this engine's device
            # graph block — and its host pg — for the cache entry's lifetime
            slim = GopherEngine.__new__(GopherEngine)
            slim.pg = _PgScalars(num_parts=self.pg.num_parts,
                                 v_max=self.pg.v_max,
                                 mailbox_cap=self.pg.mailbox_cap)
            slim.program = self.program
            slim.backend = self.backend
            slim.exchange = exchange
            slim.tier_plan = tier_plan
            slim.mesh = self.mesh
            slim.axis_name = self.axis_name
            slim.max_supersteps = self.max_supersteps
            slim._gb = None
            if getattr(self, "validate", False):
                # Gopher Sentinel gate: verify the exact loop about to be
                # compiled (the slim engine IS that loop's closure) before
                # it can enter the cache. Raises SentinelError on findings.
                from repro.analysis import validate_engine
                validate_engine(slim, num_queries=num_queries,
                                gb_example=gb_example)
            if self.backend == "local":
                loop = functools.partial(slim._run_batched,
                                         num_queries=num_queries)
                # the compiled module's name, and the trace's: jit_gopher_<exchange>
                loop.__name__ = f"gopher_{exchange}"
                cached = jax.jit(loop)
            else:
                cached = slim._sharded_fn(
                    num_queries=num_queries, gb_example=gb_example)
            if len(_RUNNER_CACHE) >= _RUNNER_CACHE_CAP:
                _RUNNER_CACHE.pop(next(iter(_RUNNER_CACHE)))
            _RUNNER_CACHE[key] = cached
        self._runner_memo[mkey] = (tier_plan, cached)
        return cached

    def _sharded_fn(self, num_queries: Optional[int] = None, gb_example=None):
        spec = P(self.axis_name)
        rep = P()

        def body(gb_shard):
            state, steps, tele = self._run_batched(gb_shard,
                                                   num_queries=num_queries)
            return state, steps, tele
        body.__name__ = f"gopher_{self.exchange}"

        gb_shapes = (graph_block(self.pg, as_spec=True) if gb_example is None
                     else {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                           for k, v in gb_example.items()})
        gb_spec = jax.tree.map(lambda _: spec, gb_shapes)
        # state leaves shard over parts; steps + hist + sent (+ per-query
        # qsteps, already psum'd) are replicated; liters shard over parts.
        state_spec = jax.tree.map(lambda _: spec,
                                  jax.eval_shape(lambda g: jax.vmap(self.program.init)(g),
                                                 gb_shapes))
        tele_spec = dict(liters=spec, hist=rep, whist=rep, sent=rep, wire=rep,
                         lsweeps=rep, cwait=rep)
        # per-pair wire telemetry shards over parts like liters: each
        # device owns its local source rows of the (P, P) matrices (phased:
        # of the (P, K, P) per-phase buckets)
        if self.exchange in ("compact", "tiered", "phased"):
            tele_spec["pairs"] = spec
            tele_spec["chist"] = rep
        if self.exchange in ("tiered", "phased"):
            tele_spec["over"] = spec
        if self.exchange == "phased":
            tele_spec["phist"] = rep
            tele_spec["seg_end"] = rep
            tele_spec["dsteps"] = rep
        if num_queries is not None:
            tele_spec["qsteps"] = rep
        out_specs = (state_spec, rep, tele_spec)
        f = compat.shard_map(body, mesh=self.mesh, in_specs=(gb_spec,),
                             out_specs=out_specs)
        return jax.jit(f)

    # ---------------- lowering entry point (dry-run / roofline) ----------------
    def lowerable_superstep(self):
        """A (fn, example_specs) pair: one shard_map'd BSP superstep suitable
        for ``jax.jit(fn).lower(*specs).compile()`` at production mesh scale.
        Used by launch/dryrun.py for the paper-side roofline."""
        assert self.backend == "shard_map"
        spec = P(self.axis_name)
        gb_specs = graph_block(self.pg, as_spec=True)
        gb_pspec = jax.tree.map(lambda _: spec, gb_specs)
        prog = self.program

        state_shapes = jax.eval_shape(
            lambda g: jax.vmap(prog.init)(g), gb_specs)
        state_pspec = jax.tree.map(lambda _: spec, state_shapes)
        inbox_spec = jax.ShapeDtypeStruct((self.pg.num_parts, self.pg.v_max), np.float32)

        def one_step(gb, state, inbox, step):
            sstep = self.make_superstep(gb)
            st, ib, ch, li, ns, wire, ex = sstep(state, inbox, step)
            return st, ib, ch

        f = compat.shard_map(one_step, mesh=self.mesh,
                             in_specs=(gb_pspec, state_pspec, spec, P()),
                             out_specs=(state_pspec, spec, spec))
        step_spec = jax.ShapeDtypeStruct((), np.int32)
        return f, (gb_specs, state_shapes, inbox_spec, step_spec)
