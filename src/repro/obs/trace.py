"""Gopher Scope: lightweight host-side span tracing.

The engine's BSP loop is normally ONE compiled ``lax.while_loop`` — nothing
host-side can see where a run's time goes, which is exactly the blind spot
ROADMAP's Gopher Hot (plan-pass overhead at small frontiers) and Gopher
Balance (straggler attribution) both hit. A :class:`Tracer` gives the host
a nested-span clock:

    run → phase → superstep → {plan, pack, exchange, sweep, halt-vote}

with wall-clock durations, per-span attributes (dispatch counts, wire
slots, changed counts), and three export formats:

  * ``chrome_trace()`` — Chrome-trace / Perfetto JSON (``ph: "X"`` complete
    events; load in ``ui.perfetto.dev`` or ``chrome://tracing``);
  * ``jsonl()`` / ``write_jsonl()`` — one event per line for ad-hoc grep;
  * ``Span`` objects directly (``tracer.spans``) for the text timeline in
    ``launch/scope.py``.

Cost model — the part that must hold for the engine to thread a tracer
through its dispatch points unconditionally:

  * DISABLED (``Tracer(enabled=False)`` or the module ``NOOP`` singleton):
    ``span()`` returns one shared no-op context manager; entering/exiting
    it is two attribute-free method calls and no allocation. The engine
    additionally never switches off the compiled fused loop unless the
    tracer is enabled, so the hot path keeps zero host syncs inside
    compiled loops.
  * ENABLED: each span costs one ``perf_counter_ns`` pair and one small
    object append. ``boundary_sync=True`` additionally calls
    ``jax.block_until_ready`` on stage outputs so per-stage wall-clock is
    honest (otherwise a span measures dispatch time and the halt-vote
    span — the host read of the vote — absorbs the device queue).

``jax_profiler_dir`` arms the optional device-side capture: the run span
wraps itself in ``jax.profiler.trace`` so a Perfetto-compatible XLA trace
lands next to the host spans.

The engine's own host steps go through :class:`step`, which is always on
and never switches drivers: a ``gopher.<name>`` span on the profiler's
clock (``jax.profiler.TraceAnnotation``, which records nothing unless a
profiler is capturing), a sample of the ``gopher_span_seconds{span=<name>}``
histogram, and a span in the tracer when one is enabled. Its device half
is :func:`op_stages`: the ``gopher.*`` named scopes of the compiled BSP
loops, read back from their HLO metadata, per instruction.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import re
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from repro.obs.metrics import default_registry

__all__ = ["Span", "Tracer", "NOOP", "get_tracer", "set_tracer",
           "validate_chrome_trace", "step", "op_stages", "hlo_stages",
           "SPAN_PREFIX", "SPAN_SECONDS"]

#: every program span and device stage name starts with this
SPAN_PREFIX = "gopher."
#: the histogram every :class:`step` observes its seconds into
SPAN_SECONDS = "gopher_span_seconds"


@dataclasses.dataclass
class Span:
    """One closed span. Times are ns from the tracer's epoch."""
    name: str
    t0_ns: int
    dur_ns: int
    depth: int
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_chrome(self) -> dict:
        return {"name": self.name, "ph": "X", "pid": 0, "tid": 0,
                "ts": self.t0_ns / 1e3, "dur": self.dur_ns / 1e3,
                "cat": "gopher", "args": self.args}


class _NoopSpan:
    """Shared no-op context manager: the disabled tracer's entire cost."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kw):                      # attribute writes vanish too
        return self


_NOOP_SPAN = _NoopSpan()


class _LiveSpan:
    __slots__ = ("tracer", "name", "t0_ns", "depth", "args")

    def __init__(self, tracer: "Tracer", name: str, args: Dict[str, Any]):
        self.tracer = tracer
        self.name = name
        self.args = args
        self.t0_ns = 0
        self.depth = 0

    def __enter__(self):
        t = self.tracer
        self.depth = len(t._stack)
        t._stack.append(self)
        self.t0_ns = time.perf_counter_ns() - t._epoch_ns
        return self

    def __exit__(self, *exc):
        now = time.perf_counter_ns() - self.tracer._epoch_ns
        top = self.tracer._stack.pop()
        assert top is self, f"span {self.name!r} closed out of order"
        self.tracer.spans.append(Span(name=self.name, t0_ns=self.t0_ns,
                                      dur_ns=now - self.t0_ns,
                                      depth=self.depth, args=self.args))
        return False

    def set(self, **kw):
        """Attach attributes mid-span (wire counts known only after the
        stage ran)."""
        self.args.update(kw)
        return self


class Tracer:
    """Nested-span tracer. ``enabled=False`` degenerates every call to the
    shared no-op span — the engine can hold a tracer unconditionally."""

    def __init__(self, enabled: bool = True, boundary_sync: bool = False,
                 jax_profiler_dir: Optional[str] = None):
        self.enabled = enabled
        self.boundary_sync = boundary_sync
        self.jax_profiler_dir = jax_profiler_dir
        self.spans: List[Span] = []
        self.counts: Dict[str, int] = {}
        self._stack: List[_LiveSpan] = []
        self._epoch_ns = time.perf_counter_ns()

    # ---------------- recording ----------------
    def span(self, name: str, **args):
        if not self.enabled:
            return _NOOP_SPAN
        return _LiveSpan(self, name, args)

    def count(self, name: str, n: int = 1) -> None:
        """Dispatch counters (host-side calls into jit'd stages)."""
        if self.enabled:
            self.counts[name] = self.counts.get(name, 0) + n

    def sync(self, x):
        """Boundary mode: block on a stage's outputs so the enclosing span's
        wall-clock covers device execution, not just dispatch. Identity when
        boundary_sync is off."""
        if self.enabled and self.boundary_sync and x is not None:
            import jax
            jax.block_until_ready(x)
        return x

    def profile_ctx(self):
        """The optional device-side jax.profiler capture around a run span
        (no-op context unless ``jax_profiler_dir`` was armed)."""
        if self.enabled and self.jax_profiler_dir:
            import jax
            return jax.profiler.trace(self.jax_profiler_dir)
        import contextlib
        return contextlib.nullcontext()

    # ---------------- invariants ----------------
    @property
    def balanced(self) -> bool:
        """True iff every opened span has been closed."""
        return not self._stack

    def open_spans(self) -> List[str]:
        return [s.name for s in self._stack]

    # ---------------- export ----------------
    def chrome_trace(self) -> dict:
        """Chrome-trace JSON object (Perfetto-loadable)."""
        return {
            "displayTimeUnit": "ms",
            "traceEvents": [s.to_chrome() for s in self.spans],
            "otherData": {"format": "gopher-scope-v1",
                          "counts": dict(self.counts)},
        }

    def write_chrome_trace(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)
        return path

    def jsonl(self) -> str:
        lines = [json.dumps({"name": s.name, "t0_us": s.t0_ns / 1e3,
                             "dur_us": s.dur_ns / 1e3, "depth": s.depth,
                             "args": s.args})
                 for s in self.spans]
        return "\n".join(lines)

    def write_jsonl(self, path: str) -> str:
        with open(path, "w") as f:
            f.write(self.jsonl() + ("\n" if self.spans else ""))
        return path

    def clear(self) -> None:
        assert self.balanced, f"clear with open spans: {self.open_spans()}"
        self.spans.clear()
        self.counts.clear()
        self._epoch_ns = time.perf_counter_ns()


#: the module no-op tracer — what the engine holds when no tracer is given.
NOOP = Tracer(enabled=False)

_default: Tracer = NOOP


def get_tracer() -> Tracer:
    """The process default tracer (NOOP unless set_tracer armed one)."""
    return _default


def set_tracer(tracer: Optional[Tracer]) -> Tracer:
    """Install (or, with None, disarm) the process default tracer."""
    global _default
    _default = tracer if tracer is not None else NOOP
    return _default


# ---------------- program spans on the profiler's clock ----------------

class step:
    """A host step of the program, as a context manager: the span
    ``gopher.<name>`` on the profiler's clock, one sample of
    ``gopher_span_seconds{span=<name>}`` in ``metrics`` (default: the
    process registry), and a span in ``tracer`` (default: the process
    tracer) when that is enabled. It syncs nothing and switches nothing."""
    __slots__ = ("name", "tracer", "metrics", "_ann", "_span", "_t0")

    def __init__(self, name: str, tracer: Optional[Tracer] = None,
                 metrics=None):
        self.name = name
        self.tracer = tracer
        self.metrics = metrics

    def __enter__(self):
        self._ann = TraceAnnotation(SPAN_PREFIX + self.name)
        self._ann.__enter__()
        tr = self.tracer if self.tracer is not None else _default
        self._span = (tr.span(SPAN_PREFIX + self.name) if tr.enabled
                      else _NOOP_SPAN)
        self._span.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self._t0
        self._span.__exit__(*exc)
        self._ann.__exit__(*exc)
        reg = self.metrics if self.metrics is not None else default_registry()
        reg.histogram(SPAN_SECONDS, {"span": self.name}).observe(dt / 1e9)
        return False


# ---------------- device stages of the compiled loops ----------------

_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)", re.M)
_HLO_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?"
                     r"metadata=\{[^}]*?op_name=\"([^\"]*)\"", re.M)
# a scope traced under a transform reads ``vmap(gopher.sweep)``
_STAGE = re.compile(r"^(?:\w+\()*(" + re.escape(SPAN_PREFIX) + r"[\w\-]+)\)*$")


def hlo_stages(hlo_text: str):
    """``(module name, {instruction: stage})`` of one compiled module's
    HLO text: an instruction's stage is the innermost ``gopher.*``
    component of its ``op_name`` metadata (a fusion carries its root's),
    read through the transforms it was traced under (``vmap(gopher.sweep)``,
    as the staged loops' vmapped partitions name their fixpoint);
    instructions outside every named stage are left out."""
    m = _HLO_MODULE.search(hlo_text)
    stages = {}
    for name, op_name in _HLO_OP.findall(hlo_text):
        scopes = [s.group(1) for s in map(_STAGE.match, op_name.split("/"))
                  if s]
        if scopes:
            stages[name] = scopes[-1]
    return (m.group(1) if m else ""), stages


def op_stages(cache: Optional[dict] = None) -> Dict[str, Dict[str, str]]:
    """``{module name: {HLO instruction: stage}}`` for every compiled BSP
    loop in ``cache`` (default: the engine's runner cache), under the
    module and instruction names a profiler trace labels device ops with.

    Each loop is lowered again from its cache key's shapes (and the
    mesh's sharding on the shard_map backend), identical lowerings are
    compiled once, and the stages are read from the compiled HLO's
    metadata. It compiles, so call it off the clock. Where two modules of
    one name disagree on an instruction, the instruction is left out."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    if cache is None:
        from repro.core import engine
        cache = engine._RUNNER_CACHE
    seen, out, clash = set(), {}, set()
    for key, runner in list(cache.items()):
        backend, mesh, axis_name, gb_sig = key[1], key[7], key[6], key[-1]
        if gb_sig is None:
            continue
        sharding = (NamedSharding(mesh, PartitionSpec(axis_name))
                    if backend == "shard_map"
                    and isinstance(mesh, jax.sharding.Mesh) else None)
        spec = {k: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
                for k, shape, dtype in gb_sig}
        lowered = runner.lower(spec)
        digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()
        if digest in seen:
            continue
        seen.add(digest)
        module, stages = hlo_stages(lowered.compile().as_text())
        merged = out.setdefault(module, {})
        for instr, stage in stages.items():
            if merged.get(instr, stage) != stage:
                clash.add((module, instr))
            merged[instr] = stage
    for module, instr in clash:
        out[module].pop(instr, None)
    return out


# ---------------- schema validation (CI smoke) ----------------

def validate_chrome_trace(obj: dict) -> None:
    """Assert ``obj`` is a structurally valid gopher-scope Chrome trace:
    the envelope keys exist, every event is a complete ('X') event with
    numeric ts/dur, and span nesting is consistent (children lie inside
    their parents). Raises AssertionError with a pointed message."""
    assert isinstance(obj, dict), "trace must be a JSON object"
    assert "traceEvents" in obj, "missing traceEvents"
    evs = obj["traceEvents"]
    assert isinstance(evs, list) and evs, "traceEvents empty"
    for i, e in enumerate(evs):
        for k in ("name", "ph", "ts", "dur", "pid", "tid"):
            assert k in e, f"event {i} missing {k!r}"
        assert e["ph"] == "X", f"event {i}: ph {e['ph']!r} != 'X'"
        assert isinstance(e["ts"], (int, float)), f"event {i}: ts not numeric"
        assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0, \
            f"event {i}: bad dur"
    # nesting: sort by start; a later-starting span either nests inside or
    # begins after every currently-open span (no partial overlap on a tid)
    spans = sorted(((e["ts"], e["ts"] + e["dur"], e["name"]) for e in evs),
                   key=lambda s: (s[0], -s[1]))
    stack: list = []
    eps = 1e-3   # µs slack: ns->µs rounding in the exporter
    for t0, t1, name in spans:
        while stack and t0 >= stack[-1][1] - eps:
            stack.pop()
        assert not stack or t1 <= stack[-1][1] + eps, \
            f"span {name!r} [{t0},{t1}] overlaps parent " \
            f"{stack[-1][2]!r} [{stack[-1][0]},{stack[-1][1]}]"
        stack.append((t0, t1, name))
