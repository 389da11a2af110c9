"""From a profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain intervals: each device's operations (name, start, end in ns) and the
host's spans. ``reduce`` turns those into, per device, the busy time (the
union of its operations' intervals inside the window), the time of its
collective operations, the operations that took most time, and the
longest idle gaps, each labelled by what the host was doing meanwhile.
The two are apart so that ``reduce`` can be checked on intervals made by
hand and ``extract`` on a trace recorded on the chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
# HLO opcodes of collectives; their "-start"/"-done" halves count too
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "collective-broadcast",
               "ragged-all-to-all")
BENCH_PREFIX = "bench."
# gaps shorter than this are not attributed to a host span one by one
SHORT_GAP_NS = 10_000
SHORT_GAP_LABEL = "(gaps under 10 us)"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def extract(path: str) -> dict:
    """``{"devices": {id: [(label, opcode, start_ns, end_ns), ...]},
    "host": [(name, start_ns, end_ns), ...]}``. A device op's label is
    its module, HLO instruction, opcode and result type
    (``jit_f:fusion.27 fusion f32[524288]``)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name.split("(")[0])
                          for ev in lines.get(MODULES_LINE, []))
            starts = [x[0] for x in mods]
            ops = []
            for ev in lines.get(OPS_LINE, []):
                name, opcode, typ = parse_hlo(ev.name)
                i = bisect.bisect_right(starts, ev.start_ns) - 1
                mod = mods[i][2] + ":" if i >= 0 and ev.start_ns < mods[i][1] else ""
                ops.append((f"{mod}{name} {opcode} {typ}".rstrip(), opcode,
                            ev.start_ns, ev.start_ns + ev.duration_ns))
            devices[int(m.group(1))] = ops
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                            for ev in line.events)
    return {"devices": devices, "host": host}


def parse_hlo(text: str):
    """(instruction, opcode, result type) of an op event's HLO text,
    ``%fusion.27 = f32[524288]{0:T(1024)} fusion(...), ...``; a tuple
    result reads ``tuple``."""
    head, sep, rest = text.partition(" = ")
    name = head.strip().lstrip("%")
    if not sep:
        return name, name, ""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        typ, rest = "tuple", rest[i + 1:].lstrip()
    else:
        typ, _, rest = rest.partition(" ")
        typ = typ.split("{")[0]
    return name, rest.split("(")[0], typ


def _union(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def is_collective(opcode: str) -> bool:
    return opcode.startswith(COLLECTIVES)


def _label(host, lo, hi):
    """What the host was doing over [lo, hi): the benchmark's innermost
    span that covers the gap's middle, and the innermost span of any kind
    that does, joined by ' > '."""
    mid = (lo + hi) / 2
    over = [(e - s, name) for name, s, e in host if s <= mid < e]
    if not over:
        return "(no host span)"
    bench = [x for x in over if x[1].startswith(BENCH_PREFIX)]
    inner = min(over)[1]
    outer = min(bench)[1] if bench else None
    if outer is None or outer == inner:
        return inner
    return f"{outer} > {inner}"


def reduce(tr: dict, window_name: str = "bench.window", top: int = 10) -> dict:
    """Reduce extracted intervals over the host span named ``window_name``.

    Returns ``window_s``; per device ``busy_s`` and ``collective_s`` (the
    union of its collective ops' intervals, which may overlap compute);
    ``device_ops``: the ``top`` op labels by total time summed over
    devices (an op nested in another, as a while loop's body is in the
    loop, counts in both); ``idle_gaps``: the ``top`` idle labels by total time
    on the first device, each gap of 10 us or more labelled by ``_label``
    and the shorter ones pooled."""
    spans = [(s, e) for name, s, e in tr["host"] if name == window_name]
    if not spans:
        raise ValueError(f"no host span {window_name!r} in the trace")
    lo, hi = max(spans, key=lambda x: x[1] - x[0])
    busy, coll, totals, gaps = {}, {}, {}, {}
    first = min(tr["devices"], default=None)
    for dev, ops in sorted(tr["devices"].items()):
        ops = [op for op in ops if op[3] > lo and op[2] < hi]
        u = _union(_clip([(s, e) for *_, s, e in ops], lo, hi))
        busy[dev] = sum(e - s for s, e in u) / 1e9
        cu = _union(_clip([(s, e) for _, opc, s, e in ops
                           if is_collective(opc)], lo, hi))
        coll[dev] = sum(e - s for s, e in cu) / 1e9
        for label, _, s, e in ops:
            totals[label] = (totals.get(label, 0.0)
                             + (min(e, hi) - max(s, lo)) / 1e9)
        if dev == first:
            edges = [lo] + [x for iv in u for x in iv] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    lab = (_label(tr["host"], a, b) if b - a >= SHORT_GAP_NS
                           else SHORT_GAP_LABEL)
                    gaps[lab] = gaps.get(lab, 0.0) + (b - a) / 1e9
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) / 1e9, "busy_s": busy,
            "collective_s": coll,
            "device_ops": [list(kv) for kv in rank(totals)],
            "idle_gaps": [list(kv) for kv in rank(gaps)]}
