"""sweep_device_s: device seconds per run in the megastep's
``gopher.sweep`` stage, the masked sweeps of the local fixpoint (and
PageRank's pull sweep).

An op's stage is its HLO instruction's ``gopher.*`` named scope, from the
program's ``op_stages()``, which compiles the loops again after the
window. Only the trace reduction's ten longest ops are counted
(``trace["device_ops"]``, summed over devices), and ``while``,
``conditional`` and ``call`` ops are skipped, since they contain the
others. Nothing to read where the program names no stages."""

from loader import load

CONTAINERS = ("while", "conditional", "call")


def read(r: dict, stages=None):
    return stage_seconds(r, "gopher.sweep", stages)


def stage_seconds(r: dict, stage: str, stages=None):
    """Device seconds per run of the ten longest ops in ``stage``."""
    ops = r.get("trace", {}).get("device_ops")
    if not ops or not r["runs"]:
        return None
    if stages is None:
        stages = op_stage_map(r)
    if not stages:
        return None
    total = 0.0
    for label, secs in ops:
        head, _, rest = label.partition(" ")
        if rest.split(" ")[0] in CONTAINERS:
            continue
        module, _, instr = head.rpartition(":")
        if stages.get(module, {}).get(instr) == stage:
            total += secs
    return total / r["runs"]


def op_stage_map(r: dict):
    """The program's ``{module: {instruction: stage}}``
    (``analytics/program_obs.py``), computed once per result dict and kept
    in it; None where the program has no stage names."""
    if "op_stages" not in r:
        r["op_stages"] = load("analytics", "program_obs").op_stages()
    return r["op_stages"]
