"""mesh_sweep_device_s: device seconds per run in the staged superstep's
``gopher.sweep`` stage, the partitions' local fixpoints, per chip: what
``sweep_device_s`` counts for the stage (the ten longest ops of the trace
reduction, summed over the cell's devices, containers skipped) over the
devices in the trace. Nothing to read where the program names no stages,
or where no op it counts is in the stage (a program whose staged loop
names none)."""
from loader import load


def read(r: dict, stages=None):
    chips = len(r.get("trace", {}).get("busy_s") or {})
    got = load("metrics", "sweep_device_s").stage_seconds(
        r, "gopher.sweep", stages)
    return got / chips if got and chips else None
