"""deliver_device_s: device seconds per run in the megastep's
``gopher.deliver`` stage, the mailbox delivery and the inbox ⊕-combine,
counted as ``sweep_device_s`` counts its stage (the ten longest ops of
the trace reduction, containers skipped). Nothing to read where the
program names no stages."""
from loader import load


def read(r: dict, stages=None):
    return load("metrics", "sweep_device_s").stage_seconds(
        r, "gopher.deliver", stages)
