"""host_prep_s: host seconds per run in the program's own preparation
spans, ``gopher.engine``, ``gopher.layout``, ``gopher.upload``,
``gopher.compose_mailbox`` and ``gopher.dispatch`` (engine construction,
graph-block layout and upload, mailbox composition, and the compiled
loop's resolve, retrace, cache load and enqueue).

Read from the program's ``gopher_span_seconds`` histograms
(``analytics/program_obs.py``): the newest ``runs`` samples of each span,
summed and divided by the runs. The engine opens none of these spans
inside another, so each second counts once. Nothing to read where the
program records no such span."""
from loader import load

SPANS = ("engine", "layout", "upload", "compose_mailbox", "dispatch")


def read(r: dict, recent=None):
    recent = recent or load("analytics", "program_obs").recent
    if not r["runs"]:
        return None
    samples = [recent("gopher_span_seconds", r["runs"], {"span": s})
               for s in SPANS]
    if not any(samples):
        return None
    return sum(sum(s) for s in samples if s) / r["runs"]
