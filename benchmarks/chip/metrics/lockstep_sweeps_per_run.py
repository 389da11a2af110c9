"""lockstep_sweeps_per_run: sweeps the megastep's flat fixpoint ran per
run, in lockstep over every partition (``Telemetry.lockstep_sweeps``; one
per superstep for PageRank): the mean of the newest ``runs`` samples of
the program's ``engine_lockstep_sweeps`` histogram
(``analytics/program_obs.py``). Where ``sweeps_per_run`` counts
per-partition sweeps, this counts what the device executed. Nothing to
read where the program records no count."""
from loader import load


def read(r: dict, recent=None):
    recent = recent or load("analytics", "program_obs").recent
    if not r["runs"]:
        return None
    got = recent("engine_lockstep_sweeps", r["runs"])
    return sum(got) / len(got) if got else None
