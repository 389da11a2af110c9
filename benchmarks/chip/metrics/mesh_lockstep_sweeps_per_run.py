"""mesh_lockstep_sweeps_per_run: lockstep sweeps per run of the staged
superstep across the mesh (``Telemetry.lockstep_sweeps``: per superstep
the busiest partition's sweeps, which the slowest chip ran and every chip
waited for), read as ``lockstep_sweeps_per_run`` reads it: the mean of the
newest ``runs`` samples of the program's ``engine_lockstep_sweeps``
histogram. Nothing to read where the program records no count."""
from loader import load


def read(r: dict, recent=None):
    return load("metrics", "lockstep_sweeps_per_run").read(r, recent)
