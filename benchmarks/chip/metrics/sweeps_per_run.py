"""sweeps_per_run: local-fixpoint sweeps per run, ``Telemetry.local_iters``
summed over partitions, averaged over the window's runs."""


def read(r: dict):
    return sum(r["sweeps"]) / r["runs"] if r["runs"] else None
