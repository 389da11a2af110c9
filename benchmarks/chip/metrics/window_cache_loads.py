"""window_cache_loads: programs loaded from the persistent compilation
cache inside the measured window, per run. Each is a trace, a lowering and
a load that the entry repeats on every call."""


def read(r: dict):
    return r["window_cache_loads"] / r["runs"] if r["runs"] else None
