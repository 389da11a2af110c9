"""The program's own observations, for the per-layer readers that read
them (``metrics/host_prep_s.py``, ``lockstep_sweeps_per_run.py``,
``sweep_device_s.py``, ``deliver_device_s.py``). Like the analytic entries
beside it, this file is where the yardstick reaches the program: the
readers themselves import none of it. Each function returns None where
the program records no such thing."""
from __future__ import annotations


def recent(name: str, n: int, labels: dict | None = None):
    """The newest ``n`` samples of the histogram ``name`` in the program's
    process metrics registry (a list, maybe empty), or None."""
    try:
        from repro.obs import default_registry
    except ImportError:
        return None
    get = getattr(default_registry(), "recent", None)
    return None if get is None else get(name, n, labels)


def op_stages():
    """``{module: {HLO instruction: gopher.* stage}}`` of the program's
    compiled loops (it compiles them again), or None."""
    try:
        from repro.obs import op_stages as stages
    except ImportError:
        return None
    return stages()
