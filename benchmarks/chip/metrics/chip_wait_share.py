"""chip_wait_share: percent of the chips' sweep slots spent waiting for
the slowest chip's fixpoint. Per run the program counts its lockstep
sweeps (``engine_lockstep_sweeps``, what the slowest chip ran) and the
slots in which a chip had finished its own partitions' fixpoints and
waited (``engine_chip_wait_sweeps``); over the newest ``runs`` samples of
each (``analytics/program_obs.py``) the share is Σ wait over chips x Σ
lockstep, the chips being the devices in the trace. Nothing to read where
the program records either count."""
from loader import load


def read(r: dict, recent=None):
    recent = recent or load("analytics", "program_obs").recent
    chips = len(r.get("trace", {}).get("busy_s") or {})
    if not r["runs"] or not chips:
        return None
    wait = recent("engine_chip_wait_sweeps", r["runs"])
    lock = recent("engine_lockstep_sweeps", r["runs"])
    if not wait or not lock or not sum(lock):
        return None
    return 100.0 * sum(wait) / (chips * sum(lock))
