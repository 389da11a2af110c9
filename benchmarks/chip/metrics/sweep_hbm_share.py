"""sweep_hbm_share: the bytes the window's sweeps had to move, as a
percent of what the devices' HBM could move in their busy time.

Bytes: ``sweeps`` of every run (partition-sweeps) times one partition's
share of a full sweep (``analytics/<analytic>.full_sweep_bytes`` of the
unpartitioned graph over the partitions). Time: device busy time summed
over the devices, at the HBM peak of ``peaks.json`` for the device kind.
Busy time also holds delivery and halt operations, and a sweep masked to
its frontier moves fewer bytes than it is counted for."""
import json
from pathlib import Path


def hbm_bytes_per_s(kind: str) -> float:
    peaks = json.loads((Path(__file__).resolve().parents[1]
                        / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return float(peaks[kind]["hbm_bytes_per_s"])


def read(r: dict):
    busy = sum(r["trace"]["busy_s"].values())
    if busy <= 0 or not r["runs"]:
        return None
    moved = sum(r["sweeps"]) * r["full_sweep_bytes"] / r["num_parts"]
    return 100.0 * moved / (busy * hbm_bytes_per_s(r["device_kind"]))
