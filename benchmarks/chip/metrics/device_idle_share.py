"""device_idle_share: percent of the traced window in which no operation
ran on the device, averaged over the cell's devices."""


def read(r: dict):
    t = r["trace"]
    if not t["busy_s"] or t["window_s"] <= 0:
        return None
    busy = sum(t["busy_s"].values()) / len(t["busy_s"])
    return 100.0 * (1.0 - busy / t["window_s"])
