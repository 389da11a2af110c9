"""collective_share: percent of the devices' busy time in which a
collective operation ran (all-to-all, collective-permute, all-reduce, ...),
summed over the cell's devices. Nothing to read where no collective ran."""


def read(r: dict):
    t = r["trace"]
    busy = sum(t["busy_s"].values())
    coll = sum(t["collective_s"].values())
    if coll <= 0 or busy <= 0:
        return None
    return 100.0 * coll / busy
