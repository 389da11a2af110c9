"""build_s: host seconds of the program's GoFS build of the cell's graph
(graph, partitioner, partitioned store), taken around those calls."""


def read(r: dict):
    return r["build_s"]
