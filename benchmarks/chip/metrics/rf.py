"""rf: replication factor of the live partitioning at the end of the
window, by the benchmark's own arithmetic over the device pack (sum over
partitions of the distinct vertices their edges touch, over |V|), as the
pack check leaves it."""


def read(run):
    return run.found.get("rf")
