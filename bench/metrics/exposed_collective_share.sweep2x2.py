"""exposed_collective_share.sweep2x2: percent of the device's busy time
in which a collective runs and no compute op covers it, both averaged
over the cell's chips by the trace reduction."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"] or tr["busy_s"] <= 0:
        return None
    return 100.0 * tr["exposed_collective_s"] / tr["busy_s"]
