"""idle_share.served: percent of the traced window in which no op ran
on the device, averaged over the cell's chips."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr["devices"] or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
