"""sweep_roofline: percent of the device's busy time per sweep that the
work selected inversion needs would take at the chip's peak.

The least time is max(useful FLOPs / (chips x peak FLOP/s at the
configuration's precision), needed bytes / (chips x HBM bytes/s)), with
the work counted from the matrix by ``work.py``; the time is the traced
busy seconds per chip divided by the solves in the window."""


def read(run):
    tr, solves = run.get("trace"), run.get("solves")
    if not tr or not solves or tr["busy_s"] <= 0:
        return None
    peak, chips = run["peak"], run["chips"]
    least = max(run["flops"] / (chips * peak["flops"][run["precision"]]),
                run["bytes"] / (chips * peak["hbm_bytes_per_s"]))
    return 100.0 * least / (tr["busy_s"] / solves)
