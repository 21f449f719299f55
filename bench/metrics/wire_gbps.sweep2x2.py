"""wire_gbps.sweep2x2: GB/s one chip's permutes achieve: the bytes the
sweep ships over all devices, from the ``wire_bytes`` attribute of the
program's ``engine.solve`` spans, per chip, over the traced collective
seconds per solve. None when the spans carry no ``wire_bytes``."""


def read(run):
    tr, solves = run.get("trace"), run.get("solves")
    wire = [attrs["wire_bytes"] for name, _, attrs in run.get("spans", ())
            if name == "engine.solve" and "wire_bytes" in attrs]
    if not wire or not tr or not solves or tr["collective_s"] <= 0:
        return None
    per_chip = sum(wire) / len(wire) / run["chips"]
    return per_chip / (tr["collective_s"] / solves) / 1e9
