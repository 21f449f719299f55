"""prep_factor_s_per_matrix.served: host seconds per matrix in the
window's supernode loop of the numeric prep (L̂ and D⁻¹), from the
program's ``prep.factor`` spans, each over ``B`` matrices."""


def read(run):
    secs = mats = 0
    for name, dur, attrs in run.get("spans", ()):
        if name == "prep.factor":
            secs, mats = secs + dur, mats + attrs.get("B", 1)
    return secs / mats if mats else None
