"""prep_s_per_matrix.served: host seconds of numeric prep per matrix in
the window, from the program's spans ``engine.prepare_values`` (one
matrix) and ``engine.prepare_values_many`` (``B`` matrices)."""


def read(run):
    secs = mats = 0
    for name, dur, attrs in run.get("spans", ()):
        if name == "engine.prepare_values":
            secs, mats = secs + dur, mats + 1
        elif name == "engine.prepare_values_many":
            secs, mats = secs + dur, mats + attrs.get("B", 1)
    return secs / mats if mats else None
