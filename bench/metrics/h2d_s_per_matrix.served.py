"""h2d_s_per_matrix.served: host seconds per matrix in the window that
the engine spent casting a batch's values and moving them to the device
until they were there, from the program's ``engine.h2d`` spans, each
over ``B`` matrices."""


def read(run):
    secs = mats = 0
    for name, dur, attrs in run.get("spans", ()):
        if name == "engine.h2d":
            secs, mats = secs + dur, mats + attrs.get("B", 1)
    return secs / mats if mats else None
