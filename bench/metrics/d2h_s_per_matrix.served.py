"""d2h_s_per_matrix.served: host seconds per matrix in the window that
the server spent copying a batch's ready answers to the host, from the
program's ``serve.d2h`` spans, each over ``B`` matrices."""


def read(run):
    secs = mats = 0
    for name, dur, attrs in run.get("spans", ()):
        if name == "serve.d2h":
            secs, mats = secs + dur, mats + attrs.get("B", 1)
    return secs / mats if mats else None
