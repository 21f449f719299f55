"""recv_imbalance.sweep2x2: the most bytes one device receives in a
sweep over the mean across devices, from the ``recv_bytes_max`` and
``recv_bytes_mean`` attributes of the program's ``engine.solve`` spans:
the communication load imbalance the trees are built to bound. None
when the spans carry no such attributes."""


def read(run):
    for name, _, attrs in run.get("spans", ()):
        if name == "engine.solve" and attrs.get("recv_bytes_mean"):
            return attrs["recv_bytes_max"] / attrs["recv_bytes_mean"]
    return None
