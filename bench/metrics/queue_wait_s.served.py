"""queue_wait_s.served: mean seconds a request of the window waited in
the server's queue before its batch was formed (``batched_at`` minus
``submitted``, both stamped by the server)."""


def read(run):
    waits = [r["batched_at"] - r["submitted"]
             for r in run.get("requests", ()) if r["batched_at"] is not None]
    return sum(waits) / len(waits) if waits else None
