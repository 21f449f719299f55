"""The general traffic generator: one function per kind of load, each
driven by a traffic file's parameters.

``sweep_loop``
    One matrix, factored on the host and placed on the device in set-up;
    the window runs back-to-back ``PSelInvEngine.solve`` calls on those
    factors, each ended by ``block_until_ready``.
``closed_loop``
    A background ``SelInvServer`` and ``clients`` client threads, each
    sending a fresh matrix as soon as its previous request returns.

Each returns a :class:`Outcome`: the end-to-end numbers, what the
per-layer readers need, and the outputs the check compares. Set-up
starts when the harness's ``main`` does; ``setup_s`` ends when the last
warm-up call has finished.
"""
from __future__ import annotations

import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List

import numpy as np

import matrices

__all__ = ["Outcome", "DRIVERS"]

#: a request still unanswered this long after the window closed is lost
ANSWER_WAIT_S = 120.0


@dataclass
class Outcome:
    e2e: Dict[str, float]
    attempted: int
    failed: int
    #: the engine or server-side facts the per-layer readers use
    facts: Dict = field(default_factory=dict)
    #: (matrix, shards) pairs whose shards the check compares
    answers: List = field(default_factory=list)
    #: the process grid the shards are laid out on
    grid: tuple = (1, 1)


def _annotate(name: str):
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


def _options(cfg: Dict):
    from repro.core.engine import PlanOptions
    if cfg["executor"] != "overlapped":
        raise ValueError(f"unknown executor {cfg['executor']!r}")
    return PlanOptions()


def sweep_loop(cfg: Dict, traffic: Dict, seed: int, seconds: float,
               tracer, t_start: float) -> Outcome:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.engine import Grid, PSelInvEngine

    b, dtype = cfg["b"], np.dtype(cfg["dtype"])
    pr, pc = traffic["grid"]
    (rho,) = matrices.ranges(seed, 1, *cfg["range_cells"])
    Q = matrices.Lattice(cfg["nx"], cfg["ny"]).precision(rho)
    eng = PSelInvEngine.analyze(Q, b=b, grid=Grid(pr, pc),
                                options=_options(cfg))
    with _annotate("prep"):
        Lh, Dinv = eng.prepare_values(Q, dtype=dtype)
    shard = NamedSharding(eng.mesh, P("xy"))
    vals = (jax.device_put(Lh, shard), jax.device_put(Dinv, shard))
    jax.block_until_ready(vals)
    del Lh, Dinv
    t = time.perf_counter()
    out = eng.solve(vals, dtype=dtype)            # compiles or loads
    compile_s = time.perf_counter() - t
    out.block_until_ready()
    del out
    setup_s = time.perf_counter() - t_start

    # the answer of one solve drawn from the seed, and the last one
    keep_at = seed % 2
    kept = None
    n = 0
    times = []
    tracer.start()
    with _annotate("window"):
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while True:
            ts = time.perf_counter()
            with _annotate("solve"):
                out = eng.solve(vals, dtype=dtype)
            with _annotate("wait"):
                out.block_until_ready()
            times.append(time.perf_counter() - ts)
            if n == keep_at:
                kept = out
            n += 1
            if time.perf_counter() >= deadline:
                break
        t1 = time.perf_counter()
    tracer.stop()
    # one slow solve and a slow run look alike in selinv_s; this tells them
    # apart
    print(f"solves {n}: min {min(times)!r} median {np.median(times)!r} "
          f"max {max(times)!r} s", file=sys.stderr, flush=True)
    answers = [out] if kept is None or kept is out else [kept, out]
    return Outcome(e2e={"setup_s": setup_s, "selinv_s": (t1 - t0) / n},
                   attempted=n, failed=0,
                   facts={"compile_s": compile_s, "solves": n,
                          "devices": list(eng.mesh.devices.flat)},
                   answers=[(Q, a) for a in answers], grid=(pr, pc))


def closed_loop(cfg: Dict, traffic: Dict, seed: int, seconds: float,
                tracer, t_start: float) -> Outcome:
    from repro.core.engine import Grid, SolveValues
    from repro.serve.batcher import BatchWindow, RequestStatus, ServeError
    from repro.serve.server import SelInvServer, ServeConfig

    b, dtype = cfg["b"], np.dtype(cfg["dtype"])
    pr, pc = traffic["grid"]
    clients = traffic["clients"]
    window = BatchWindow(max_batch=traffic["max_batch"],
                         max_wait_ms=traffic["max_wait_ms"])
    lattice = matrices.Lattice(cfg["nx"], cfg["ny"])
    # each client's ranges, drawn from the seed; far more than a window
    # can use
    draws = matrices.ranges(seed, clients * traffic["max_requests"],
                            *cfg["range_cells"]).reshape(clients, -1)
    server = SelInvServer(ServeConfig(
        b=b, grid=Grid(pr, pc), options=_options(cfg), window=window,
        dtype=dtype)).start()
    try:
        # a request-built matrix, so the window's first submit finds its
        # pattern's fingerprint already mapped to the warm engine
        eng = server.engine_for(lattice.precision(draws[0, 0]))
        shape = (eng.grid.size, eng.nb // pr, eng.nb // pc, b, b)
        zeros = np.zeros((window.max_batch,) + shape, dtype)
        compile_s = 0.0
        for B in range(1, window.max_batch + 1):   # every bucket and pad
            t = time.perf_counter()
            out = eng.solve(SolveValues(zeros[:B], zeros[:B]),
                            dtype=dtype, bucket=True)
            compile_s += time.perf_counter() - t
            np.asarray(out)
        del zeros, out
        setup_s = time.perf_counter() - t_start

        go = threading.Event()
        records: List[List] = [[] for _ in range(clients)]
        errors: List[BaseException] = []

        def client(c: int) -> None:
            go.wait()
            try:
                for s in draws[c]:
                    if time.monotonic() >= deadline:
                        return
                    M = lattice.precision(s)
                    with _annotate("submit"):
                        r = server.submit(M)
                    with _annotate("wait"):
                        try:
                            out = r.result(ANSWER_WAIT_S)
                        except (ServeError, TimeoutError):
                            out = None
                    records[c].append((float(s), r, out))
                    if not r.done():
                        return                     # lost: stop this client
                raise RuntimeError("client ran out of seeded ranges")
            except Exception as e:                 # noqa: BLE001 — report
                errors.append(e)

        threads = [threading.Thread(target=client, args=(c,),
                                    name=f"client-{c}")
                   for c in range(clients)]
        for th in threads:
            th.start()
        tracer.start()
        with _annotate("window"):
            t0 = time.monotonic()
            deadline = t0 + seconds
            go.set()
            for th in threads:
                th.join()
        tracer.stop()
    finally:
        server.stop()
    if errors:
        raise errors[0]
    reqs = [x for rec in records for x in rec]
    done = [r.completed for _, r, _ in reqs if r.completed is not None]
    t_end = max(done) if done else time.monotonic()
    solved = [(s, out) for s, r, out in reqs
              if r.status == RequestStatus.SOLVED]
    return Outcome(
        e2e={"setup_s": setup_s,
             "served_matrices_per_s": len(solved) / (t_end - t0)},
        attempted=len(reqs), failed=len(reqs) - len(solved),
        facts={"compile_s": compile_s,
               "devices": list(eng.mesh.devices.flat),
               "requests": [{"submitted": r.submitted,
                             "batched_at": r.batched_at,
                             "completed": r.completed}
                            for _, r, _ in reqs]},
        answers=[(lattice.precision(s), out) for s, out in solved],
        grid=(pr, pc))


DRIVERS: Dict[str, Callable[..., Outcome]] = {
    "sweep_loop": sweep_loop,
    "closed_loop": closed_loop,
}
