#!/usr/bin/env python3
"""Chip benchmark of selected inversion: one cell of ``BENCHMARK.json``
per run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything is found by name. The cell names a configuration (its file
is given in ``BENCHMARK.json``) and a traffic mix,
``bench/traffic/<traffic>.json``, whose ``driver`` picks the kind of
load in ``drivers.py``. A per-layer metric ``<name>`` is read by
``bench/metrics/<name>.py``, whose ``read(run)`` returns a number or
None when the run has nothing for it.

A run sets up (matrix from the seed, host prep, compile or cache load,
warm-up), measures for ``--seconds``, then checks the answers the window
produced against the plain reference (``reference.py``); with
``--control 1`` the control takes the answers' place. With
``--trace 1`` the window runs under the profiler and the result holds
the per-layer metrics, the device's busy and window seconds and a
breakdown; with ``--trace 0`` the end-to-end metrics. The last line of
standard output is one JSON object. Without a TPU, with fewer chips than
the cell asks for, or on a device kind ``peaks.json`` does not list, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

#: host spans the harness opens around its own calls, which name the
#: device's idle gaps; the window, and the anchor that puts the
#: program's span clock on the profiler's
GAP_SPANS = ("prep", "submit", "solve", "wait")
HARNESS_SPANS = GAP_SPANS + ("window", "anchor")


class BenchError(Exception):
    """The run cannot be made as asked: exit non-zero, print no result."""


def load_cell(root: str, name: str):
    """``(spec, cell, config, traffic)`` for the cell ``name``."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(os.path.join(root, entry["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    return spec, cell, cfg, traffic


def check_device(devices, chips: int, peaks: dict) -> dict:
    """The peaks of the device this run is on; raises unless it is a TPU
    with ``chips`` devices or more, of a kind ``peaks`` lists."""
    d0 = devices[0]
    if d0.platform != "tpu":
        raise BenchError(f"needs a TPU, found platform {d0.platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, found "
                         f"{len(devices)}")
    if d0.device_kind not in peaks:
        raise BenchError(f"device kind {d0.device_kind!r} is not in "
                         "peaks.json")
    return peaks[d0.device_kind]


def applies(metric: dict, cell: str, e2e_of_cell) -> bool:
    """Whether ``metric`` is reported in ``cell``: listed there, or
    listing no cells and moving a metric the cell reports."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_of_cell


def read_metric(root: str, name: str, run: dict):
    """Call ``bench/metrics/<name>.py``'s ``read(run)``."""
    path = os.path.join(root, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


class Profile:
    """The profiler and the program's span tracer, on around the window
    of a ``--trace 1`` run and off otherwise."""

    def __init__(self, on: bool, logdir: str):
        self.on, self.logdir = on, logdir
        self.anchor_ns = None

    def start(self) -> None:
        if not self.on:
            return
        import jax
        from jax.profiler import ProfileOptions, TraceAnnotation
        from repro.obs.trace import TRACER
        shutil.rmtree(self.logdir, ignore_errors=True)
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.logdir, profiler_options=opts)
        TRACER.clear()
        TRACER.enable()
        with TraceAnnotation("anchor"):       # the two clocks, side by side
            self.anchor_ns = time.perf_counter_ns()

    def stop(self) -> None:
        if not self.on:
            return
        import jax
        from repro.obs.trace import TRACER
        TRACER.disable()
        jax.profiler.stop_trace()

    def reduce(self):
        """``(summary, program spans)`` of the traced window; the spans
        as ``(name, seconds, attrs)``."""
        import reduce
        from repro.obs.trace import TRACER
        (path,) = glob.glob(os.path.join(self.logdir, "**",
                                         "*.xplane.pb"), recursive=True)
        tr = reduce.load(path, host_names=HARNESS_SPANS)
        spans = {n: (s, e) for n, s, e in tr.host
                 if n in ("window", "anchor")}
        offset = spans["anchor"][0] - self.anchor_ns
        prog = TRACER.spans()
        named = [x for x in tr.host if x[0] in GAP_SPANS] + [
            (s.name, s.t0_us * 1e3 + offset, s.t1_us * 1e3 + offset)
            for s in prog]
        summary = reduce.summarize(tr, spans["window"], named)
        shutil.rmtree(self.logdir, ignore_errors=True)
        return summary, [(s.name, s.dur_us / 1e6, s.attrs) for s in prog]


def check_answers(outcome, cfg: dict, struct) -> dict:
    """Each compared number beside its limit: the widest relative error
    of an answer's selected blocks against the reference, and, where
    answers can go missing, how many did."""
    import numpy as np
    from threadpoolctl import threadpool_limits

    import reference
    b = cfg["b"]
    pr, pc = outcome.grid
    refs = {}
    worst = math.inf if not outcome.answers else 0.0
    for Q, shards in outcome.answers:
        shards = np.asarray(shards)
        if id(Q) not in refs:
            with threadpool_limits(1, user_api="blas"):
                refs[id(Q)] = reference.selected_inverse(Q, b, struct)
        got = reference.unshard(shards, shards.shape[1] * pr, b, pr, pc)
        worst = max(worst, reference.max_rel_err(got, refs[id(Q)],
                                                 struct))
    checks = {"max_rel_err": (worst, cfg["limits"]["max_rel_err"])}
    if "unanswered" in cfg["limits"]:
        checks["unanswered"] = (outcome.failed,
                                cfg["limits"]["unanswered"])
    return checks


def run(args, root: str = ROOT, device_check=check_device) -> dict:
    """One run of a cell; returns the result line's object."""
    t_start = time.perf_counter()
    spec, cell, cfg, traffic = load_cell(root, args.workload)

    # libtpu logs to a fixed /tmp path unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    cache = os.path.join(root, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    peak = device_check(devices, cell["chips"], peaks)

    sys.path.insert(0, os.path.join(root, "src"))
    import drivers
    import matrices
    import work
    profile = Profile(bool(args.trace),
                      os.path.join(root, ".bench_trace", cell["name"]))
    outcome = drivers.DRIVERS[traffic["driver"]](
        cfg, traffic, args.seed, args.seconds, profile, t_start)
    used = outcome.facts.pop("devices")
    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
           for d in used]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(mem)}

    struct = work.block_structure(matrices.Lattice(
        cfg["nx"], cfg["ny"]).precision(cfg["range_cells"][0]), cfg["b"])
    if args.control:
        import reference
        outcome.answers = [
            (Q, reference.control(Q, cfg["b"], struct, cfg["precision"],
                                  *outcome.grid))
            for Q, _ in outcome.answers]
    checks = check_answers(outcome, cfg, struct)
    correct = all(v <= lim for v, lim in checks.values())

    e2e_of_cell = [m["name"] for m in spec["end_to_end"]
                   if applies(m, cell["name"], ())]
    metrics = {}
    result = {"correct": correct, "attempted": outcome.attempted,
              "failed": outcome.failed, "metrics": metrics,
              "device": device}
    if not args.trace:
        units = {m["name"]: m["unit"] for m in spec["end_to_end"]}
        for name in e2e_of_cell:
            metrics[name] = {"value": outcome.e2e[name],
                             "unit": units[name]}
    else:
        summary, spans = profile.reduce()
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        import reduce
        result["breakdown"] = {
            "device_ops": reduce.top(summary["op_s"]),
            "idle_gaps": reduce.top(summary["gap_s"])}
        facts = dict(outcome.facts, trace=summary, spans=spans,
                     chips=cell["chips"], peak=peak,
                     flops=work.useful_flops(struct, cfg["b"]),
                     bytes=work.needed_bytes(struct, cfg["b"]),
                     precision=cfg["precision"])
        for m in spec["per_layer"]:
            if applies(m, cell["name"], e2e_of_cell):
                v = read_metric(root, m["name"], facts)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    # a non-finite error (NaN or no answer) prints as the largest double,
    # so the line stays plain JSON
    result["checks"] = {k: {"value": v if math.isfinite(v)
                            else sys.float_info.max, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: put the control in the program's place: "
                    "compare the reference at the precision below the "
                    "configuration's instead of the window's answers; "
                    "the run has to come out not correct")
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for k, c in result["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
