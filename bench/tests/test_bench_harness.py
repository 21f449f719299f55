"""The harness on the CPU: the device guard, cells found by name in a
directory of their own, and ``correct`` coming out false when the timed
path is broken underneath.

The chip check is replaced by one that accepts the CPU; everything else
is a whole run at a small size (16 x 16 lattice, b=8, nb=32)."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(REPO, "bench"), os.path.join(REPO, "src")]

import run as bench_run  # noqa: E402

CONFIG = "gmrf2d-128x128-b128"


def _accept_cpu(devices, chips, peaks):
    return peaks["TPU v5 lite"]


def make_root(tmp, traffic_over=None, extra_metric=False):
    """A directory with ``BENCHMARK.json`` and the bench's data files:
    the real configuration cut to a 16 x 16 lattice under a new name, a
    new sweep mix, a served mix and (optionally) a new per-layer
    metric."""
    root = str(tmp)
    for d in ("configs", "traffic", "metrics"):
        os.makedirs(os.path.join(root, "bench", d), exist_ok=True)
    with open(os.path.join(REPO, "bench", "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny", nx=16, ny=16, b=8)
    cfg["limits"]["unanswered"] = 0
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    mixes = {"loop_a": {"driver": "sweep_loop", "grid": [1, 1]},
             "loop_2x2": {"driver": "sweep_loop", "grid": [2, 2]},
             "closed_b": {"driver": "closed_loop", "grid": [1, 1],
                          "clients": 4, "max_batch": 4,
                          "max_wait_ms": 50.0, "max_requests": 5000}}
    for name, mix in mixes.items():
        mix.update((traffic_over or {}).get(name, {}))
        with open(os.path.join(root, "bench", "traffic",
                               name + ".json"), "w") as f:
            json.dump(mix, f)
    for m in os.listdir(os.path.join(REPO, "bench", "metrics")):
        shutil.copy(os.path.join(REPO, "bench", "metrics", m),
                    os.path.join(root, "bench", "metrics", m))
    per_layer = [{"name": "compile_s", "unit": "s", "better": "lower",
                  "source": "host_clock", "layer": "engine",
                  "moves": "setup_s"}]
    if extra_metric:
        with open(os.path.join(root, "bench", "metrics",
                               "solves_seen.py"), "w") as f:
            f.write("def read(run):\n    return run.get('solves')\n")
        per_layer.append({"name": "solves_seen", "unit": "1",
                          "better": "higher", "source": "host_clock",
                          "layer": "engine", "moves": "selinv_s",
                          "workloads": ["tiny-sweep"]})
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [
            {"name": "tiny-sweep", "config": "tiny", "traffic": "loop_a",
             "chips": 1, "why": "test"},
            {"name": "tiny-2x2", "config": "tiny", "traffic": "loop_2x2",
             "chips": 4, "why": "test"},
            {"name": "tiny-served", "config": "tiny",
             "traffic": "closed_b", "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"},
            {"name": "selinv_s", "unit": "s", "better": "lower",
             "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny-sweep", "tiny-2x2"]},
            {"name": "served_matrices_per_s", "unit": "matrices/s",
             "better": "higher", "bound": 0.05, "source": "host_clock",
             "workloads": ["tiny-served"]}],
        "per_layer": per_layer}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


@pytest.fixture
def jax_config():
    """Restore the compile-cache settings a run changes."""
    import jax
    keep = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", keep[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      keep[1])


def one_run(root, cell, trace=0, seed=2 ** 33 + 1, seconds=0.5,
            control=0):
    args = SimpleNamespace(workload=cell, seed=seed, seconds=seconds,
                           trace=trace, control=control)
    return bench_run.run(args, root=root, device_check=_accept_cpu)


# ---- the device guard ----------------------------------------------------

def _dev(platform="tpu", kind="TPU v5 lite"):
    return SimpleNamespace(platform=platform, device_kind=kind)


def test_guard_refuses_cpu_unknown_kind_and_too_few_chips():
    with open(os.path.join(REPO, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    assert bench_run.check_device([_dev()], 1, peaks) is peaks[
        "TPU v5 lite"]
    with pytest.raises(bench_run.BenchError, match="needs a TPU"):
        bench_run.check_device([_dev("cpu", "cpu")], 1, peaks)
    with pytest.raises(bench_run.BenchError, match="not in peaks"):
        bench_run.check_device([_dev(kind="TPU v9 mystery")], 1, peaks)
    with pytest.raises(bench_run.BenchError, match="needs 4 chips"):
        bench_run.check_device([_dev()], 4, peaks)


def test_cli_on_cpu_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench", "run.py"),
                        "--workload", "sweep-128x128-1chip", "--seed",
                        "3000000000", "--seconds", "1", "--trace", "0"],
                       cwd=REPO, env=env, capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert "needs a TPU" in r.stderr
    assert not [ln for ln in r.stdout.splitlines()
                if ln.strip().startswith("{")]


# ---- cells found by name -------------------------------------------------

def test_new_config_traffic_and_metric_are_found_by_name(tmp_path,
                                                          jax_config):
    root = make_root(tmp_path, extra_metric=True)
    r = one_run(root, "tiny-sweep")
    assert r["correct"] is True
    assert set(r["metrics"]) == {"setup_s", "selinv_s"}
    assert r["metrics"]["selinv_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    t = one_run(root, "tiny-sweep", trace=1)
    assert t["correct"] is True
    # the new metric is read; compile_s moves setup_s, which every cell
    # reports; the CPU has no device plane, so busy is 0 there
    assert t["metrics"]["solves_seen"]["value"] == t["attempted"] > 0
    assert "compile_s" in t["metrics"]
    assert t["device"]["window_s"] > 0 and "breakdown" in t


def test_served_cell_is_correct(tmp_path, jax_config):
    r = one_run(make_root(tmp_path), "tiny-served", seconds=1.0)
    assert r["correct"] is True and r["failed"] == 0
    assert r["metrics"]["served_matrices_per_s"]["value"] > 0
    assert r["checks"]["unanswered"] == {"value": 0, "limit": 0}


# ---- the control and the faults the check has to catch -------------------

@pytest.mark.parametrize("cell", ["tiny-sweep", "tiny-served"])
def test_control_comes_out_not_correct(tmp_path, jax_config, cell):
    """The reference at Precision.HIGH in the program's place fails the
    configuration's own limit through the run's own comparison."""
    r = one_run(make_root(tmp_path), cell, seconds=1.0, control=1)
    assert r["correct"] is False
    lim = r["checks"]["max_rel_err"]["limit"]
    assert lim < r["checks"]["max_rel_err"]["value"] < 100 * lim


def test_fault_solve_returns_its_state_unchanged(tmp_path, jax_config,
                                                 monkeypatch):
    from repro.core.engine import PSelInvEngine
    monkeypatch.setattr(PSelInvEngine, "solve",
                        lambda self, values, dtype=None, **kw: values[0])
    r = one_run(make_root(tmp_path), "tiny-sweep")
    assert r["correct"] is False
    assert r["checks"]["max_rel_err"]["value"] > 0.1


def test_fault_half_the_batch_left_out(tmp_path, jax_config, monkeypatch):
    """A batched solve that computes the first half of its lanes and
    fills the rest with their mean."""
    import jax.numpy as jnp
    from repro.serve.server import SelInvServer
    real = SelInvServer._execute

    def half(self, eng, vals, B, bkt):
        out = real(self, eng, vals, B, bkt)
        keep = max(B // 2, 1)
        if keep == B:
            return out
        mean = out[:keep].mean(axis=0, keepdims=True)
        return jnp.concatenate(
            [out[:keep], jnp.repeat(mean, B - keep, axis=0)])

    monkeypatch.setattr(SelInvServer, "_execute", half)
    r = one_run(make_root(tmp_path), "tiny-served", seconds=1.0)
    assert r["correct"] is False


def test_fault_answer_altered_where_produced(tmp_path, jax_config,
                                             monkeypatch):
    """One request's answer altered in one block as the server hands it
    out."""
    from repro.serve.batcher import RequestStatus, SolveRequest
    real = SolveRequest._finish
    hit = []

    def finish(self, status, result=None, error=None):
        if status == RequestStatus.SOLVED and not hit:
            result = np.array(result)
            result[0, 1, 1] *= 1.01
            hit.append(self.rid)
        return real(self, status, result=result, error=error)

    monkeypatch.setattr(SolveRequest, "_finish", finish)
    r = one_run(make_root(tmp_path), "tiny-served", seconds=1.0)
    assert hit and r["correct"] is False


_TWO_BY_TWO = """
import json, sys
from types import SimpleNamespace
sys.path[:0] = [{bench!r}, {src!r}]
import run
if {broken}:
    import jax
    # the exchange between chips left out: every permute returns its input
    jax.lax.ppermute = lambda x, axis_name, perm: x
args = SimpleNamespace(workload="tiny-2x2", seed=5, seconds=0.5, trace=0,
                       control=0)
r = run.run(args, root={root!r},
            device_check=lambda d, c, p: p["TPU v5 lite"])
print(json.dumps(r))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_fault_exchange_between_chips_left_out(tmp_path, broken):
    from repro.jaxenv import host_mesh_env
    root = make_root(tmp_path)
    code = _TWO_BY_TWO.format(bench=os.path.join(REPO, "bench"),
                              src=os.path.join(REPO, "src"), root=root,
                              broken=broken)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=host_mesh_env(4), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is (not broken)
