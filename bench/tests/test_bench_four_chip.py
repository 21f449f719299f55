"""The four-chip configuration on the CPU: ``gmrf2d-256x128-b128`` cut to
a 32 x 16 lattice at b=8 (nb=64), run through the harness on a 4-device
host mesh at grid 2x2. It is correct; with the permutes left out, or
with the control in the program's place, it is not."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_bench_harness import REPO, make_root  # noqa: E402

CONFIG = "gmrf2d-256x128-b128"
SEED = 2 ** 33 + 7

_RUN = """
import json, sys
from types import SimpleNamespace
sys.path[:0] = [{bench!r}, {src!r}]
import run
if {broken}:
    import jax
    # the exchange between chips left out: every permute returns its input
    jax.lax.ppermute = lambda x, axis_name, perm: x
args = SimpleNamespace(workload="tiny-2x2", seed={seed}, seconds=0.5,
                       trace=0, control={control})
r = run.run(args, root={root!r},
            device_check=lambda d, c, p: p["TPU v5 lite"])
print(json.dumps(r))
"""


def make_four_chip_root(tmp):
    """The harness's test root with its ``tiny`` configuration replaced
    by the four-chip one, cut to a 32 x 16 lattice at b=8."""
    root = make_root(tmp)
    with open(os.path.join(REPO, "bench", "configs", CONFIG + ".json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny", nx=32, ny=16, b=8)
    cfg["limits"]["unanswered"] = 0
    with open(os.path.join(root, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(cfg, f)
    return root


@pytest.mark.parametrize("broken,control", [
    pytest.param(False, 0, id="256x128"),
    pytest.param(True, 0, id="256x128-broken"),
    pytest.param(False, 1, id="256x128-control"),
])
def test_four_chip_config_on_host_mesh(tmp_path, broken, control):
    from repro.jaxenv import host_mesh_env
    root = make_four_chip_root(tmp_path)
    code = _RUN.format(bench=os.path.join(REPO, "bench"),
                       src=os.path.join(REPO, "src"), root=root,
                       broken=broken, control=control, seed=SEED)
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=host_mesh_env(4), capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    res = json.loads(r.stdout.strip().splitlines()[-1])
    assert res["correct"] is not (broken or control)
    if control:
        lim = res["checks"]["max_rel_err"]["limit"]
        assert lim < res["checks"]["max_rel_err"]["value"] < 100 * lim
