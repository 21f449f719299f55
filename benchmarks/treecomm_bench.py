"""Tree-collective HLO accounting: hierarchical (RS + tree cross-pod AR +
AG) vs flat psum gradient sync — collective op counts/bytes from compiled
HLO on an 8-device CPU host mesh (2 pods × 4); re-execs itself as a
host-mesh child when it is not one already. CPU smoke, not device speed."""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.comm.hierarchical import hierarchical_allreduce
from repro.compat import shard_map
from repro.core.trees import TreeKind
from repro.jaxenv import ROOT, host_mesh_env, in_host_mesh

from .common import csv_row, reemit_child_rows


def run(full: bool = False):
    if not in_host_mesh(8):
        # re-exec in a subprocess with 8 CPU host devices
        import subprocess
        import sys
        r = subprocess.run(
            [sys.executable, "-m", "benchmarks.treecomm_bench"]
            + (["--full"] if full else []),
            env=host_mesh_env(8), cwd=ROOT, capture_output=True,
            text=True, timeout=600)
        reemit_child_rows(r.stdout)
        if r.returncode != 0:
            raise RuntimeError(r.stderr[-2000:])
        return None
    devs = np.array(jax.devices()[:8]).reshape(2, 4)
    mesh = Mesh(devs, ("pod", "data"))
    n = 1 << (16 if full else 12)
    x = jnp.zeros((2, 4, n), jnp.float32)

    def flat(xs):
        g = xs.reshape(n)
        return jax.lax.psum(g, ("pod", "data")).reshape(1, 1, n)

    def tree(xs):
        g = xs.reshape(n)
        out = hierarchical_allreduce(g, "pod", "data", npods=2,
                                     inner_size=4, kind=TreeKind.SHIFTED,
                                     tag=3)
        return out.reshape(1, 1, n)

    from repro.launch.dryrun import collective_bytes
    results = {}
    for name, f in (("flat_psum", flat), ("hier_tree", tree)):
        sm = shard_map(f, mesh=mesh, in_specs=P("pod", "data"),
                           out_specs=P("pod", "data"))
        txt = jax.jit(sm).lower(x).compile().as_text()
        cb = collective_bytes(txt)
        results[name] = cb
        csv_row(f"treecomm/{name}", 0.0,
                " ".join(f"{k}={v/1e3:.1f}KB" for k, v in cb.items()))
        # numerics must agree
    a = jax.jit(shard_map(flat, mesh=mesh, in_specs=P("pod", "data"),
                              out_specs=P("pod", "data")))(x + 1.0)
    b = jax.jit(shard_map(tree, mesh=mesh, in_specs=P("pod", "data"),
                              out_specs=P("pod", "data")))(x + 1.0)
    assert np.allclose(np.asarray(a), np.asarray(b))
    csv_row("treecomm/equivalence", 0.0, "tree == psum: True")
    return results


if __name__ == "__main__":
    import sys
    run(full="--full" in sys.argv)
