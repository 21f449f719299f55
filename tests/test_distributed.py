"""Distributed tests — run in subprocesses with their own XLA device
count (8 host devices), so the main pytest process stays single-device."""
import pytest

from conftest import run_sub


def test_run_distributed_validates_grid_and_inputs():
    """An oversized process grid raises a ValueError naming the requested
    grid vs the available devices (it used to die in a cryptic numpy
    reshape inside the device slicing), and ``analyze_structure`` rejects
    a matrix whose size is not a multiple of the block size with a real
    ValueError (not an ``assert`` that vanishes under ``python -O``).
    The main pytest process is single-device, which is exactly the
    misconfiguration the grid check must catch."""
    from repro.core import sparse
    from repro.core.engine import Grid, PSelInvEngine
    from repro.core.pselinv_dist import analyze_structure

    A = sparse.laplacian_2d(12, 8)
    # a grid no host plausibly satisfies, so the check fires regardless
    # of how many devices this machine (or its XLA_FLAGS) exposes
    with pytest.raises(ValueError, match=r"grid 64x64 needs 4096 devices"):
        PSelInvEngine.analyze(A, b=8, grid=Grid(64, 64))
    with pytest.raises(ValueError, match=r"not a multiple of the supernode"):
        analyze_structure(A, b=7, pr=1, pc=1)


def test_tree_collectives_match_builtins():
    run_sub("""
        import jax, numpy as np
        from repro.compat import shard_map
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core.trees import TreeKind, build_tree
        from repro.comm.treecomm import (tree_allreduce, subset_broadcast,
                                         subset_reduce)
        devs = jax.devices()
        mesh = Mesh(np.array(devs).reshape(8), ("x",))
        x = jnp.arange(8.0 * 4).reshape(8, 4)
        members = [1, 3, 4, 6]
        y = jax.jit(shard_map(
            lambda v: subset_broadcast(v, "x", 3, members,
                                       TreeKind.SHIFTED, tag=7),
            mesh=mesh, in_specs=P("x"), out_specs=P("x")))(x)
        y = np.asarray(y)
        for r in range(8):
            exp = x[3] if r in members else x[r]
            assert np.allclose(y[r], exp)
        z = jax.jit(shard_map(
            lambda v: subset_reduce(v, "x", 4, members, TreeKind.BINARY),
            mesh=mesh, in_specs=P("x"), out_specs=P("x")))(x)
        assert np.allclose(np.asarray(z)[4],
                           sum(np.asarray(x[m]) for m in members))
        tree = build_tree(TreeKind.SHIFTED, 2, [0,1,3,4,5,6,7], tag=13)
        w = jax.jit(shard_map(
            lambda v: tree_allreduce(v, "x", tree),
            mesh=mesh, in_specs=P("x"), out_specs=P("x")))(x)
        assert np.allclose(np.asarray(w), np.asarray(x).sum(0))
        print("OK")
    """)


def test_hierarchical_allreduce_matches_psum():
    run_sub("""
        import jax, numpy as np
        from repro.compat import shard_map
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.comm.hierarchical import hierarchical_allreduce
        devs = jax.devices()
        mesh = Mesh(np.array(devs).reshape(2, 4), ("pod", "data"))
        xx = jnp.arange(8.0 * 8).reshape(2, 4, 8)
        def ha(xs):
            return hierarchical_allreduce(
                xs.reshape(8), "pod", "data", 2, 4, tag=3).reshape(1, 1, 8)
        out = jax.jit(shard_map(ha, mesh=mesh, in_specs=P("pod","data"),
                                    out_specs=P("pod","data")))(xx)
        assert np.allclose(np.asarray(out), np.asarray(xx).sum((0,1)))
        print("OK")
    """)


def test_distributed_pselinv_matches_oracle():
    run_sub("""
        import numpy as np
        import jax.numpy as jnp
        from repro.core import sparse
        from repro.core.trees import TreeKind
        from repro.core.engine import Grid, PlanOptions, PSelInvEngine
        from repro.core.pselinv_dist import gather_blocks
        from repro.core.selinv import dense_selinv_oracle
        A = sparse.laplacian_2d(12, 8)
        ref = dense_selinv_oracle(A)
        for kind in (TreeKind.FLAT, TreeKind.SHIFTED):
            eng = PSelInvEngine.analyze(A, b=8, grid=Grid(4, 2),
                                        options=PlanOptions(kind=kind))
            out = np.asarray(eng.solve(A, dtype=jnp.float64))
            blocks = gather_blocks(out, eng)
            bs = eng.bs
            err = 0.0
            for K in range(bs.nsuper):
                err = max(err, abs(blocks[K, K]
                                   - ref[K*8:(K+1)*8, K*8:(K+1)*8]).max())
                for I in bs.struct[K]:
                    I = int(I)
                    err = max(err, abs(blocks[I, K]
                                       - ref[I*8:(I+1)*8, K*8:(K+1)*8]).max())
            assert err < 1e-9, (kind, err)
        print("OK")
    """, x64=True)


def test_grad_sync_tree_equals_psum():
    """Manual-DP gradient sync with the paper's hierarchical tree equals
    plain psum (the LM-training integration of the technique)."""
    run_sub("""
        import jax, numpy as np
        from repro.compat import shard_map
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.comm.hierarchical import hierarchical_allreduce
        devs = jax.devices()
        mesh = Mesh(np.array(devs).reshape(2, 4), ("pod", "data"))
        w = jnp.ones((16,)) * 0.5
        x = jnp.arange(2.0 * 4 * 16).reshape(2, 4, 16)

        def loss(w, xb):
            return jnp.sum(jnp.tanh(xb @ w))

        def step_tree(w, xb):
            g = jax.grad(loss)(w, xb.reshape(1, 16))
            g = hierarchical_allreduce(g, "pod", "data", 2, 4, tag=0)
            return g.reshape(1, 1, 16)

        def step_psum(w, xb):
            g = jax.grad(loss)(w, xb.reshape(1, 16))
            return jax.lax.psum(g, ("pod", "data")).reshape(1, 1, 16)

        gt = jax.jit(shard_map(lambda xb: step_tree(w, xb), mesh=mesh,
                     in_specs=P("pod", "data"), out_specs=P("pod","data")))(x)
        gp = jax.jit(shard_map(lambda xb: step_psum(w, xb), mesh=mesh,
                     in_specs=P("pod", "data"), out_specs=P("pod","data")))(x)
        assert np.allclose(np.asarray(gt), np.asarray(gp), rtol=1e-6)
        print("OK")
    """)
