"""Compile the sweep's chip path for a described TPU v5e — no chip needed.

The TPU compiler ships with jaxlib and compiles for a topology that is
described, not attached (``jax.experimental.topologies``). These tests
lower the Pallas level GEMM and the stream and overlapped sweeps at the
chip's real block width (b=128) on one described v5e chip and on a 2×2
mesh of them, and check what only the TPU compile shows: the Mosaic
kernel is really on the path (``tpu_custom_call``), the program fits a
chip's 16 GB, and HloLint reads the TPU HLO (async
``collective-permute-start``/``-done`` pairs) with no ERROR.

On the CPU backend ``ops.pselinv_level_gemm`` takes its plain-dot
reference branch; each test steers it onto the Pallas branch itself.

The topology is described inside a fixture, never at import: one
process at a time may load the TPU library, and test workers import
every test file.
"""
import numpy as np
import pytest

from repro.core import hlo_verify as HV
from repro.core import sparse
from repro.core.plan import PlanOptions
from repro.core.pselinv_dist import analyze_structure, build_program
from repro.kernels import ops

B = 128
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile can be written to the persistent cache
    # but never read back without a chip: keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def pallas_gemm(monkeypatch):
    monkeypatch.setattr(ops, "use_interpret", lambda: False)


def test_block_gemm_compiles_to_mosaic(topo):
    """The level GEMM of the N=16,384 smoke sweep at grid 1×1: the whole
    (16384 × 16384) A⁻¹ grid times a leaf level's Û stack."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.kernels.block_gemm import block_gemm_pallas
    one_chip = SingleDeviceSharding(topo.devices[0])
    n, k = 128 * B, 32 * B
    a = jax.ShapeDtypeStruct((n, n), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((n, k), jnp.float32, sharding=one_chip)
    compiled = jax.jit(block_gemm_pallas, static_argnames=("interpret",)
                       ).lower(a, b, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("grid", [(1, 1), (2, 2)], ids=["1x1", "2x2"])
@pytest.mark.parametrize("stream", [False, True],
                         ids=["overlapped", "stream"])
def test_sweep_compiles_for_v5e(topo, pallas_gemm, grid, stream):
    """laplacian_2d(32, 128): N=4,096, nb=32 uniform b=128 supernodes."""
    from jax.sharding import Mesh
    pr, pc = grid
    bs, nb = analyze_structure(sparse.laplacian_2d(32, B), B, pr, pc)
    prog = build_program(bs, nb, B, pr, pc,
                         options=PlanOptions(stream=stream))
    mesh = Mesh(np.array(topo.devices[:pr * pc]), ("xy",))
    compiled = HV._traced_sweep(prog, mesh=mesh).lower().compile()
    txt = compiled.as_text()
    assert "tpu_custom_call" in txt
    ma = compiled.memory_analysis()
    assert (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes) < V5E_HBM_BYTES
    errors = [d for d in HV.lint_text(txt, prog, layer="hlo")
              if d.severity == "error"]
    assert errors == []
    if pr * pc > 1:
        assert "collective-permute-start" in txt
