"""Ahead-of-time compiles of the serving path's kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* v5e, and refuses what the chip would refuse (a block whose
second-minor dim is not a multiple of 8, a vector shape Mosaic cannot lay
out) where interpret mode passes.  Each kernel test asserts that the
compiled program holds the Pallas kernel (``tpu_custom_call``); the slot
writer's, that it updates in place.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.gnn import build_model
from repro.kernels import ops
from repro.kernels.autotune import KernelConfig
from repro.serving import Bucket, ExecutorPool, ModelRegistry


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except RuntimeError as e:
        # Only a missing TPU compiler skips; any other failure is a fault.
        if "TPU support not installed" not in str(e):
            raise
        pytest.skip(f"no TPU compiler installed: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_lowering(monkeypatch):
    """Trace the kernels for the TPU, not for interpret mode.

    The wrappers read ``auto_interpret()`` at trace time and this process
    only has the CPU backend; clearing JAX's caches around the test keeps a
    trace made for one mode from being reused by the other.
    """
    monkeypatch.setattr(ops, "auto_interpret", lambda: False)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _struct(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# The default GhostConfig tile (20 x 20) is not sublane-aligned; 8 x 8 is.
TILES = [pytest.param(20, id="v20"), pytest.param(8, id="v8")]


@pytest.mark.parametrize("v", TILES)
def test_block_spmm_compiles(one_chip, v):
    b, g, f = 16, 4, 256
    args = (_struct(one_chip, (b, v, v)),
            _struct(one_chip, (b,), jnp.int32),
            _struct(one_chip, (b,), jnp.int32),
            _struct(one_chip, (g * v, f)))
    fn = jax.jit(lambda *a: ops.block_spmm_padded(*a, g, interpret=False))
    _assert_kernel(fn.lower(*args).compile())


@pytest.mark.parametrize("v", TILES)
@pytest.mark.parametrize("reduce", ["sum", "mean", "max", "int8"])
def test_fused_block_spmm_compiles(one_chip, v, reduce):
    b, g, f_in, f_out = 16, 4, 160, 24
    args = [_struct(one_chip, (b, v, v)),
            _struct(one_chip, (b,), jnp.int32),
            _struct(one_chip, (b,), jnp.int32),
            _struct(one_chip, (g * v, f_in)),
            _struct(one_chip, (f_in, f_out)),
            _struct(one_chip, (f_out,))]
    if reduce == "mean":
        args.append(_struct(one_chip, (g * v,)))

    def fn(blocks, row, col, feat, w, bias, inv_deg=None):
        return ops.fused_block_spmm_padded(
            blocks, row, col, feat, w, bias, inv_deg, g, activation="relu",
            reduce="max" if reduce == "max" else "sum",
            quantized=reduce == "int8", interpret=False)

    _assert_kernel(jax.jit(fn).lower(*args).compile())


# (bucket, f_in): a small serving bucket at the default tile, and the bucket
# a Cora-sized graph (2,708 nodes, 1,433 features) lands in.
BUCKETS = [
    pytest.param(Bucket(num_blocks=16, num_dst_groups=4, num_src_groups=4,
                        v=20, n=20, f=16), 12, id="small"),
    pytest.param(Bucket(num_blocks=8192, num_dst_groups=256,
                        num_src_groups=256, v=20, n=20, f=2048), 1433,
                 id="cora"),
]


@pytest.mark.parametrize("bucket,f_in", BUCKETS)
@pytest.mark.parametrize("order", ["auto", "aggregate_first"])
def test_executor_compiles(one_chip, chip_lowering, bucket, f_in, order):
    """The vmapped GCN executor, as ExecutorPool builds it.  ``auto`` is
    what serving plans (combine-first at these widths: the unfused SpMM);
    ``aggregate_first`` forces the fused epilogue kernel."""
    model = build_model("gcn", f_in, 7, hidden=16)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    entry = ModelRegistry().register("gcn", model, params, task="node")
    cfg = None if order == "auto" else KernelConfig(order=order)
    pool = ExecutorPool(slots=8, backend="pallas_fused", kernel_config=cfg)
    _assert_kernel(pool.lower(entry, bucket, sharding=one_chip).compile())


@pytest.mark.parametrize("v", TILES)
@pytest.mark.parametrize("heads,f", [pytest.param(8, 8, id="h8f8"),
                                     pytest.param(1, 7, id="h1f7")])
def test_attention_block_spmm_compiles(one_chip, v, heads, f):
    """GAT's edge-softmax kernel at Cora's published head shapes."""
    b, g = 16, 4
    args = (_struct(one_chip, (b, v, v)),
            _struct(one_chip, (b,), jnp.int32),
            _struct(one_chip, (b,), jnp.int32),
            _struct(one_chip, (g * v, heads, f)),
            _struct(one_chip, (g * v, heads)),
            _struct(one_chip, (g * v, heads)))
    fn = jax.jit(lambda *a: ops.attention_block_spmm_padded(
        *a, g, interpret=False))
    _assert_kernel(fn.lower(*args).compile())


@pytest.mark.parametrize("bucket,f_in", BUCKETS)
def test_gat_executor_compiles(one_chip, chip_lowering, bucket, f_in):
    """The vmapped GAT executor at its published widths (8 heads of 8,
    then one head of 7) on pallas_fused, as the gat-cora cell serves it:
    its attention runs in the kernel, never in the jnp oracle."""
    model = build_model("gat", f_in, 7, hidden=8, heads=8)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    entry = ModelRegistry().register("gat", model, params, task="node")
    pool = ExecutorPool(slots=4, backend="pallas_fused")
    text = pool.lower(entry, bucket, sharding=one_chip).compile().as_text()
    # One kernel per GAT layer.
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("bucket,f_in", [
    *BUCKETS,
    pytest.param(Bucket(num_blocks=256, num_dst_groups=256,
                        num_src_groups=256, v=20, n=20, f=1024), 602,
                 id="reddit"),
])
def test_slot_writer_compiles_in_place(topo, bucket, f_in):
    """The slot writer of a pool pinned to one chip updates its donated
    slot set in place: every output aliases its input and the program
    needs no scratch copy of the stack."""
    mesh = Mesh(np.array(topo.devices[:1]), ("data",))
    pool = ExecutorPool(slots=8, backend="pallas_fused", mesh=mesh)
    with pool._lock:
        writer = pool._writer(pool.stack_shape(bucket))
    assert writer.as_text().count("may-alias") == 4
    assert writer.memory_analysis().temp_size_in_bytes == 0


def test_quantized_matmul_compiles(one_chip):
    x = _struct(one_chip, (256, 256))
    w = _struct(one_chip, (256, 256))
    fn = jax.jit(lambda x, w: ops.quantized_matmul_kernel(x, w,
                                                          interpret=False))
    _assert_kernel(fn.lower(x, w).compile())
