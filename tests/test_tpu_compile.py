"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel wrapper for one chip of a
``v5e:2x2`` topology at the widths the full news20 deployment
(d = 1,355,191, N = 19,954, 455 nnz per row) hands it, compiles it with
the TPU compiler, and checks that the kernel survived as a Mosaic custom
call.  That catches what the interpreter cannot — an op Mosaic has no
lowering for, a block that breaks the tiling, a window that overflows
SMEM or VMEM — without a chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

NEWS20_N = 19_954
FD_BATCH = 8  # the paper's mini-batch u for the FD methods
Q8_BLOCK, Q8_NNZ = 169_399, 161  # q=8 one-chip shares: d/8, widest block
Q4_BLOCK, Q4_NNZ = 338_798, 212  # q=4 mesh shares: d/4, stacked width
SERVE_D, SERVE_ROWS, SERVE_WIDTH = 1_355_191, 256, 512  # whole w, batcher max


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for(one_chip):
    """Lower ``fn`` at the given shapes for the described chip and
    compile it with the persistent compilation cache off (its entries
    could not be read back without a chip)."""
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def compile_(fn, *shapes):
        args = [
            jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes
        ]
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled

    yield compile_
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.mark.parametrize(
    "d_block,rows,width",
    [
        (Q8_BLOCK, NEWS20_N, Q8_NNZ),  # full-gradient margins, q=8
        (Q8_BLOCK, FD_BATCH, Q8_NNZ),  # inner-step margins, q=8
        (Q8_BLOCK, FD_BATCH, 47),  # narrowest q=8 block
        (Q4_BLOCK, NEWS20_N, Q4_NNZ),  # full-gradient margins, q=4 mesh
        (Q4_BLOCK, FD_BATCH, Q4_NNZ),  # inner-step margins, q=4 mesh
        (SERVE_D, SERVE_ROWS, SERVE_WIDTH),  # widest serving batch
        (SERVE_D, 1, 8),  # a lone narrow request
    ],
)
def test_sparse_margins_compiles(compile_for, d_block, rows, width):
    def margins(idx, val, w):
        return ops.sparse_margins(idx, val, w, interpret=False)

    compile_for(
        margins,
        ((rows, width), jnp.int32),
        ((rows, width), jnp.float32),
        ((d_block,), jnp.float32),
    )


@pytest.mark.parametrize(
    "d_block,width", [(Q8_BLOCK, Q8_NNZ), (Q4_BLOCK, Q4_NNZ), (SERVE_D, 455)]
)
@pytest.mark.parametrize(
    "lams", [(1e-4, 0.0, 0.0), (0.0, 1e-4, 0.0)], ids=["l2", "l1"]
)
def test_fused_block_prox_update_compiles(compile_for, d_block, width, lams):
    lam, lam1, lam2 = lams

    def update(w, idx, val, coef, z, eta):
        return ops.fused_block_prox_update(
            w, idx, val, coef, z, eta, lam=lam, lam1=lam1, lam2=lam2,
            interpret=False,
        )

    compile_for(
        update,
        ((d_block,), jnp.float32),
        ((FD_BATCH, width), jnp.int32),
        ((FD_BATCH, width), jnp.float32),
        ((FD_BATCH,), jnp.float32),
        ((d_block,), jnp.float32),
        ((), jnp.float32),
    )


def test_lazy_block_flush_compiles(compile_for):
    def flush(w, last, z, eta, total, stop):
        return ops.lazy_block_flush(
            w, last, z, eta, total, stop, lam=1e-4, interpret=False
        )

    compile_for(
        flush,
        ((Q8_BLOCK,), jnp.float32),
        ((Q8_BLOCK,), jnp.int32),
        ((Q8_BLOCK,), jnp.float32),
        ((), jnp.float32),
        ((), jnp.int32),
        ((), jnp.int32),
    )


@pytest.mark.parametrize(
    "kernel,args",
    [
        ("lazy_block_catchup", ("w", "last", "z", "idx", "eta", "m", "stop")),
        ("lazy_block_touch_update", ("w", "idx", "val", "coef", "z", "eta")),
        ("lazy_block_proba_update",
         ("w", "idx", "val", "coef", "z", "w", "eta")),
    ],
)
def test_lazy_kernels_refuse_to_compile_by_name(kernel, args):
    """These lazy kernels do not lower for the TPU yet; asking for one
    compiled names it instead of falling back to the interpreter or jnp."""
    w = jnp.zeros((16,), jnp.float32)
    arrays = {
        "w": w, "z": w, "last": jnp.zeros((16,), jnp.int32),
        "idx": jnp.zeros((2, 3), jnp.int32),
        "val": jnp.zeros((2, 3), jnp.float32),
        "coef": jnp.zeros((2,), jnp.float32),
        "eta": 0.1, "m": 1, "stop": 2,
    }
    with pytest.raises(NotImplementedError, match=kernel):
        getattr(ops, kernel)(
            *(arrays[a] for a in args), lam=0.0, interpret=False
        )
