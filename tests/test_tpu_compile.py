"""Compile rehearsals for a described TPU v5e: the main path's kernels
and collectives at Mixtral-8x7B widths, compiled by the TPU compiler
without a chip attached.

What the CPU tests cannot show: interpret-mode Pallas accepts blocks the
Mosaic compiler refuses (for more VMEM than a launch may use, or tiles
not aligned to the hardware).  Each case compiles one program for one
described v5e chip, or for the 2x2 host's four chips, in a couple of
seconds.  Nothing runs: results and times need the chip.

The topology is described inside a module-scoped fixture: only one
process at a time may load the TPU compiler's library, so no module
makes that call while it is imported.
"""

import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P, SingleDeviceSharding

from repro.kernels.moe_gemm import (
    moe_gemm_grouped_pallas,
    moe_gemm_grouped_pallas_dgrad,
    moe_gemm_grouped_pallas_wgrad,
    select_backward_block_f,
    select_block_sizes,
)

E, D, F = 8, 4096, 14336  # Mixtral-8x7B: experts, d_model, d_ff_expert


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("kernel", ["forward", "dgrad", "wgrad"])
@pytest.mark.parametrize("c", [256, 1024])
def test_moe_gemm_compiles_at_mixtral_widths(one_chip, kernel, c):
    """The blocks the selectors pick compile for v5e: the working set
    fits the VMEM limit the launch asks for."""
    block_c, block_f = select_block_sizes(c, D, F)
    bwd_f = select_backward_block_f(c, D, F, block_c)
    assert bwd_f is not None, "backward fell back to the einsum oracle"

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x, g = sds((E, c, D)), sds((E, c, D))
    w_in, w_out = sds((E, D, F)), sds((E, F, D))
    meta = sds((E * (c // block_c),), jnp.int32)
    if kernel == "forward":
        compiled = _compile(
            lambda x, m, a, b, w: moe_gemm_grouped_pallas(
                x, m, a, b, w, block_c=block_c, block_f=block_f,
                interpret=False,
            ),
            x, meta, w_in, w_in, w_out,
        )
    else:
        fn = (
            moe_gemm_grouped_pallas_dgrad
            if kernel == "dgrad"
            else moe_gemm_grouped_pallas_wgrad
        )
        compiled = _compile(
            lambda g, x, m, a, b, w: fn(
                g, x, m, a, b, w, block_c=block_c, block_f=bwd_f,
                interpret=False,
            ),
            g, x, meta, w_in, w_in, w_out,
        )
    assert "tpu_custom_call" in compiled.as_text()


def test_ragged_all_to_all_compiles_on_2x2(topo):
    """The ``ragged_a2a`` fabric's transfer — one live peer per rank, the
    rest dark — compiles to the ragged collective over the four chips of
    the 2x2 host, expert-parallel over 'model' as ``launch.train`` builds
    its mesh."""
    from repro.parallel.fabric.ragged_a2a import RaggedA2AFabric

    mesh = Mesh(
        np.asarray(topo.devices).reshape(1, 4),
        ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    n = 4
    ctx = SimpleNamespace(n=n, axis="model")  # the FabricContext fields read

    def body(block):
        me = jax.lax.axis_index("model")
        return RaggedA2AFabric()._ragged_send(
            ctx, block, (me + 1) % n, jnp.bool_(True), (me - 1) % n,
            jnp.bool_(True),
        )

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=P("model"), out_specs=P("model"),
        check_vma=False,
    )
    rows = jax.ShapeDtypeStruct(
        (n * 256, D), jnp.bfloat16,
        sharding=jax.sharding.NamedSharding(mesh, P("model")),
    )
    text = _compile(fn, rows).as_text()
    assert "ragged-all-to-all" in text
