"""Compile rehearsal: every main-path Pallas kernel, compiled (not
interpreted) for a described TPU v5e chip at the paper's published widths.

Interpret mode never enforces Mosaic's rules — (8, 128) block tiling,
scalar stores into VMEM, the scoped VMEM limit — so the CPU parity tests
cannot see a kernel the chip's compiler would refuse.  These tests run the
TPU compiler that ships with JAX against shapes only: nothing executes, so
they say nothing about results or speed.

The topology is described inside a fixture, never at import time: only one
process at a time may load the TPU library, and pytest-xdist workers all
import this module.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.datasets import STATS
from repro.kernels.gcn_fused.kernel import gcn_fused_kernel, gcn_network_kernel
from repro.kernels.spmm_abft.kernel import spmm_abft_kernel

BLOCK = 128
NETWORK_STRIPES = 8      # a packed serving batch the network kernel admits


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _lanes(n: int) -> int:
    return -(-n // BLOCK) * BLOCK


def _widths(name: str):
    """(stripes, ELL width, lane-padded feature / hidden widths) of the
    full published graph; synthetic ER graphs fill every column block, so
    the ELL width is the stripe count."""
    st = STATS[name]
    nbm = -(-st.nodes // BLOCK)
    return nbm, nbm, _lanes(st.feat_dim), _lanes(st.hidden)


def _compile(kernel, sharding, shapes, **static):
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
            for shape, dtype in shapes]
    compiled = jax.jit(functools.partial(kernel, interpret=False, **static)
                       ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()   # a Mosaic kernel ran


def _block_ell(nbm, width):
    return [((nbm, width), jnp.int32),
            ((nbm, width, BLOCK, BLOCK), jnp.float32)]


DATASETS = ["cora", "pubmed"]


@pytest.mark.parametrize("inject", [None, (1, 2, 5.0)],
                         ids=["clean", "inject"])
@pytest.mark.parametrize("name", DATASETS)
def test_spmm_abft_compiles(one_chip, name, inject):
    """At the logical width the cells run: the kernel lays x_r into X's
    lane padding itself."""
    nbm, width, _f, _gp = _widths(name)
    g = STATS[name].hidden
    _compile(spmm_abft_kernel, one_chip,
             _block_ell(nbm, width) + [((nbm * BLOCK, g), jnp.float32),
                                       ((nbm * BLOCK, 1), jnp.float32)],
             inject=inject)


@pytest.mark.parametrize("variant", [
    {},
    {"with_check": False},
    {"with_slots": True},
    {"inject": (1, 2, 5.0)},
], ids=["check", "nocheck", "slots", "inject"])
@pytest.mark.parametrize("name", DATASETS)
def test_gcn_fused_compiles(one_chip, name, variant):
    nbm, width, f, g = _widths(name)
    _compile(gcn_fused_kernel, one_chip,
             _block_ell(nbm, width) + [((nbm * BLOCK, f), jnp.float32),
                                       ((f, g), jnp.float32),
                                       ((f, 1), jnp.float32)],
             **variant)


@pytest.mark.parametrize("variant", [
    {},
    {"with_check": False},
    {"inject": (1, 1, 2, 5.0), "stash_acts": True},
], ids=["check", "nocheck", "inject-stash"])
@pytest.mark.parametrize("name", DATASETS)
def test_gcn_network_compiles(one_chip, name, variant):
    _nbm, _width, f, _g = _widths(name)
    nbm = width = NETWORK_STRIPES
    p = f                      # the shared width is the widest layer
    _compile(gcn_network_kernel, one_chip,
             _block_ell(nbm, width) + [((nbm * BLOCK, p), jnp.float32),
                                       ((2, p, p), jnp.float32),
                                       ((2, p, 1), jnp.float32)],
             **variant)


@pytest.mark.parametrize("inject", [None, (257, 0, 4.0)],
                         ids=["clean", "inject"])
def test_sharded_aggregation_compiles_on_four_chips(topo, inject):
    """NELL's widest layer on a 2x2 mesh: the all-gather of X and x_r,
    then the stripe-sharded kernel and its psum'd corner, with the fault
    hook on the shard that owns stripe 257.  Each chip's operands fit a
    quarter of the table."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.engine import sharded

    mesh = Mesh(np.asarray(topo.devices), ("graph",))
    st = STATS["nell"]
    nbm = -(-st.nodes // BLOCK)
    stripes = -(-nbm // 4) * 4
    rows, width, g = stripes * BLOCK, 410, st.classes

    def arg(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))
    gather = sharded._gather_program(mesh, "graph", 2).lower(
        arg((rows, g), jnp.float32, "graph"),
        arg((rows, 1), jnp.float32, "graph")).compile()
    assert "all-gather" in gather.as_text()
    program = sharded._spmm_program(mesh, "graph", nbm * BLOCK, BLOCK, BLOCK,
                                    False, "layer", inject)
    compiled = program.lower(
        arg((stripes, width), jnp.int32, "graph"),
        arg((stripes, width, BLOCK, BLOCK), jnp.float32, "graph"),
        arg((rows, g), jnp.float32), arg((rows, 1), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "all-reduce" in text
    tiles = stripes // 4 * width * BLOCK * BLOCK * 4
    assert compiled.memory_analysis().argument_size_in_bytes < 1.1 * tiles
