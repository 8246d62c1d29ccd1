"""Compile-only checks of the main path's kernels for a TPU v5e.

The TPU compiler (Mosaic) is installed without a chip, and it compiles for a
described ``v5e:2x2`` topology: what it refuses here -- a block that breaks
the (8, 128) tiling, an op with no lowering -- it would refuse on the chip.
Nothing runs, so these tests say nothing about results or times.

All of these compiles live in this one file: the TPU library admits one
process at a time, so the topology is described inside a module fixture (in
the worker that runs this file) and never while a module is imported.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.bayesnet import SCENARIOS, by_name, compile_network
from repro.distributed.context import mesh_context
from repro.kernels.bayes_decide.ops import bayes_decide
from repro.kernels.pand_popcount.ops import pand_popcount

KERNEL = dict(use_kernel=True, interpret=False)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep the cache off for these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile_net(name, batch, n_bits, decide, sharding, ev_sharding=None):
    spec = by_name(name)
    net = compile_network(spec, n_bits=n_bits, **KERNEL)
    fn = net.decide if decide else net.run
    key = _sds((2,), jnp.uint32, sharding)
    ev = _sds((batch, len(spec.evidence)), jnp.int32, ev_sharding or sharding)
    return net, jax.jit(fn).lower(key, ev).compile()


@pytest.mark.parametrize("decide", [False, True], ids=["posterior", "decide"])
@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_net_sweep_compiles_production_launch(one_chip, name, decide):
    """1024 frames x 4096 bits: the batch launch, in-kernel decide on and off."""
    _, compiled = _compile_net(name, 1024, 4096, decide, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_bits", [128, 4096])
@pytest.mark.parametrize("bucket", [1, 2, 4, 8])
@pytest.mark.parametrize("name", ["pedestrian-night", "obstacle-class"])
def test_net_sweep_compiles_short_buckets(one_chip, name, bucket, n_bits):
    """The driver's short power-of-two buckets: blocks must span the batch."""
    _, compiled = _compile_net(name, bucket, n_bits, True, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_bayes_decide_compiles_quickstart_shape(one_chip):
    """The fusion operator at the README quickstart shape (2, 4096, 2)."""
    key = _sds((2,), jnp.uint32, one_chip)
    p = _sds((2, 4096, 2), jnp.float32, one_chip)
    compiled = bayes_decide.lower(key, p, n_bits=128, **KERNEL).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pand_popcount_compiles(one_chip):
    streams = _sds((2, 4096, 4), jnp.uint32, one_chip)
    compiled = pand_popcount.lower(streams, **KERNEL).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("decide", [False, True], ids=["posterior", "decide"])
def test_sharded_sweep_compiles_on_four_chips(topo, decide):
    """The scale-out path: one shard_map launch over a 4-chip frames mesh."""
    mesh = Mesh(np.asarray(topo.devices[:4]), ("frames",))
    with mesh_context(mesh):
        net, compiled = _compile_net(
            "intersection", 4096, 4096, decide,
            NamedSharding(mesh, P()), NamedSharding(mesh, P("frames")),
        )
    assert net.n_shards == 4
    assert "tpu_custom_call" in compiled.as_text()
