"""Compile the main-path device programs for a described TPU v5e chip.

Nothing runs here: the TPU compiler that ships with JAX compiles each
program for one chip of a described ``v5e:2x2`` topology, at the widths a
real run uses, and raises what the chip's compiler would refuse (unaligned
blocks, unsupported gathers, too much VMEM). Each compile takes about two
seconds. The topology is described inside a fixture, so that only the test
worker given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_vcs import LINEITEM_SCHEMA
from repro.core.objects import OBJECT_CAPACITY
from repro.kernels.rowhash import rowhash_pallas
from repro.kernels.search import lower_bound_lanes, probe_lanes
from repro.kernels.segsum_diff import LANES, boundary_pallas

KEY_LANES = 2 * len(LINEITEM_SCHEMA.primary_key)
ROW_LANES = 2 * len(LINEITEM_SCHEMA.columns)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    prev_cache = jax.config.jax_enable_compilation_cache
    prev_log = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev_cache)
        cc.reset_cache()
        if prev_log is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = prev_log


def _u32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.uint32, sharding=sharding)


def _is_pallas(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lanes", [KEY_LANES, ROW_LANES])
def test_rowhash_compiles_for_v5e(one_chip, lanes):
    rows = 8192
    compiled = rowhash_pallas.lower(
        _u32((lanes, rows // 128, 128), one_chip)).compile()
    assert _is_pallas(compiled)
    assert compiled.memory_analysis().output_size_in_bytes == 4 * rows * 4


def test_probe_compiles_against_object_table(one_chip):
    tab = tuple(_u32((OBJECT_CAPACITY,), one_chip) for _ in range(4))
    q = tuple(_u32((1024,), one_chip) for _ in range(4))
    compiled = probe_lanes.lower(tab, q).compile()
    assert compiled.memory_analysis().output_size_in_bytes >= 2 * 1024 * 4


def test_lower_bound_compiles_beyond_vmem(one_chip):
    n = 4 << 20                     # 32 MiB of key lanes: no VMEM holds it
    tab = tuple(_u32((n,), one_chip) for _ in range(2))
    q = tuple(_u32((1024,), one_chip) for _ in range(2))
    compiled = lower_bound_lanes.lower(tab, q).compile()
    assert compiled.memory_analysis().argument_size_in_bytes >= 2 * n * 4


def test_segsum_compiles_at_object_rows(one_chip):
    rows = OBJECT_CAPACITY // LANES
    keys = [_u32((rows, LANES), one_chip) for _ in range(4)]
    compiled = boundary_pallas.lower(*keys).compile()
    assert _is_pallas(compiled)
