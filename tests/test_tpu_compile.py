"""Compile the fused device loop for a described TPU v5e chip.

No chip is needed: the TPU compiler is installed and compiles for a
described, unattached device. What it refuses here (an f64 construct it
cannot rewrite, a program too large for the device) it would refuse on
the chip. Each compile takes tens of seconds, so the file holds two:

  * ``_device_rounds`` at the full grid's first-chunk signature (the
    1024-row rung the 1116-row ``full_matrix`` plans; the grid's other
    rungs differ only in rows, C and P);
  * ``_device_rounds_coupled`` at the tenant-smoke fleet's signature
    (6 groups, 29 coupled rows).

Both are the donated twins the async executor runs.
"""
import os

import pytest

#: (rows, C, K, P, B, T, Q), the canonical signature of the full grid's
#: first chunk as ``bucketing.canonical_signature`` plans it
GRID_SIG = (1024, 8, 4, 16, 1, 1, 16384)
#: the tenant-smoke fleet's one coupled chunk, plus its bucketed link
#: and group axes
FLEET_SIG = (32, 16, 4, 16, 1, 1, 1024)
FLEET_LINKS, FLEET_GROUPS = 32, 8
#: bytes one program may hold on the device: arguments, outputs and
#: scratch (16 GB of HBM on a v5e; these loops need tens of MB)
MAX_DEVICE_BYTES = 64 << 20


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A program compiled for a described chip cannot be read back
    here: keep it out of any persistent cache."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", saved)
        cc.reset_cache()


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


def test_grid_loop_compiles_for_v5e(topo, no_compile_cache):
    from repro.eval.fabric import jax_backend
    from repro.eval.fabric.bucketing import COMPACT_FLOOR
    from repro.eval.fabric.jaxenv import x64

    with x64():
        mut, const, qsizes = jax_backend.signature_shapes(
            GRID_SIG, topo.devices[0]
        )
        compiled = jax_backend._device_rounds_donated.lower(
            mut, const, qsizes, COMPACT_FLOOR
        ).compile()
    assert 0 < _device_bytes(compiled) < MAX_DEVICE_BYTES


def test_fleet_loop_compiles_for_v5e(topo, one_chip, no_compile_cache):
    import jax
    import jax.numpy as jnp

    from repro.eval.fabric import jax_backend
    from repro.eval.fabric.bucketing import COMPACT_FLOOR
    from repro.eval.fabric.jaxenv import x64

    rows = FLEET_SIG[0]

    def aval(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with x64():
        mut, const, qsizes = jax_backend.signature_shapes(
            FLEET_SIG, topo.devices[0]
        )
        fab = {
            "gid": aval((rows,), jnp.int64),
            "member": aval((FLEET_LINKS, rows), jnp.bool_),
            "link_cap": aval((FLEET_LINKS,), jnp.float64),
            "gslot": aval((FLEET_GROUPS,), jnp.float64),
        }
        compiled = jax_backend._device_rounds_coupled_donated.lower(
            mut, const, qsizes, fab, COMPACT_FLOOR
        ).compile()
    assert 0 < _device_bytes(compiled) < MAX_DEVICE_BYTES
