"""Compile the device path for a described v5e chip (no chip attached).

The TPU compiler refuses what the CPU backend and the Pallas interpreter
accept: a Mosaic kernel that cannot be legalized, a program that does not
fit the chip's memory.  These tests hand it the real shapes: the packed
step at the CLI defaults and at a larger batch, the superbatch step, and
both Pallas kernels with ``interpret=False``, and the mesh backend's
collective merge on a 2x2 mesh.  Nothing runs; a pass is not a chip run.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every xdist worker imports this
file (on-chip-measurement guide §2).  The session's conftest keeps the
persistent compilation cache off, so no TPU entry lands in the checkout.
"""

import dataclasses
import os

import pytest

from kafka_topic_analyzer_tpu.config import AnalyzerConfig
from kafka_topic_analyzer_tpu.jax_support import jnp

#: One v5e chip's HBM.
V5E_HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # pragma: no cover - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, args, donate=()):
    import jax

    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        - mem.alias_size_in_bytes
        + mem.temp_size_in_bytes
    )
    assert total < V5E_HBM_BYTES, (total, mem)
    return compiled


def _state_shapes(config, sharding):
    import jax

    from kafka_topic_analyzer_tpu.models.state import AnalyzerState

    return jax.tree.map(
        lambda a: _shape(sharding, a.shape, a.dtype),
        jax.eval_shape(lambda: AnalyzerState.init(config)),
    )


def _scan_config(bits, batch_size):
    """`-c --distinct-keys --quantiles` over 64 partitions."""
    return AnalyzerConfig(
        num_partitions=64,
        batch_size=batch_size,
        count_alive_keys=True,
        alive_bitmap_bits=bits,
        enable_hll=True,
        enable_quantiles=True,
    )


def _pair_shape(config, k, sharding):
    from kafka_topic_analyzer_tpu.packing import (
        pair_table_capacity,
        pair_table_nbytes,
    )

    cap = pair_table_capacity(config, config.batch_size, k)
    return _shape(sharding, (pair_table_nbytes(config, cap),), jnp.uint8)


@pytest.mark.parametrize(
    "bits,batch_size",
    [(32, 1 << 18), (26, 1 << 20)],
    ids=["cli-defaults-bits32-B2^18", "bits26-B2^20"],
)
def test_packed_step_compiles_for_v5e(one_chip, bits, batch_size):
    from kafka_topic_analyzer_tpu.backends.tpu import make_packed_step
    from kafka_topic_analyzer_tpu.packing import packed_nbytes

    config = _scan_config(bits, batch_size)
    assert config.compact_alive
    args = (
        _state_shapes(config, one_chip),
        _shape(one_chip, (packed_nbytes(config, batch_size),), jnp.uint8),
        _pair_shape(config, 1, one_chip),
    )
    _compile(make_packed_step(config), args, donate=(0,))


def test_superstep_compiles_for_v5e(one_chip):
    from kafka_topic_analyzer_tpu.backends.tpu import make_packed_superstep
    from kafka_topic_analyzer_tpu.packing import packed_nbytes

    k = 4
    config = _scan_config(26, 1 << 18)
    args = (
        _state_shapes(config, one_chip),
        _shape(
            one_chip, (k, packed_nbytes(config, config.batch_size)), jnp.uint8
        ),
        _pair_shape(config, k, one_chip),
    )
    _compile(make_packed_superstep(config, k), args, donate=(0,))


def test_pallas_counters_update_compiles_for_v5e(one_chip):
    from kafka_topic_analyzer_tpu.ops.pallas_counters import (
        pallas_counters_update,
    )

    p, b = 64, 1 << 18

    def fn(per_partition, part, klen, vlen, knull, vnull, valid):
        return pallas_counters_update(
            per_partition, part, klen, vlen, knull, vnull, valid, p,
            interpret=False,
        )

    i32 = [_shape(one_chip, (b,), jnp.int32) for _ in range(3)]
    flags = [_shape(one_chip, (b,), jnp.bool_) for _ in range(3)]
    compiled = _compile(fn, [_shape(one_chip, (p, 7), jnp.int64)] + i32 + flags)
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_counters_merge_compiles_for_v5e(one_chip):
    from kafka_topic_analyzer_tpu.ops.pallas_counters import (
        pallas_counters_merge,
    )

    table = _shape(one_chip, (64, 7), jnp.int64)
    compiled = _compile(
        lambda a, b: pallas_counters_merge(a, b, interpret=False),
        [table, table],
    )
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("mesh_shape", [(4, 1), (2, 2)], ids=["4", "2x2"])
def test_sharded_merge_compiles_for_v5e(topo, monkeypatch, mesh_shape):
    """The finalize collectives: the TPU lowers only SUM all-reduces of
    64-bit integers, so a 64-bit pmin/pmax there is refused."""
    import jax
    from jax.sharding import NamedSharding

    from kafka_topic_analyzer_tpu.parallel import sharded
    from kafka_topic_analyzer_tpu.parallel.mesh import make_mesh

    # Described devices hold no arrays: place the initial state as shapes.
    monkeypatch.setattr(
        sharded,
        "_global_put",
        lambda x, mesh, spec: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=NamedSharding(mesh, spec)
        ),
    )
    config = dataclasses.replace(
        _scan_config(26, 1 << 18), mesh_shape=mesh_shape
    )
    backend = sharded.ShardedTpuBackend(
        config, mesh=make_mesh(*mesh_shape, devices=topo.devices), init_now_s=0
    )
    compiled = backend._merge.lower(backend.state).compile()
    assert "all-reduce" in compiled.as_text()
