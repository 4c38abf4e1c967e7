"""The sort's exchange step compiled for the chip it runs on, with no chip:
the TPU's compiler is installed here and compiles for a described v5e 2x2
host, so what it would refuse on the machine (a shape it cannot tile, a
program that does not fit a chip, a collective it cannot place) is refused
here, at the configuration's real shapes (``sort-gensort-mesh4``: a chunk
of 1 MiB a device, a store of 1,436,672 rows a device).  Nothing runs: a
compile that passes is not a chip run.

The topology is described inside a fixture, and only there: one process at
a time may load the TPU's library, so nothing in this file touches it
while the file is imported (``on-chip-measurement`` guide, section 2).
These are the repository's only tests that load it; keep others that do in
this file.
"""

import os

import numpy as np
import pytest

jax = pytest.importorskip("jax")


@pytest.fixture(scope="module")
def mesh():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from dsi_tpu.parallel.shuffle import AXIS

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return Mesh(np.array(topo.devices), (AXIS,))


def test_the_exchange_step_compiles_for_four_chips_at_the_cells_shapes(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dsi_tpu.ops import sortk
    from dsi_tpu.parallel.shuffle import AXIS
    from dsi_tpu.parallel.sortstream import device_capacity

    per = (1 << 20) // sortk.RECORD_BYTES
    capacity = device_capacity(5_368_704, (25_000,) * 4, per)
    assert capacity == 1_436_672

    def shaped(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    compiled = sortk.exchange_fn(per, mesh).lower(
        shaped((4 * capacity, 25), np.uint32, AXIS, None),
        shaped((3, 4 * capacity), np.uint32, None, AXIS),
        shaped((4,), np.int32, AXIS),
        shaped((4, sortk.chunk_words(1 << 20)), np.uint32, AXIS, None),
        shaped((9, 3), np.uint32), shaped((3, 3), np.uint32)).compile()
    text = compiled.as_text()
    # the records cross the chips in one collective, and the stores are
    # appended to in place: what a device is handed it gives back
    assert text.count(" all-to-all(") + text.count(" all-to-all-start(") == 1
    memory = compiled.memory_analysis()
    store_and_lanes = capacity * (32 + 3) * 4   # rows tile to 32 words
    assert memory.alias_size_in_bytes >= store_and_lanes
    # a step's own buffers: the send and receive blocks, tiled to 128 lanes
    assert memory.temp_size_in_bytes < 256 << 20
