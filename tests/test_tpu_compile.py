"""The chip's compiler, asked without the chip.

The TPU compiler is installed where the tests run, and it compiles for a
chip that is described and not attached.  Interpret-mode Pallas
(tests/test_pallas.py) cannot see what Mosaic refuses — the 1-D sign
concatenate of the merged decompress passed every interpret-mode test and
failed to lower for a v5e — so the executables production selects on a TPU
are compiled here for a described ``v5e:2x2``: one chip at the 128- and
10,240-lane buckets (the packed input), and the four-device ``shard_map``
form at 10,240, 8,192 and 256.
Nothing runs, so this says nothing about verdicts or times; chip_smoke.py
does that on the chip.

All in this one file, the chip described inside a module-scoped fixture:
only one process may hold the TPU library, and under xdist only the worker
that is handed this file loads it.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from cometbft_tpu.ops import verify as ov
from cometbft_tpu.parallel import mesh as pmesh


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # what is compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(lanes: int, two_d, one_d) -> dict:
    byte = jax.ShapeDtypeStruct((lanes, 32), jnp.uint8, sharding=two_d)
    return dict(
        a_bytes=byte,
        r_bytes=byte,
        s_bytes=byte,
        m_bytes=byte,
        s_ok=jax.ShapeDtypeStruct((lanes,), jnp.bool_, sharding=one_d),
    )


@pytest.mark.parametrize("lanes", [128, 10240])
def test_pallas_bucket_compiles_for_one_chip(topo, lanes):
    """What ``bucket_executable("pallas", lanes)`` builds on a TPU: the
    Pallas kernel behind the ONE packed input, split on the device by row
    slices that fuse into the unpacking (no (4, lanes, 32) copy of the
    four tables)."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    packed = jax.ShapeDtypeStruct(
        (ov.packed_rows(lanes), 32), jnp.uint8, sharding=one_chip
    )
    compiled = ov._bucket_jitted("pallas").lower(packed).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    assert f"u8[4,{lanes},32]" not in text


@pytest.mark.parametrize("lanes", [10240, 8192, 256])
def test_pallas_mesh_compiles_for_four_chips(topo, lanes):
    """What ``sharded_verify_call`` builds on a four-chip host: the kernel
    inside ``shard_map`` and the one ``psum`` as an all-reduce.  At the
    10,240-lane commit bucket; at 8,192, the light prefix of that commit
    and what the served path launches for it (2,048 lanes a chip); and at
    256, the smallest bucket the mesh takes (``elastic.min_batch()``), 64
    lanes a chip, under the kernel's tile."""
    mesh = Mesh(np.array(topo.devices), (pmesh.SIG_AXIS,))
    assert mesh.devices.size == 4
    jitted, _ = pmesh.sharded_verify_fn(mesh, impl="pallas", donated=True)
    two_d, one_d = pmesh.mesh_shardings(mesh)
    shapes = _shapes(lanes, two_d, one_d)
    compiled = jitted.lower(*(shapes[k] for k in pmesh.ARG_ORDER)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "all-reduce" in text
