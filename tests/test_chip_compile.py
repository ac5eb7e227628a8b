"""Compile the main path's programs for a DESCRIBED TPU v5e, at the real
sizes, without a chip: what the chip's compiler would refuse (a program
that does not fit, a kernel Mosaic rejects, a collective that should not
be there) fails here, at no chip time.

This is the only file that describes the chip.  The topology is described
inside a module-scoped fixture — never at import, in a ``skipif`` or in
``parametrize`` arguments — because the process that describes it loads
the TPU library, and only the xdist worker that is handed this file may
do that.  Nothing runs: a compile that passes is not a chip run.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

CAPACITY, WINDOW, BUCKET = 1 << 20, 16, 4096
# 739 B per group at W=16 (PR 18's slab accounting, from shapes)
SLAB_BYTES = 739 * CAPACITY

# the eight packed serving kernels ColumnarBackend._warm_kernels warms,
# with the row count of each packed [k, bucket] input
SERVING = {
    "propose_p": (4,), "accept_p": (6,), "accept_reply_p": (6,),
    "commit_p": (5,), "propose_accept_self_p": (5,),
    "accept_reply_commit_self_p": (6,), "accept_commit_p": (6, 5),
    "request_reply_p": (5, 6),
}


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep it out
    from jax.experimental.compilation_cache import compilation_cache
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


def _state_shapes(G, W, sharding):
    from gigapaxos_tpu.ops.types import make_state
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        jax.eval_shape(lambda: make_state(G, W)))


def _packed(rows, sharding, bucket=BUCKET):
    return jax.ShapeDtypeStruct((rows, bucket), jnp.int32,
                                sharding=sharding)


def _compile(fn, *shapes):
    from gigapaxos_tpu.utils.engineledger import EngineLedger
    with EngineLedger.warming():  # a lowering is not a retrace incident
        return fn.lower(*shapes).compile()


def _serving(name):
    def run(topo):
        from gigapaxos_tpu.ops import kernels
        chip = SingleDeviceSharding(topo.devices[0])
        c = _compile(getattr(kernels, name),
                     _state_shapes(CAPACITY, WINDOW, chip),
                     *[_packed(k, chip) for k in SERVING[name]])
        ma = c.memory_analysis()
        assert SLAB_BYTES <= ma.argument_size_in_bytes < 1.01 * SLAB_BYTES
        # slab + the wave's scratch must fit one v5e beside two more
        # in-process nodes' slabs (16 GB; ROADMAP S6 notes the scratch)
        assert ma.temp_size_in_bytes < 3 << 30
    return run


def _storm(topo):
    from gigapaxos_tpu.ops.storm import storm
    chip = SingleDeviceSharding(topo.devices[0])
    G, B = 1 << 14, 1 << 12
    lane = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=chip)
    valid = jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=chip)
    c = _compile(storm, tuple(_state_shapes(G, WINDOW, chip)
                              for _ in range(3)), lane, lane, lane, valid)
    assert c.memory_analysis().argument_size_in_bytes >= 3 * 739 * G


def _mesh(name):
    def run(topo):
        from gigapaxos_tpu.ops.meshkernels import GROUP_AXIS, MeshKernels
        mesh = Mesh(topo.devices, (GROUP_AXIS,))
        assert mesh.size == 4
        rows = NamedSharding(mesh, PartitionSpec(GROUP_AXIS))
        repl = NamedSharding(mesh, PartitionSpec())
        c = _compile(getattr(MeshKernels(mesh), name),
                     _state_shapes(CAPACITY, WINDOW, rows),
                     *[_packed(k, repl) for k in SERVING[name]])
        # one psum per wave: the outputs ride a single all-reduce
        n = len(re.findall(r" all-reduce(?:-start)?\(", c.as_text()))
        assert n == 1, f"{n} all-reduces in mesh.{name}"
        per_chip = c.memory_analysis().argument_size_in_bytes
        assert SLAB_BYTES / 4 <= per_chip < 1.01 * SLAB_BYTES / 4
    return run


def _pallas(topo):
    from gigapaxos_tpu.ops.pallas_accept import _accept_blocks
    chip = SingleDeviceSharding(topo.devices[0])
    G, Rb, L = 1 << 14, 4096, 16

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    c = _accept_blocks.lower(
        s((Rb,)), s((G,)), s((G,), jnp.bool_), s((G,)),
        *[s((Rb, L))] * 6, *[s((G, WINDOW))] * 4, False).compile()
    assert "tpu_custom_call" in c.as_text()


CASES = {**{f"serving.{n}": _serving(n) for n in SERVING},
         "storm.decide_storm_step": _storm,
         "mesh.accept_p": _mesh("accept_p"),
         "mesh.accept_commit_p": _mesh("accept_commit_p"),
         "pallas._accept_blocks": _pallas}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, topo, no_persistent_cache):
    CASES[case](topo)
