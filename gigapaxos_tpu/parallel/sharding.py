"""Group-axis sharding of the columnar state over a jax Mesh.

Design (SURVEY.md §2.7, "TPU-native equivalent" column): every per-group
array (the linear ``[G * 16]`` group table and the linear ``[G * W]``
window planes) is sharded on its one axis, cut into whole groups; batch
lanes stay replicated.  The per-wave kernels run as explicit ``shard_map``
programs (:mod:`gigapaxos_tpu.ops.meshkernels`): each shard masks the
batch down to the rows it owns and runs the unmodified kernel body on its
local block — no cross-device gather/scatter on the hot path, one output
``psum`` per wave.

One node scales along ONE axis, resolved here: the **mesh**
(``PC.ENGINE_MESH``, device axis): the slab's ``[G, W]`` planes shard
over D devices; a row lives on device ``row // (G/D)``.

:func:`resolve_engine_mesh` is the single authority for the mesh knob —
``ColumnarBackend`` calls it at construction, so the storm path and the
node runtime resolve the device axis identically.

This module is used by BOTH the storm kernel (``make_sharded_storm``,
the driver dryrun and ``python -m gigapaxos_tpu.parallel``) and the node
runtime; on the test env's virtual 8-CPU mesh the e2e/failover suites
run the mesh-sharded path end to end.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gigapaxos_tpu.ops.meshkernels import GROUP_AXIS
from gigapaxos_tpu.ops.storm import decide_storm_step
from gigapaxos_tpu.ops.types import ColumnarState


def make_group_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the first ``n_devices`` (default: all)."""
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise ValueError(
                f"need {n_devices} devices, have {len(devs)} "
                "(set XLA_FLAGS=--xla_force_host_platform_device_count=N)")
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (GROUP_AXIS,))


def resolve_engine_mesh(capacity: int, devs=None) -> Optional[Mesh]:
    """Resolve ``PC.ENGINE_MESH`` into a Mesh (or None = single device).

    ``"off"`` — no mesh.  ``"auto"`` — all of ``devs`` when there are
    >1 and ``capacity`` divides evenly.  An integer N — the first N of
    ``devs``; falls back to single-device WITH a warning when the host
    has fewer devices or capacity doesn't divide (a capture recorded on
    a bigger mesh must still replay on this box, just unsharded —
    bit-parity makes that safe).
    """
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.utils.config import Config

    knob = str(Config.get(PC.ENGINE_MESH)).strip().lower()
    if knob == "off":
        return None
    if devs is None:
        devs = jax.local_devices()
    if knob == "auto":
        if len(devs) > 1 and capacity % len(devs) == 0:
            return Mesh(np.asarray(devs), (GROUP_AXIS,))
        return None
    n = int(knob)
    if n <= 1:
        return None
    if len(devs) < n or capacity % n:
        from gigapaxos_tpu.utils.logutil import get_logger
        get_logger("gp.sharding").warning(
            "ENGINE_MESH=%d needs %d devices (have %d) and capacity %% "
            "mesh == 0 (capacity=%d); running single-device",
            n, n, len(devs), capacity)
        return None
    return Mesh(np.asarray(devs[:n]), (GROUP_AXIS,))


def state_sharding(mesh: Mesh) -> ColumnarState:
    """Pytree of NamedShardings: every state field sharded on axis 0."""
    ns = NamedSharding(mesh, P(GROUP_AXIS))
    return jax.tree_util.tree_map(lambda _: ns, ColumnarState(
        *ColumnarState._fields))


def shard_fleet(states: Tuple[ColumnarState, ...], mesh: Mesh
                ) -> Tuple[ColumnarState, ...]:
    """Move replica states onto the mesh, group-axis sharded."""
    ns = NamedSharding(mesh, P(GROUP_AXIS))
    return jax.tree_util.tree_map(
        lambda a: jax.device_put(a, ns), states)


def make_sharded_storm(mesh: Mesh, n_replicas: int = 3):
    """The full decide-storm step as ONE shard_map program: every shard
    masks the wave down to its own groups (block ownership, same math as
    :mod:`gigapaxos_tpu.ops.meshkernels`), runs the whole propose ->
    accept x R -> the R replies -> commit x R pipeline on its local state
    block, and the only collective is the psum of the decided count.
    State stays resident and donated; ``n_replicas`` is pinned by the
    caller and unused here (the fleet tuple's length carries it)."""
    del n_replicas  # shape comes from the states tuple itself

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(P(GROUP_AXIS), P(), P(), P(), P()),
             out_specs=(P(GROUP_AXIS), P()), check_vma=False)
    def _local(states, g, rlo, rhi, valid):
        d = jax.lax.axis_index(GROUP_AXIS)
        gs = states[0].G  # local block: rows per shard
        mine = valid & (g // gs == d)
        lg = jnp.where(mine, g - d * gs, 0)
        states, decided = decide_storm_step(states, lg, rlo, rhi, mine)
        return states, jax.lax.psum(decided, GROUP_AXIS)

    return jax.jit(_local, donate_argnums=0)
