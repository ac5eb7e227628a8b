"""shard_map variants of the columnar kernels (device-mesh engine).

Every leaf of :class:`~gigapaxos_tpu.ops.types.ColumnarState` (the
linear ``[G * 16]`` group table and the eleven linear ``[G * W]``
window-plane components, each of which a cut on axis 0 divides into
whole groups) is sharded on its one axis over a 1-D ``Mesh`` named
:data:`GROUP_AXIS`; batch lanes stay replicated.  The
per-wave kernels run as explicit ``shard_map`` programs: each shard owns
a contiguous block of ``Gs = G / D`` rows, masks the batch down to the
lanes it owns, rewrites their row indices to shard-local ones, and runs
the UNMODIFIED kernel body from :mod:`gigapaxos_tpu.ops.kernels` on its
local state block — no cross-device gather or scatter on the hot path.
The only collective is one ``psum`` per output (each lane's result is
non-zero on exactly its owner shard), which XLA lowers to a single
all-reduce over the already-materialized ``[k, B]`` output.

Bit-parity with the unsharded kernels (proven by the blackbox replay
cross-check and ``tests/test_mesh_engine.py``) rests on one invariant:
every lane of a group lands on that group's owner shard, so the batch
computations that couple lanes — the per-group ballot ``max``, the
stable-sort run ranks of ``propose``, the post-scatter quorum re-gather
and within-batch dedup of ``accept_reply`` — see exactly the same lane
set they see unsharded.  Lanes a shard does not own are masked invalid,
which the kernel bodies already treat as padding (out-of-bounds scatter
indices with ``mode="drop"``).

:class:`MeshKernels` exposes the same attribute surface the backend's
``self._k`` indirection uses for the module-level jit entries, so
:class:`~gigapaxos_tpu.paxos.backend.ColumnarBackend` swaps it in when a
mesh is active and every op method stays untouched.  Instances are
memoized per device set (:func:`mesh_kernels`) so all backends over the
same mesh share one jit cache, exactly like the module-level entries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from gigapaxos_tpu.ops import kernels as _K
from gigapaxos_tpu.utils.engineledger import EngineLedger

GROUP_AXIS = "groups"

_i32 = jnp.int32


def _own(state, g, valid):
    """(mine, local_g): ownership mask and shard-local row indices.

    ``state`` here is the LOCAL shard block, so ``state.G`` is the rows
    per shard; global row ``g`` lives on shard ``g // Gs`` at local row
    ``g - d * Gs`` (block partitioning, the layout ``device_put`` with
    ``P(GROUP_AXIS)`` produces)."""
    d = jax.lax.axis_index(GROUP_AXIS)
    gs = state.G
    mine = valid & (g // gs == d)
    return mine, jnp.where(mine, g - d * gs, 0)


def _merge(x, mine):
    """All-reduce one LANE-LEADING output leaf (``[B]`` or ``[B, W]``):
    mask to owned lanes, psum.  Each live lane is owned by exactly one
    shard, so the sum IS the owner's value; padding lanes sum to 0 and
    are sliced off host-side."""
    m = mine.reshape(mine.shape + (1,) * (x.ndim - 1))
    if x.dtype == jnp.bool_:
        s = jax.lax.psum(jnp.where(m, x, False).astype(_i32), GROUP_AXIS)
        return s != 0
    return jax.lax.psum(jnp.where(m, x, jnp.zeros((), x.dtype)),
                        GROUP_AXIS)


def _merge_packed(out, mine):
    """Same, for the packed ``[k, B]`` outputs (lanes on the LAST axis)."""
    return jax.lax.psum(jnp.where(mine[None, :], out, 0), GROUP_AXIS)


def _packed1(body):
    """Local program for a packed ``(state, [k, B]) -> (state, [j, B])``
    kernel: packed[0] is the row index, packed[-1] the valid mask."""
    def local(state, packed):
        mine, lg = _own(state, packed[0], packed[-1] != 0)
        packed = packed.at[0].set(lg).at[-1].set(mine.astype(_i32))
        state, out = body(state, packed)
        return state, _merge_packed(out, mine)
    return local


def _packed2(body):
    """Local program for the dual-input fused waves
    (``accept_commit_packed`` / ``request_reply_packed``)."""
    def local(state, p1, p2):
        m1, lg1 = _own(state, p1[0], p1[-1] != 0)
        p1 = p1.at[0].set(lg1).at[-1].set(m1.astype(_i32))
        m2, lg2 = _own(state, p2[0], p2[-1] != 0)
        p2 = p2.at[0].set(lg2).at[-1].set(m2.astype(_i32))
        state, o1, o2 = body(state, p1, p2)
        return state, _merge_packed(o1, m1), _merge_packed(o2, m2)
    return local


def _wave_local(state, packed):
    """Local program for :func:`kernels.node_wave_packed`: each of the
    four stacked sections masked down to the lanes this shard owns, as
    :func:`_packed1` does for one, and the stacked output merged under
    its section's mask in ONE ``psum``."""
    secs, masks = [], []
    for (lo, hi), k in zip(_K.WAVE_IN_CUTS, _K.WAVE_OUT):
        p = packed[lo:hi]
        mine, lg = _own(state, p[0], p[-1] != 0)
        secs.append(p.at[0].set(lg).at[-1].set(mine.astype(_i32)))
        masks.append(jnp.broadcast_to(mine[None, :], (k,) + mine.shape))
    state, out = _K.node_wave_packed(state, jnp.concatenate(secs))
    return state, jax.lax.psum(
        jnp.where(jnp.concatenate(masks), out, 0), GROUP_AXIS)


def _rowcall(body):
    """Local program for the unpacked row ops whose first batch array is
    the row index and last is the valid mask, returning state only
    (create/delete/set_cursor/gc/install_coordinator)."""
    def local(state, g, *rest):
        mine, lg = _own(state, g, rest[-1])
        state, _none = body(state, lg, *rest[:-1], mine)
        return state
    return local


def _prepare_local(state, g, bal, valid):
    mine, lg = _own(state, g, valid)
    state, o = _K.prepare_batch(state, lg, bal, mine)
    return state, type(o)(*[_merge(x, mine) for x in o])


class MeshKernels:
    """The backend's kernel table, compiled as shard_map programs over
    one mesh.  Attribute names match the module-level jit entries in
    :mod:`gigapaxos_tpu.ops.kernels` that ``ColumnarBackend`` drives
    through ``self._k``; state buffers are donated exactly like them."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh
        sh = P(GROUP_AXIS)   # pytree prefix: every state leaf on axis 0
        rp = P()             # batch lanes / outputs: replicated

        def jit1(name, local, n_in, out_specs):
            # the ledger wraps the shard_map program (not the local
            # body): one trace event per (mesh kernel, signature)
            return jax.jit(
                EngineLedger.traced(
                    f"mesh.{name}",
                    jax.shard_map(local, mesh=mesh,
                                  in_specs=(sh,) + (rp,) * n_in,
                                  out_specs=out_specs, check_vma=False)),
                donate_argnums=0)

        # packed hot entries: (state, [k, B]) -> (state, [j, B])
        self.propose_p = jit1(
            "propose_p", _packed1(_K.propose_packed), 1, (sh, rp))
        self.accept_p = jit1(
            "accept_p", _packed1(_K.accept_packed), 1, (sh, rp))
        self.accept_reply_p = jit1(
            "accept_reply_p", _packed1(_K.accept_reply_packed), 1,
            (sh, rp))
        self.commit_p = jit1(
            "commit_p", _packed1(_K.commit_packed), 1, (sh, rp))
        self.propose_accept_self_p = jit1(
            "propose_accept_self_p",
            _packed1(_K.propose_accept_self_packed), 1, (sh, rp))
        self.accept_reply_commit_self_p = jit1(
            "accept_reply_commit_self_p",
            _packed1(_K.accept_reply_commit_self_packed), 1, (sh, rp))
        # fused dual-input waves
        self.accept_commit_p = jit1(
            "accept_commit_p", _packed2(_K.accept_commit_packed), 2,
            (sh, rp, rp))
        self.request_reply_p = jit1(
            "request_reply_p", _packed2(_K.request_reply_packed), 2,
            (sh, rp, rp))
        # one worker batch's four sections in one program
        self.node_wave_p = jit1("node_wave_p", _wave_local, 1, (sh, rp))
        # the read index's two: the state handed back as it came
        self.read_index_p = jit1(
            "read_index_p", _packed1(_K.read_index_packed), 1, (sh, rp))
        self.confirm_p = jit1(
            "confirm_p", _packed1(_K.confirm_packed), 1, (sh, rp))
        # unpacked cold/control ops
        self.prepare = jit1("prepare", _prepare_local, 3, (sh, rp))
        self._install = jit1(
            "install_coordinator",
            _rowcall(_K.install_coordinator_batch), 7, sh)
        self._create = jit1(
            "create_groups", _rowcall(_K.create_groups_batch), 6, sh)
        self._delete = jit1(
            "delete_groups", _rowcall(_K.delete_groups_batch), 2, sh)
        self._set_cursor = jit1(
            "set_cursor", _rowcall(_K.set_cursor_batch), 4, sh)
        self._gc = jit1("gc", _rowcall(_K.gc_batch), 3, sh)

    # state-only ops keep the module entries' (state, None) return shape
    def install_coordinator(self, state, *args):
        return self._install(state, *args), None

    def create_groups(self, state, *args):
        return self._create(state, *args), None

    def delete_groups(self, state, *args):
        return self._delete(state, *args), None

    def set_cursor(self, state, *args):
        return self._set_cursor(state, *args), None

    def gc(self, state, *args):
        return self._gc(state, *args), None


_MESH_KERNELS: dict = {}


def mesh_kernels(mesh: Mesh) -> MeshKernels:
    """Memoized per device set + axis names: every backend over the
    same mesh shares ONE MeshKernels (hence one jit cache), matching
    the compile economics of the shared module-level entries."""
    key = (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)
    mk = _MESH_KERNELS.get(key)
    if mk is None:
        mk = _MESH_KERNELS[key] = MeshKernels(mesh)
    return mk
