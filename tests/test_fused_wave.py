"""Fused acceptor-wave kernel (`accept_commit_packed`) parity.

The fused call composes the SAME packed accept and commit bodies, in
the same order the manager's split handlers run them (accepts first,
then commits), so device state and both outputs must be bit-identical
to the two sequential calls — including the interaction case where an
accept and the commit for the same (group, slot) land in one wave.

Below them, the same for ``node_wave_packed`` (PR 35): a worker batch's
four roles in one program against the four packed stage kernels in
sequence, and ``ColumnarBackend.wave_submit`` against the pair calls it
replaces, over every way a batch can hold its roles.
"""

import jax
import jax.numpy as jnp
import numpy as np

from gigapaxos_tpu.ops import kernels, make_state, pack_ballot
from gigapaxos_tpu.ops.types import NO_BALLOT, NO_SLOT, split_req_id


def _mkstate(G=8, W=8):
    st = make_state(G, W)
    rows = jnp.arange(G, dtype=jnp.int32)
    st, _ = kernels.create_groups(
        st, rows, jnp.full(G, 3, jnp.int32), jnp.zeros(G, jnp.int32),
        jnp.full(G, pack_ballot(0, 0), jnp.int32),
        jnp.zeros(G, bool), jnp.ones(G, bool))
    return st


def _pack(cols, fills, B, n):
    out = np.zeros((len(cols) + 1, B), np.int32)
    for i, (c, fill) in enumerate(zip(cols, fills)):
        if fill:
            out[i, n:] = fill
        out[i, :n] = c
    out[len(cols), :n] = 1
    return jnp.asarray(out)


def _tree_equal(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(fa, fb))


def test_fused_wave_matches_sequential():
    bal = pack_ballot(1, 0)
    # accepts: slots 0,1 on groups 0,1; plus group 2 slot 0
    ag = np.asarray([0, 1, 2], np.int32)
    aslot = np.asarray([0, 1, 0], np.int32)
    abal = np.full(3, bal, np.int32)
    alo, ahi = zip(*[split_req_id(r) for r in (201, 202, 203)])
    # commits: group 0 slot 0 (same slot as its accept in THIS wave —
    # the rapid-pipeline coalesce case), group 3 slot 0 (never
    # accepted here: out-of-order commit installs the decision)
    cg = np.asarray([0, 3], np.int32)
    cslot = np.asarray([0, 0], np.int32)
    clo, chi = zip(*[split_req_id(r) for r in (201, 204)])
    B = 8

    acc = _pack([ag, aslot, abal, alo, ahi],
                [0, NO_SLOT, NO_BALLOT, 0, 0], B, 3)
    com = _pack([cg, cslot, clo, chi], [0, NO_SLOT, 0, 0], B, 2)

    st_f = _mkstate()
    st_f, ao_f, co_f = kernels.accept_commit_p(st_f, acc, com)

    st_s = _mkstate()
    st_s, ao_s = kernels.accept_p(st_s, acc)
    st_s, co_s = kernels.commit_p(st_s, com)

    assert np.array_equal(np.asarray(ao_f), np.asarray(ao_s))
    assert np.array_equal(np.asarray(co_f), np.asarray(co_s))
    assert _tree_equal(st_f, st_s)
    # sanity on semantics, not just parity: all three accepts acked,
    # both commits applied, group 0's cursor advanced past slot 0
    ao = np.asarray(ao_f)
    co = np.asarray(co_f)
    assert ao[0, :3].all()
    assert co[0, :2].all()
    assert int(np.asarray(st_f.exec_cursor)[0]) == 1


def test_fused_wave_empty_lane_padding():
    """All-invalid lanes on either side must be pure no-ops."""
    B = 8
    acc = jnp.zeros((6, B), jnp.int32)
    com = jnp.zeros((5, B), jnp.int32)
    st0 = _mkstate()
    st1, ao, co = kernels.accept_commit_p(_mkstate(), acc, com)
    assert _tree_equal(st0, st1)
    assert not np.asarray(ao)[0].any()
    assert not np.asarray(co)[0].any()


def test_fused_coord_wave_matches_sequential():
    """request_reply_packed == propose_accept_self then
    accept_reply_commit_self, bit-identical state and outputs."""
    me = 0
    bal = pack_ballot(1, me)
    B = 8
    st0 = _mkstate()
    rows = jnp.arange(8, dtype=jnp.int32)
    # make `me` coordinator with an outstanding proposal on group 0
    st0, _ = kernels.install_coordinator(
        st0, rows, jnp.full(8, bal, jnp.int32), jnp.zeros(8, jnp.int32),
        jnp.full((8, 8), NO_SLOT, jnp.int32), jnp.zeros((8, 8), jnp.int32),
        jnp.zeros((8, 8), jnp.int32), jnp.ones(8, bool))
    lo, hi = split_req_id(301)
    seed = _pack([[0], [lo], [hi], [0]], [0, 0, 0, 0], B, 1)
    st0, _ = kernels.propose_accept_self_p(st0, seed)  # slot 0 in flight

    # wave: new request on group 1 + a peer ack for group 0 slot 0
    plo, phi = split_req_id(302)
    req = _pack([[1], [plo], [phi], [0]], [0, 0, 0, 0], B, 1)
    rep = _pack([[0], [0], [bal], [1], [1]],
                [0, NO_SLOT, NO_BALLOT, 0, 0], B, 1)

    st_f = jax.tree_util.tree_map(lambda x: jnp.array(x), st0)
    st_s = jax.tree_util.tree_map(lambda x: jnp.array(x), st0)

    st_f, po_f, ro_f = kernels.request_reply_p(st_f, req, rep)
    st_s, po_s = kernels.propose_accept_self_p(st_s, req)
    st_s, ro_s = kernels.accept_reply_commit_self_p(st_s, rep)

    assert np.array_equal(np.asarray(po_f), np.asarray(po_s))
    assert np.array_equal(np.asarray(ro_f), np.asarray(ro_s))
    assert _tree_equal(st_f, st_s)
    # semantics: the peer ack + our own fused vote = quorum of 2/3 ->
    # group 0 slot 0 newly decided; group 1 got slot 0 granted
    assert int(np.asarray(ro_f)[0, 0]) == 1
    assert int(np.asarray(po_f)[0, 0]) == 1


# --------------------------------------------------------------------------
# node_wave_p: one worker batch's four roles in one program (PR 35)
# --------------------------------------------------------------------------

import itertools  # noqa: E402

import pytest  # noqa: E402

from gigapaxos_tpu.paxos.backend import ColumnarBackend, _BUCKET_CAP  # noqa: E402,E501

ME, G, W = 0, 64, 8
B0, B1, B2 = pack_ballot(0, ME), pack_ballot(1, 1), pack_ballot(2, ME)
ROLES = kernels.WAVE_SECTIONS
_u64 = np.uint64


def wave_backend(mesh="off"):
    """A slab on which every role finds work: this node coordinates all
    64 rows at (0, ME); rows 0..15 and 20 have slot 0 in flight (own vote
    cast, a peer's ack decides it); rows 5 and 6 were re-won at (2, ME)
    and have slot 0 in flight at that ballot (the handoff cases)."""
    bk = ColumnarBackend(G, W, mesh=mesh)
    rows = np.arange(G, dtype=np.int32)
    bk.create(rows, np.full(G, 3, np.int32), np.zeros(G, np.int32),
              np.full(G, B0, np.int32), np.ones(G, bool))
    won = np.asarray([5, 6], np.int32)
    bk.install_coordinator(won, np.full(2, B2, np.int32),
                           np.zeros(2, np.int32),
                           np.full((2, W), NO_SLOT, np.int32),
                           np.zeros((2, W), _u64))
    seeded = np.asarray(list(range(16)) + [20], np.int32)
    bk.propose_self(seeded, seeded.astype(_u64) + _u64(1000),
                    np.full(len(seeded), ME, np.int32))
    return bk


def _i32(*xs):
    return np.asarray(xs, np.int32)


def wave_case(case):
    """The four sections of a wave, as ``wave_submit`` takes them, None
    for a role the case leaves out."""
    req = (np.arange(24, 32, dtype=np.int32),
           np.arange(24, 32).astype(_u64) + _u64(2000),
           np.full(8, ME, np.int32))
    rep = (np.arange(8, 16, dtype=np.int32), np.zeros(8, np.int32),
           np.full(8, B0, np.int32), np.ones(8, np.int32),
           np.ones(8, bool))
    acc = (np.arange(32, 40, dtype=np.int32), np.zeros(8, np.int32),
           np.full(8, B1, np.int32),
           np.arange(32, 40).astype(_u64) + _u64(3000))
    com = (np.arange(40, 46, dtype=np.int32), np.zeros(6, np.int32),
           np.arange(40, 46).astype(_u64) + _u64(4000))
    full = dict(zip(ROLES, (req, rep, acc, com)))
    if case == "handoff":
        # row 5: a peer's ack at OUR new ballot beside the old regime's
        # accept for the next slot (nacked: the promise is ours); row 6:
        # a stale ack at the old ballot (counts for nothing) beside a
        # newer regime's accept (acked, and it preempts us)
        full["rep"] = (_i32(5, 6), _i32(0, 0), _i32(B2, B0), _i32(1, 1),
                       np.ones(2, bool))
        full["acc"] = (_i32(5, 6), _i32(1, 0), _i32(B1, pack_ballot(3, 1)),
                       np.asarray([5001, 5002], _u64))
        keep = ("rep", "acc")
    elif case == "req_com_same_row":
        # row 20: slot 0's commit comes back while slot 1 is proposed
        full["req"] = (_i32(20, 21), np.asarray([6001, 6002], _u64),
                       _i32(ME, ME))
        full["com"] = (_i32(20), _i32(0), np.asarray([1020], _u64))
        keep = ("req", "com")
    elif case == "chunked":
        # 5,000 accept lanes: two chunks of the bucket cap, beside one
        # chunk's worth of the other roles
        n = _BUCKET_CAP + 904
        i = np.arange(n)
        full["acc"] = ((32 + i % 32).astype(np.int32),
                       (i // 32).astype(np.int32),
                       np.full(n, B1, np.int32), i.astype(_u64) + _u64(7000))
        keep = ROLES
    else:
        keep = ROLES if case == "all" else tuple(case.split("+"))
    return [full[r] if r in keep else None for r in ROLES]


WAVE_CASES = (["all"] + list(ROLES)
              + ["+".join(p) for p in itertools.combinations(ROLES, 2)]
              + ["handoff", "req_com_same_row", "chunked"])


def _packed_sections(secs, B):
    """Each given section packed as its own kernel takes it."""
    def split(reqs):
        lo, hi = zip(*[split_req_id(int(r)) for r in reqs]) \
            if len(reqs) else ((), ())
        return np.asarray(lo, np.int32), np.asarray(hi, np.int32)

    req, rep, acc, com = secs
    out = [jnp.zeros((k, B), jnp.int32) for k in kernels.WAVE_IN]
    if req is not None:
        out[0] = _pack([req[0], *split(req[1]), req[2]], [0, 0, 0, 0], B,
                       len(req[0]))
    if rep is not None:
        out[1] = _pack([*rep[:4], rep[4].astype(np.int32)],
                       [0, NO_SLOT, NO_BALLOT, 0, 0], B, len(rep[0]))
    if acc is not None:
        out[2] = _pack([*acc[:3], *split(acc[3])],
                       [0, NO_SLOT, NO_BALLOT, 0, 0], B, len(acc[0]))
    if com is not None:
        out[3] = _pack([*com[:2], *split(com[2])], [0, NO_SLOT, 0, 0], B,
                       len(com[0]))
    return out


@pytest.mark.parametrize("case", WAVE_CASES)
def test_node_wave_matches_the_four_calls(case):
    """``node_wave_p`` == ``propose_accept_self_p``,
    ``accept_reply_commit_self_p``, ``accept_p``, ``commit_p`` in
    sequence on the same packed sections: every output row and every
    leaf of the state bit-identical."""
    secs = wave_case(case)
    B = 8
    while B < max(len(s[0]) for s in secs if s is not None):
        B *= 8
    packed = _packed_sections(secs, B)
    st0 = wave_backend().state
    st_w = jax.tree_util.tree_map(lambda x: jnp.array(x), st0)
    st_s = jax.tree_util.tree_map(lambda x: jnp.array(x), st0)

    st_w, out = kernels.node_wave_p(st_w, jnp.concatenate(packed))
    outs = []
    for name, p in zip(("propose_accept_self_p",
                        "accept_reply_commit_self_p", "accept_p",
                        "commit_p"), packed):
        st_s, o = getattr(kernels, name)(st_s, p)
        outs.append(o)

    assert out.shape == (sum(kernels.WAVE_OUT), B)
    for (lo, hi), o, role in zip(kernels.WAVE_OUT_CUTS, outs, ROLES):
        assert np.array_equal(np.asarray(out[lo:hi]), np.asarray(o)), role
    assert _tree_equal(st_w, st_s)
    if case == "all":
        # semantics, not just parity: eight grants, eight decisions with
        # the own commit applied, eight acks, six commits applied
        o = np.asarray(out)
        assert o[0, :8].all() and o[9, :8].all() and o[9 + 6, :8].all()
        assert o[18, :8].all() and o[22, :6].all()
    if case == "handoff":
        o = np.asarray(out)
        assert o[9, :2].tolist() == [1, 0]    # only OUR ballot's ack counts
        assert o[18, :2].tolist() == [0, 1]   # old regime nacked, new acked


def _res_equal(a, b, msg):
    if isinstance(a, tuple) and not hasattr(a, "_fields"):
        for i, (x, y) in enumerate(zip(a, b)):
            _res_equal(x, y, f"{msg}[{i}]")
    elif hasattr(a, "_fields"):
        for x, y, f in zip(a, b, a._fields):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"{msg}.{f}")
    else:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=msg)


def check_wave_submit(case, mesh="off"):
    """``wave_submit`` on one backend against ``propose_self_reply`` +
    ``accept_commit`` (the pair the wave replaces) on another."""
    secs = wave_case(case)
    one, pair = wave_backend(mesh), wave_backend("off")
    assert (one.engine_mesh == "off") == (mesh == "off")
    launches0 = one.launches
    got = one.wave_submit(*secs).collect()
    empty = wave_case("all")
    req, rep, acc, com = [
        s if s is not None else tuple(c[:0] for c in e)
        for s, e in zip(secs, empty)]
    want = (*pair.propose_self_reply(*req, *rep),
            *pair.accept_commit(*acc, *com))
    for g, w, s, role in zip(got, want, secs, ROLES):
        if s is None:
            assert g is None, role
        else:
            _res_equal(g, w, f"{case}.{role}")
    assert _tree_equal(one.state, pair.state)
    n = max(len(s[0]) for s in secs if s is not None)
    assert one.launches - launches0 == -(-n // _BUCKET_CAP)


@pytest.mark.parametrize("case", WAVE_CASES)
def test_wave_submit_matches_the_pair_calls(case):
    check_wave_submit(case)
