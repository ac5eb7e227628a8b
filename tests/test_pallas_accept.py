"""Pallas acceptor kernel vs the XLA scatter path (interpret mode on
CPU; the TPU compile probe happens in ColumnarBackend init)."""

import numpy as np
import pytest

from gigapaxos_tpu.ops import kernels
from gigapaxos_tpu.ops.pallas_accept import PallasAccept, group_lanes_by_block
from gigapaxos_tpu.ops.types import ACC_RHI, ACC_RLO, ACC_SLOT, make_state


def _mk_state(G=64, W=8, n_active=56):
    import jax.numpy as jnp

    st = make_state(G, W)
    rows = jnp.arange(n_active, dtype=jnp.int32)
    st, _ = kernels.create_groups(
        st, rows, jnp.full(n_active, 3, jnp.int32),
        jnp.zeros(n_active, jnp.int32), jnp.zeros(n_active, jnp.int32),
        jnp.zeros(n_active, bool), jnp.ones(n_active, bool))
    return st


def test_group_lanes_by_block_overflow():
    # rows 5,2 share octile 0; rows 17,18 share octile 2
    rows = np.asarray([5, 5, 5, 2, 17, 18], np.int32)
    uniq, lane_index, overflow = group_lanes_by_block(rows, L=3)
    assert list(uniq) == [0, 2]
    # octile 0 lanes: first three of batch idx 0,1,2,3 (lane order)
    assert set(lane_index[0]) == {0, 1, 2}
    assert set(lane_index[1][lane_index[1] >= 0]) == {4, 5}
    assert overflow.sum() == 1 and overflow[3]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pallas_accept_matches_xla(seed):
    """Bit-parity with the XLA path requires the whole batch in one
    kernel call (overflow splits are a different — still valid —
    linearization, covered below): distinct rows + L=8 guarantee ≤8
    lanes per octile."""
    import jax.numpy as jnp

    G, W, B = 64, 8, 48
    rng = np.random.default_rng(seed)
    st_ref = _mk_state(G, W)
    st_pal = _mk_state(G, W)

    pal = PallasAccept(L=8, interpret=True)
    for round_ in range(3):
        g = rng.permutation(G)[:B].astype(np.int32)  # incl. inactive
        slot = rng.integers(-2, W + 4, B).astype(np.int32)
        bal = rng.integers(0, 5, B).astype(np.int32) * 4  # packed-ish
        rlo = rng.integers(1, 1 << 30, B).astype(np.int32)
        rhi = rng.integers(1, 1 << 30, B).astype(np.int32)
        valid = rng.random(B) < 0.9

        st_ref, o = kernels.accept(
            st_ref, jnp.asarray(g), jnp.asarray(slot), jnp.asarray(bal),
            jnp.asarray(rlo), jnp.asarray(rhi), jnp.asarray(valid))
        st_pal, (acked, stale, out_win, cur_bal) = pal(
            st_pal, g, slot, bal, rlo, rhi, valid)

        np.testing.assert_array_equal(np.asarray(o.acked), acked,
                                      err_msg=f"round {round_} acked")
        np.testing.assert_array_equal(np.asarray(o.stale), stale)
        np.testing.assert_array_equal(np.asarray(o.out_window), out_win)
        np.testing.assert_array_equal(
            np.asarray(o.cur_bal)[valid], cur_bal[valid])
        for field in ("bal", "acc"):
            np.testing.assert_array_equal(
                np.asarray(getattr(st_ref, field)),
                np.asarray(getattr(st_pal, field)),
                err_msg=f"round {round_} state.{field}")


def test_pallas_accept_untouched_rows_preserved():
    """The aliased in-place outputs must keep rows the grid never
    visits (this is exactly what input_output_aliases guarantees)."""
    import jax.numpy as jnp

    G, W = 64, 8
    st = _mk_state(G, W)
    # accept something on row 3 first, via the XLA path
    one = lambda x: jnp.asarray(np.asarray([x], np.int32))  # noqa: E731
    st, _ = kernels.accept(st, one(3), one(0), one(0), one(7), one(9),
                           jnp.asarray([True]))
    before = np.asarray(st.acc[3, :, ACC_RLO]).copy()

    pal = PallasAccept(L=4, interpret=True)
    g = np.asarray([10, 11], np.int32)
    st, (acked, *_rest) = pal(
        st, g, np.zeros(2, np.int32), np.zeros(2, np.int32),
        np.full(2, 5, np.int32), np.full(2, 6, np.int32),
        np.ones(2, bool))
    assert acked.all()
    np.testing.assert_array_equal(np.asarray(st.acc[3, :, ACC_RLO]),
                                  before)
    assert int(st.acc[10, 0, ACC_RLO]) == 5


def test_pallas_accept_multi_lane_rows_and_overflow():
    """Several slots per row in one octile, plus an overflow spill (the
    follow-up call is a second linearization — every lane must still be
    acked and the window must hold all slots)."""
    import jax.numpy as jnp

    G, W = 64, 8
    st = _mk_state(G, W)
    pal = PallasAccept(L=4, interpret=True)
    # 6 lanes into octile 0 (rows 1 and 2, slots 0..2 each) → 2 overflow
    g = np.asarray([1, 1, 1, 2, 2, 2], np.int32)
    slot = np.asarray([0, 1, 2, 0, 1, 2], np.int32)
    bal = np.zeros(6, np.int32)
    rlo = np.arange(10, 16, dtype=np.int32)
    rhi = np.arange(20, 26, dtype=np.int32)
    st, (acked, stale, ow, cb) = pal(st, g, slot, bal, rlo, rhi,
                                     np.ones(6, bool))
    assert acked.all() and not stale.any() and not ow.any()
    for i in range(6):
        r, s = int(g[i]), int(slot[i])
        assert int(st.acc[r, s % W, ACC_SLOT]) == s
        assert int(st.acc[r, s % W, ACC_RLO]) == 10 + i
        assert int(st.acc[r, s % W, ACC_RHI]) == 20 + i


def test_columnar_backend_pallas_path(monkeypatch):
    """ColumnarBackend with the Pallas accept enabled agrees with the
    default XLA path through the backend SPI.  The backend never picks
    interpret mode by itself (an asked-for kernel builds or raises), so
    on the CPU the TEST hands it the interpreter."""
    import functools

    import jax

    from gigapaxos_tpu.ops import pallas_accept
    from gigapaxos_tpu.paxos.backend import ColumnarBackend
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.utils.config import Config

    Config.set(PC.ENGINE_MESH, "off")  # Mosaic path is single-device
    if jax.default_backend() == "cpu":
        monkeypatch.setattr(
            pallas_accept, "PallasAccept",
            functools.partial(pallas_accept.PallasAccept, interpret=True))
    G, W, B = 64, 8, 24
    rng = np.random.default_rng(7)
    bks = [ColumnarBackend(G, W, use_pallas_accept=flag)
           for flag in (False, True)]
    assert bks[1]._pallas is not None
    rows = np.arange(48, dtype=np.int32)
    for bk in bks:
        bk.create(rows, np.full(48, 3, np.int32), np.zeros(48, np.int32),
                  np.zeros(48, np.int32), np.ones(48, bool))
    for _ in range(3):
        g = rng.permutation(48)[:B].astype(np.int32)
        slot = rng.integers(0, W, B).astype(np.int32)
        bal = np.zeros(B, np.int32)
        req = rng.integers(1, 1 << 62, B).astype(np.uint64)
        outs = [bk.accept(g, slot, bal, req) for bk in bks]
        for a, b in zip(outs[0], outs[1]):
            np.testing.assert_array_equal(a, b)
