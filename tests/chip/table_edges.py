"""On the chip, at the cells' own size: full-range words written into the
group table come back bit for bit through the 128-word row read (the
masked row sums of ``kernels._group_words``), through the read views and
through a kernel that answers with them.  ``tests/test_group_table.py``
holds the same on the CPU at toy sizes; this is the check that the
device's integer row reduce is exact above 2^24.

    chiprun -- python3 tests/chip/table_edges.py
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from gigapaxos_tpu.ops import kernels, make_state  # noqa: E402
from gigapaxos_tpu.ops.types import GROUP_COLS, RowState  # noqa: E402

G, W, N = int(os.environ.get("EDGE_G", 1 << 20)), 16, 4096


def main():
    rng = np.random.default_rng(37)
    rows = np.sort(rng.choice(G, N, replace=False)).astype(np.int32)
    want = {}
    for f, dt, _ in GROUP_COLS:
        if dt == np.bool_:
            want[f] = rng.random(N) < 0.5
        else:  # every bit pattern, the extremes among them
            a = rng.integers(0, 1 << 32, N, dtype=np.uint64).astype(
                np.uint32)
            a[:4] = (0, 0x7FFFFFFF, 0x80000000, 0xFFFFFFFF)
            want[f] = a.view(np.int32) if dt == np.int32 else a
    want["active"][:] = True  # so that prepare answers for every row
    st = make_state(G, W)
    empty = jax.device_get(kernels.gather_rows(st, rows))
    row = empty._replace(**want)
    st, _ = jax.jit(kernels.scatter_rows, donate_argnums=0)(
        st, jnp.asarray(rows), RowState(*map(jnp.asarray, row)),
        jnp.ones((N,), bool))
    got = jax.device_get(kernels.gather_rows(st, rows))
    wrong = {}
    for f, dt, _ in GROUP_COLS:
        a, v = getattr(got, f), np.asarray(getattr(st, f))[rows]
        assert a.dtype == dt and v.dtype == dt, f
        wrong[f] = [int((a != want[f]).sum()), int((v != want[f]).sum())]
    # a kernel's answer: the promise and the cursor of a prepare that
    # does not move the ballot
    st, out = kernels.prepare(
        st, jnp.asarray(rows), jnp.full((N,), -(1 << 31), jnp.int32),
        jnp.ones((N,), bool))
    wrong["prepare.cur_bal"] = int(
        (np.asarray(out.cur_bal) != want["bal"]).sum())
    wrong["prepare.exec_cursor"] = int(
        (np.asarray(out.exec_cursor) != want["exec_cursor"]).sum())
    ok = not any(np.sum(v) for v in wrong.values())
    print(json.dumps({"ok": bool(ok), "device": jax.devices()[0].device_kind,
                      "groups": G, "rows": N, "wrong": wrong}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
