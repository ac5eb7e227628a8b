"""PR 37's primitives on the chip: a group's scalars through one 128-word
row of the table (three ways of taking the lane's own columns out of the
row), a one-word gather for reference, and the one-word set, at 64, 4,096
and 262,144 lanes on a 2^20-group table (s32[2^24]).  Writes
chiprun_out/microbench_pr37.json (merged with what is there)."""
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

G, GW, LANES = int(os.environ.get("MB_G", 1 << 20)), 16, 128
N = int(os.environ.get("MB_CALLS", 30))
i32 = jnp.int32
OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "chiprun_out", "microbench_pr37.json")


def rows_of(grp, gi):
    return grp.reshape(-1, LANES)[gi // 8]


def percol(ncols):
    def f(grp, gi):
        rows = rows_of(grp, gi)
        c = jnp.arange(LANES, dtype=i32)[None, :]
        base = (gi % 8) * GW
        cols = [jnp.sum(jnp.where(c == (base + k)[:, None], rows, 0), axis=1)
                for k in range(ncols)]
        return sum(cols[1:], cols[0])
    return f


def fold(ncols):
    def f(grp, gi):
        rows = rows_of(grp, gi)
        c = jnp.arange(LANES, dtype=i32)[None, :]
        x = jnp.where(c // GW == (gi % 8)[:, None], rows, 0)
        for s in (64, 32, 16):
            x = x + jnp.roll(x, s, axis=1)
        return sum((x[:, k] for k in range(1, ncols)), x[:, 0])
    return f


def resh(ncols):
    def f(grp, gi):
        rows = rows_of(grp, gi).reshape(-1, 8, GW)
        mine = jnp.arange(8, dtype=i32)[None, :, None] == \
            (gi % 8)[:, None, None]
        x = jnp.sum(jnp.where(mine, rows, 0), axis=1)
        return sum((x[:, k] for k in range(1, ncols)), x[:, 0])
    return f


def words(ncols):
    def f(grp, gi):
        return sum((grp[gi * GW + k] for k in range(1, ncols)),
                   grp[gi * GW])
    return f


def set1(grp, gi, v):
    return grp.at[gi * GW + 3].set(v, mode="drop", unique_indices=True)


def set2(grp, gi, v):
    idx = (gi[:, None] * GW + jnp.asarray([3, 4], i32)[None, :]).reshape(-1)
    return grp.at[idx].set(jnp.stack([v, v + 1], 1).reshape(-1),
                           mode="drop", unique_indices=True)


def set2chain(grp, gi, v):
    return grp.at[gi * GW + 3].set(v, mode="drop").at[gi * GW + 4].set(
        v + 1, mode="drop")


def timed(fn, *args, donate=False):
    out = fn(*args)
    jax.block_until_ready(out)
    if donate:
        args = (out,) + args[1:]
    t0 = time.perf_counter()
    for _ in range(N):
        out = fn(*args)
        if donate:
            args = (out,) + args[1:]
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / N


def main():
    res = {"device": jax.devices()[0].device_kind, "calls": N}
    rng = np.random.default_rng(37)
    for B in (64, 4096, 262144):
        gi = jnp.asarray(np.sort(rng.choice(G, B, replace=False))
                         .astype(np.int32))
        v = jnp.arange(B, dtype=i32)
        grp = jnp.asarray(rng.integers(0, 1 << 30, G * GW, dtype=np.int32))
        want = None
        for name, mk in (("row_percol", percol), ("row_fold", fold),
                         ("row_reshape", resh), ("gather_words", words)):
            for ncols in (1, 3, 6):
                try:
                    f = jax.jit(mk(ncols))
                    got = np.asarray(f(grp, gi))
                    if ncols == 3:
                        want = got if want is None else want
                        assert (got == want).all(), name
                    res[f"{name}.cols={ncols}.B={B}"] = timed(f, grp, gi)
                except Exception as e:  # a form the compiler refuses
                    res[f"{name}.cols={ncols}.B={B}"] = repr(e)[:200]
        for name, f in (("set_word", set1), ("set_2cols_one_scatter", set2),
                        ("set_2cols_chained", set2chain)):
            f = jax.jit(f, donate_argnums=0)
            res[f"{name}.B={B}"] = timed(f, grp, gi, v, donate=True)
            grp = jnp.asarray(rng.integers(0, 1 << 30, G * GW,
                                           dtype=np.int32))
    old = {}
    if os.path.exists(OUT):
        old = json.load(open(OUT))
    old["primitives"] = res
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    json.dump(old, open(OUT, "w"), indent=1)
    print(json.dumps(res, indent=0))


if __name__ == "__main__":
    sys.exit(main())
