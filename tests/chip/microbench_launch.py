"""What a launch of node_wave_p costs the HOST by the number of state
leaves (PR 32 read 435 us + 6.3 us a leaf): the same script on the parent
(22 leaves) and on this tree (12), one thread, warm, bucket 8, a 2^20-row
slab.  ``python3 tests/chip/microbench_launch.py <tree root> <label>``; merges
into chiprun_out/microbench_pr37.json under "launch.<label>"."""
import json
import os
import statistics
import sys
import time

root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from gigapaxos_tpu.ops import kernels, make_state  # noqa: E402

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "chiprun_out", "microbench_pr37.json")
CAP = int(os.environ.get("MB_CAP", 1 << 20))
CALLS = int(os.environ.get("MB_CALLS", 2000))


def main():
    st = make_state(CAP, 16)
    n = 4096
    rows = jnp.arange(n, dtype=jnp.int32)
    st, _ = kernels.create_groups(
        st, rows, jnp.full((n,), 3, jnp.int32), jnp.zeros((n,), jnp.int32),
        jnp.zeros((n,), jnp.int32), jnp.ones((n,), bool),
        jnp.ones((n,), bool))
    leaves = len(jax.tree_util.tree_leaves(st))
    packed = np.zeros((22, 8), np.int32)  # four sections of padding lanes
    res = {"leaves": leaves, "calls": CALLS,
           "device": jax.devices()[0].device_kind}
    for _ in range(20):
        st, out = kernels.node_wave_p(st, jnp.asarray(packed))
    jax.block_until_ready(out)
    for rnd in range(3):
        launch, whole = [], []
        for _ in range(CALLS):
            dev = jnp.asarray(packed)
            t0 = time.perf_counter()
            st, out = kernels.node_wave_p(st, dev)
            t1 = time.perf_counter()
            np.asarray(out)
            t2 = time.perf_counter()
            launch.append(t1 - t0)
            whole.append(t2 - t0)
        res[f"round{rnd}"] = {
            "launch_us_mean": 1e6 * statistics.fmean(launch),
            "launch_us_p50": 1e6 * statistics.median(launch),
            "launch_and_copy_back_us_p50": 1e6 * statistics.median(whole)}
    old = json.load(open(OUT)) if os.path.exists(OUT) else {}
    old[f"launch.{sys.argv[2]}"] = res
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    json.dump(old, open(OUT, "w"), indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
