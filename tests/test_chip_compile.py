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
STORM_LANES = 1 << 18  # the storm cell's step, at its own size
SERVING_TEMP, STORM_TEMP = 32 << 20, 1 << 30
# 768 B per group at W=16, from shapes: eleven window planes of sixteen
# words and the sixteen-word row of the group table (739 B while the
# scalars were eleven [G] arrays, before PR 37)
SLAB_BYTES = 768 * CAPACITY

# the nine packed serving kernels ColumnarBackend._warm_kernels warms,
# with the row count of each packed [k, bucket] input
SERVING = {
    "propose_p": (4,), "accept_p": (6,), "accept_reply_p": (6,),
    "commit_p": (5,), "propose_accept_self_p": (5,),
    "accept_reply_commit_self_p": (6,), "accept_commit_p": (6, 5),
    "request_reply_p": (5, 6), "node_wave_p": (22,),
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


_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) \(.*\{$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")
# what moves a plane and is no scatter: each of these, at a plane's size,
# is a pass or two over 67 MB or more that a wave would pay
_RELAYOUTS = {"copy", "reshape", "broadcast", "transpose",
              "dynamic-update-slice"}
# the compiler's own staging of an array in its fast memory (``S(1)``) and
# back: asynchronous, the linear order kept
_STAGING = {"copy-done"}


def _words(dims):
    """Elements of a shape printed as ``4096,128``."""
    n = 1
    for d in filter(None, dims.split(",")):
        n *= int(d)
    return n


def _plane_moves(compiled, words, ops):
    """The instructions of the compiled program, of the kinds ``ops``,
    that produce an array of ``words`` words or more.  Read outside the
    fused computations: what is inside a fusion is never an array in
    memory, the fusion's own result is."""
    text = compiled.as_text()
    fused = set(re.findall(r"\bfusion\(.*?calls=(%[\w.\-]+)", text))
    found, skip = [], False
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            skip = head.group(1) in fused
            continue
        m = None if skip else _INSTRUCTION.match(line)
        if m and m.group(2) in ops and _words(m.group(1)) >= words:
            found.append(line.strip()[:160])
    return found


@pytest.fixture(scope="module")
def compiled(topo, no_persistent_cache):
    """Each hot program compiled once for the described chip, by name:
    the cases below read the one compile from more than one side."""
    made = {}

    def get(name, bucket=BUCKET):
        if (name, bucket) not in made:
            chip = SingleDeviceSharding(topo.devices[0])
            if name == "storm":
                from gigapaxos_tpu.ops.storm import storm
                lane = jax.ShapeDtypeStruct((STORM_LANES,), jnp.int32,
                                            sharding=chip)
                valid = jax.ShapeDtypeStruct((STORM_LANES,), jnp.bool_,
                                             sharding=chip)
                made[name, bucket] = _compile(
                    storm, tuple(_state_shapes(CAPACITY, WINDOW, chip)
                                 for _ in range(3)),
                    lane, lane, lane, valid)
            else:
                from gigapaxos_tpu.ops import kernels
                made[name, bucket] = _compile(
                    getattr(kernels, name),
                    _state_shapes(CAPACITY, WINDOW, chip),
                    *[_packed(k, chip, bucket) for k in SERVING[name]])
        return made[name, bucket]
    return get


def _serving(name):
    def run(topo, compiled):
        ma = compiled(name).memory_analysis()
        assert SLAB_BYTES <= ma.argument_size_in_bytes < 1.01 * SLAB_BYTES
        # a wave's scratch is lanes, not planes: the widest wave (two
        # inputs of 4,096 lanes, a [4096, 128] row read) asks SERVING_TEMP
        # beside its slab, where a program that copied ONE component
        # plane would ask 67 MB (request_reply_p at 64 lanes asked
        # 270,886,400 B before PR 32)
        assert ma.temp_size_in_bytes < SERVING_TEMP
    return run


def _storm(topo, compiled):
    ma = compiled("storm").memory_analysis()
    assert ma.argument_size_in_bytes >= 3 * SLAB_BYTES
    # three replicas' planes are updated in place: the step's scratch is
    # lane arrays and the [B, 128] row reads (3,285,974,528 B before
    # PR 32, beside 2,328,100,864 B of state)
    assert ma.temp_size_in_bytes < STORM_TEMP


def _no_relayout(name, bucket=BUCKET, stagings=0):
    """No copy, reshape, broadcast, transpose or dynamic-update-slice of
    a component plane or of the group table (which has a plane's 2^24
    words at W=16; or of anything larger) in the program: each is only
    ever the operand of a scatter, in place, or of a gather, and is seen
    as rows of 128 words through a bitcast.  ``stagings``: the one thing
    left is the compiler's to choose, its own asynchronous copies of an
    array into its fast memory (``S(1)``) and back, the linear order
    kept.  At 4,096 lanes and in the storm step it keeps a plane that a
    stage scatters into and the next reads there (``dec_slot``,
    ``prop_votes``, and since PR 37 the group table, which every stage
    reads and most set: the row reads of a table it holds there cost a
    third); the counts are what it chooses today, the resign branches'
    included.  At the lane counts the served cells run (8-1,024) it
    stages none."""
    def run(topo, compiled):
        c, words = compiled(name, bucket), CAPACITY * WINDOW
        moved = _plane_moves(c, words, _RELAYOUTS)
        assert not moved, "\n".join(moved)
        copies = _plane_moves(c, words, _STAGING)
        assert len(copies) <= stagings, "\n".join(copies)
    return run


_SHAPE = re.compile(r"\b(?:pred|[suf]\d+)\[([\d,]+)\]")


def _no_group_array(name):
    """No gather and no scatter, inside a fusion or out of one, has an
    operand of ``G`` elements: a stage takes its groups' scalars out of
    ONE row read of the group table and sets them a word at a time, and
    there is no ``[G]`` array to index (PR 37; the storm step read
    fourteen of them a step, 43% of its time)."""
    def run(topo, compiled):
        found = []
        for line in compiled(name).as_text().splitlines():
            if not re.search(r" (?:gather|scatter)\(", line):
                continue
            if any(_words(dims) == CAPACITY
                   for dims in _SHAPE.findall(line)):
                found.append(line.strip()[:200])
        assert not found, "\n".join(found)
    return run


def _twelve_leaves(topo, _compiled):
    from gigapaxos_tpu.ops.types import GROUP_WORDS
    leaves = jax.tree_util.tree_leaves(_state_shapes(CAPACITY, WINDOW, None))
    assert len(leaves) == 12
    assert sorted(a.shape for a in leaves) == sorted(
        [(CAPACITY * GROUP_WORDS,)] + [(CAPACITY * WINDOW,)] * 11)


def _mesh(name):
    def run(topo, _compiled):
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


def _pallas(topo, _compiled):
    from gigapaxos_tpu.ops.pallas_accept import _accept_blocks
    chip = SingleDeviceSharding(topo.devices[0])
    G, Rb, L = 1 << 14, 4096, 16

    def s(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    c = _accept_blocks.lower(
        s((Rb,)), s((G,)), s((G,), jnp.bool_), s((G,)),
        *[s((Rb, L))] * 6, *[s((G * WINDOW,))] * 4, False).compile()
    assert "tpu_custom_call" in c.as_text()


CASES = {**{f"serving.{n}": _serving(n) for n in SERVING},
         "storm.decide_storm_step": _storm,
         # the window planes are linear and addressed a word at a time:
         # no program copies, reshapes or broadcasts one (PR 32)
         **{f"no_plane_relayout.serving.{n}": _no_relayout(
             n, stagings={"request_reply_p": 3, "node_wave_p": 5}.get(n, 2))
            for n in SERVING},
         **{f"no_plane_relayout.serving.{n}.bucket64": _no_relayout(n, 64)
            for n in ("request_reply_p", "accept_commit_p", "node_wave_p")},
         "no_plane_relayout.storm.decide_storm_step": _no_relayout(
             "storm", stagings=12),
         # the groups' scalars are one word table (PR 37)
         **{f"no_group_array.serving.{n}": _no_group_array(n)
            for n in SERVING},
         "no_group_array.storm.decide_storm_step": _no_group_array("storm"),
         "state.twelve_leaves": _twelve_leaves,
         "mesh.accept_p": _mesh("accept_p"),
         "mesh.accept_commit_p": _mesh("accept_commit_p"),
         "pallas._accept_blocks": _pallas}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, topo, compiled):
    CASES[case](topo, compiled)


def test_boot_loads_node_wave_at_every_bucket():
    """``_warm_kernels`` leaves ``node_wave_p`` traced at all four
    buckets of the ladder and hot, so that no batch, whatever roles and
    however many lanes it holds, traces it again: a program first used
    inside a window compiles there (``tests/test_failover_five.py`` holds
    the election programs to the same)."""
    import numpy as np

    from gigapaxos_tpu.paxos.backend import ColumnarBackend, _ladder
    from gigapaxos_tpu.utils.engineledger import EngineLedger

    def ledger():
        return EngineLedger.kernels().get(
            "node_wave_p", {"compiles": 0, "retraces": 0})

    before = ledger()["compiles"]
    G = 1064  # a state shape of this test's own
    bk = ColumnarBackend(G, WINDOW, mesh="off")
    booted = ledger()
    assert booted["compiles"] - before == len(list(_ladder())) == 4
    assert booted["hot"]
    rows = np.arange(G, dtype=np.int32)
    bk.create(rows, np.full(G, 3, np.int32), np.zeros(G, np.int32),
              np.zeros(G, np.int32), np.ones(G, bool))
    rng = np.random.default_rng(35)
    for n in (3, 40, 300, 2000, 5000):  # every bucket, and a chunked wave
        r = rng.integers(0, G, n).astype(np.int32)
        ids = rng.integers(1, 1 << 40, n).astype(np.uint64)
        z = np.zeros(n, np.int32)
        secs = [(r, ids, z), (r, z, z, z + 1, np.ones(n, bool)),
                (r, z, z, ids), (r, z, ids)]
        for mask in ((1, 1, 1, 1), (1, 0, 0, 0), (0, 1, 1, 0), (0, 0, 0, 1)):
            bk.wave_submit(*[s if on else None
                             for s, on in zip(secs, mask)]).collect()
    after = ledger()
    assert after["compiles"] == booted["compiles"], after
    assert after["retraces"] == 0
