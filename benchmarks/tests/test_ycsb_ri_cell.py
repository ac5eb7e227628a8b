"""The read-mostly record store's own parts on the CPU at tiny sizes: the
plain reference (``reference/record_store_ri.py``) by hand and against its
four controls, the read index's byte count and readers, and the driver
(``drivers/ycsb_ri.py``) through whole runs, one of them traced, and on a
program without the read index."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import loadgen_ycsb, roofline_read_index
from benchmarks.reference import record_store_ri as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "tiny_ycsb_ri")
NEW = {"read_index_share", "read_wait_ms", "read_exec_wait_ms",
       "read_index_kernels_roofline"}


# -- the reference by hand -----------------------------------------------

def by_hand():
    """Record 0 (two fields of 2 B) with two updates of field 0 and three
    reads around them; record 1 only read."""
    initial = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.uint8)
    payload = [b"R", b"U\x00aa", b"R", b"U\x00bb", b"R", b"R"]
    reply = [ref.checkpoint_of(0, b"\x01\x02\x03\x04"),
             ref.checkpoint_of(1, b""),
             ref.checkpoint_of(1, b"aa\x03\x04"),
             ref.checkpoint_of(2, b""),
             ref.checkpoint_of(2, b"bb\x03\x04"),
             ref.checkpoint_of(0, b"\x05\x06\x07\x08")]
    ops = {"record": np.asarray([0, 0, 0, 0, 0, 1]),
           "t_send": np.asarray([0.0, 1.0, 2.0, 3.0, 4.0, 0.5]),
           "t_recv": np.asarray([0.5, 1.5, 2.5, 3.5, 4.5, 0.9]),
           "status": np.zeros(6, np.int16), "payload": payload,
           "reply": reply}
    cps = [{0: ref.checkpoint_of(2, b"bb\x03\x04")} for _ in range(3)]
    return initial, ops, cps


def numbers(checks):
    return {n: v for n, v, _lim in checks}


def test_sound_operations_read_zero_everywhere():
    initial, ops, cps = by_hand()
    got = numbers(ref.check(initial, ops, 2, cps, 0))
    assert set(got) == {"answers_wrong", "places_taken_twice",
                        "order_against_real_time",
                        "reads_older_than_real_time",
                        "reads_from_the_future", "answers_refused",
                        "never_answered", "replica_groups_diverged",
                        "writes_nobody_sent"}
    assert not any(got.values()), got
    order = ref.order_of(ops)
    assert order == {0: ([1, 3], [0, 2, 4]), 1: ([], [5])}
    replies, sim_cps = ref.simulate(initial, ops, 2, 3, order)
    assert replies == ops["reply"] and sim_cps == cps
    # a read that overlaps an update may see it or not
    early = list(ops["reply"])
    early[2] = ref.checkpoint_of(2, b"bb\x03\x04")  # sent 2.0, U2 at 3.0
    assert numbers(ref.check(initial, dict(ops, reply=early), 2, cps, 0)) \
        ["reads_from_the_future"] == 1
    t_send = ops["t_send"].copy()
    t_send[3] = 1.8  # the second update now overlaps the read
    assert not any(numbers(ref.check(
        initial, dict(ops, reply=early, t_send=t_send), 2, cps, 0))
        .values())


def test_each_fault_shows_in_its_number():
    initial, ops, cps = by_hand()

    def got(**changed):
        o = dict(ops, **{k: v for k, v in changed.items() if k in ops})
        return numbers(ref.check(initial, o, 2, changed.get("cps", cps),
                                 changed.get("un", 0)))
    stale = list(ops["reply"])
    stale[4] = ref.checkpoint_of(1, b"aa\x03\x04")
    assert got(reply=stale)["reads_older_than_real_time"] == 1
    torn = list(ops["reply"])
    torn[4] = ref.checkpoint_of(2, b"aa\x03\x04")
    assert got(reply=torn)["answers_wrong"] == 1
    dup = list(ops["reply"])
    dup[3] = ref.checkpoint_of(3, b"")
    assert got(reply=dup)["places_taken_twice"] == 1
    swap = list(ops["reply"])
    swap[1], swap[3] = ref.checkpoint_of(2, b""), ref.checkpoint_of(1, b"")
    g = got(reply=swap)
    assert g["order_against_real_time"] == 1
    assert got(status=np.asarray([0, 0, 0, 0, 0, 3], np.int16)
               )["answers_refused"] == 1
    bad = [dict(c) for c in cps]
    bad[1][0] = ref.checkpoint_of(2, b"aa\x03\x04")
    assert got(cps=bad)["replica_groups_diverged"] == 1
    assert got(un=1)["writes_nobody_sent"] == 1


@pytest.mark.parametrize("broken", ref.CONTROLS)
def test_each_control_by_hand_is_not_correct(broken):
    initial, ops, _cps = by_hand()
    order = ref.order_of(ops)
    victim = ref.pick_victim(initial, ops, 2, order, broken,
                             np.random.default_rng(0))
    # a read with an update answered before it; the last update of a
    # field updated twice with other bytes
    assert victim in ((2, 4) if broken == "stale_read" else (3,))
    replies, cps = ref.simulate(initial, ops, 2, 3, order, broken, victim)
    got = numbers(ref.check(initial, dict(ops, reply=replies), 2, cps, 0))
    want = {"lost_update": "replica_groups_diverged",
            "stale_read": "reads_older_than_real_time",
            "doubled": "places_taken_twice",
            "reordered": "replica_groups_diverged"}[broken]
    assert got[want] > 0, got


def test_least_places_is_the_highest_place_answered_before():
    ops = {"t_send": np.asarray([0.0, 0.1, 0.2, 5.0, 1.5]),
           "t_recv": np.asarray([3.0, 1.0, 2.0, 6.0, 2.6])}
    # updates 0, 1, 2 in place order 1, 2, 3; ops 3 and 4 are reads
    assert ref.least_places(ops, [0, 1, 2], [3, 4]).tolist() == [3, 2]
    assert ref.least_places(ops, [], [3]).tolist() == [0]


def test_the_reference_imports_nothing_of_the_program():
    src = open(os.path.join(ROOT, "benchmarks/reference/record_store_ri.py")
               ).read()
    assert "gigapaxos_tpu" not in src.split('"""', 2)[2]


def test_the_read_index_bytes():
    assert roofline_read_index.COORD_LANE == 512 + 4 + 9
    assert roofline_read_index.ACCEPTOR_LANE == 13
    assert roofline_read_index.read_index_bytes(2, 4) == 2 * 525 + 4 * 13


def test_readers_read_nothing_where_the_program_has_no_read_index():
    from benchmarks import harness
    cell = harness.Cell("tiny-ycsb-ri-zipf", root=TINY)
    run = {"before": {"totals": {}}, "after": {"totals": {}},
           "trace": {"module_s": {}}, "window": {"acked": 10},
           "config": cell.config}
    for name in NEW:
        assert cell.reader(name).read(run) is None, name
    run["after"]["totals"] = {
        "ri.reads": {"wall_s": 0.0, "calls": 3, "items": 90},
        "ri.fallbacks": {"wall_s": 0.0, "calls": 1, "items": 10},
        "ri.wait": {"wall_s": 0.9, "calls": 3, "items": 90},
        "ri.exec_wait": {"wall_s": 0.09, "calls": 3, "items": 90}}
    got = {n: cell.reader(n).read(run) for n in NEW}
    assert got["read_index_share"] == 90.0
    assert got["read_wait_ms"] == pytest.approx(10.0)
    assert got["read_exec_wait_ms"] == pytest.approx(1.0)


# -- the driver ----------------------------------------------------------

@pytest.fixture
def ri_cell(monkeypatch):
    from benchmarks import harness
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.utils.config import Config
    Config.set(PC.FUSE_WAVES, "on")  # the handlers the chip run takes
    monkeypatch.setattr(harness, "TRACE_S", 0.5)
    cell = harness.Cell("tiny-ycsb-ri-zipf", root=TINY)
    for const, value in (("RAMP_BURST_S", 0.1), ("WARMUP_BURST_S", 0.3),
                         ("QUIET_BURSTS", 1), ("MAX_BURSTS", 3)):
        monkeypatch.setattr(cell.driver(), const, value)
    return cell


def test_tiny_cell_is_correct_and_reads_take_no_round(ri_cell, measure,
                                                      capfd):
    line = measure(ri_cell, seed=2**31 + 41, seconds=1.0)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 8
    assert set(line["metrics"]) == {"commit_rate", "commit_p50_ms",
                                    "setup_s"}
    assert {"reads_older_than_real_time", "reads_from_the_future",
            "read_index_off"} <= set(line["checks"])
    window = json.loads(next(ln for ln in capfd.readouterr().out.splitlines()
                             if '"phase": "window"' in ln))
    assert window["read_index"] is True
    assert window["reads_by_read_index"] > 0.9 * window["reads"]
    assert window["confirm_frames"] > 0
    assert not window["kernels_traced_in_window"]


@pytest.mark.parametrize("seed", [43, 2**31 + 4301, 2**32 + 4302])
def test_a_traced_run_reports_the_new_metrics_and_its_controls_fail(ri_cell,
                                                                    seed):
    driver = ri_cell.driver()
    run = driver.run(ri_cell, seed=seed, seconds=1.5, trace=True,
                     t_start=time.perf_counter())
    assert all(v <= lim for _n, v, lim in run["checks"]), run["checks"]
    got = {m["name"]: ri_cell.reader(m["name"]).read(run)
           for m in ri_cell.per_layer()}
    # on the CPU no device plane is traced: no roofline has device time
    assert {k for k, v in got.items() if v is None} <= {
        "read_index_kernels_roofline"}, got
    assert got["read_index_share"] > 90
    assert 0 < got["read_exec_wait_ms"] <= got["read_wait_ms"] < 1000
    from benchmarks import span_ring
    kinds = {s["kind"] for s in span_ring.session() or []}
    assert {"ri.serve", "ri.confirm", "eng.read_index",
            "eng.confirm"} <= kinds
    ctl = driver.controls(run, seed)
    assert set(ctl) == set(driver.CONTROLS) == set(ref.CONTROLS)
    for broken, cks in ctl.items():
        assert any(v > lim for _n, v, lim in cks), broken


def test_a_program_without_the_read_index_is_refused_at_once(ri_cell,
                                                             monkeypatch):
    """On the parent's program the driver says what is missing and exits,
    before it boots anything: no run with reads taken as rounds."""
    from gigapaxos_tpu.paxos.interfaces import Replicable
    monkeypatch.delattr(Replicable, "read")
    t0 = time.perf_counter()
    with pytest.raises(SystemExit) as exc:
        ri_cell.driver().run(ri_cell, seed=1, seconds=1.0, trace=False,
                             t_start=t0)
    assert "read()" in str(exc.value.code)
    assert time.perf_counter() - t0 < 1.0


def test_the_new_cell_is_new_files_and_entries_only():
    from benchmarks import harness
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    conf = {c["name"]: c for c in bench["configs"]}["ycsb-b-3r-100k"]
    on_file = harness.load_json(os.path.join(ROOT, conf["file"]))
    a = harness.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "ycsb-a-3r-100k.json"))
    assert on_file["reduced"] == conf["reduced"] == []
    for k in ("replicas", "live_groups", "backend", "capacity_rows",
              "window", "app", "fields", "field_bytes"):
        assert on_file[k] == a[k], k
    for k, v in a["guarantees"].items():
        if not k.startswith("reads_are_consensus_rounds"):
            assert on_file["guarantees"][k] == v, k
    assert on_file["guarantees"]["reads_are_read_index"] is True
    assert on_file["pc"] == {"PAUSE_IDLE_S": 0, "READ_INDEX": True}
    cell = harness.Cell("ycsb-b-zipf")
    assert cell.chips == 1 and cell.traffic["read_share"] == 0.95
    loadgen_ycsb.check_mix(cell.traffic, cell.config["fields"])
    zipf_a = harness.Cell("ycsb-a-zipf").traffic
    for k, v in zipf_a.items():
        if k not in ("name", "read_share", "update_share"):
            assert cell.traffic[k] == v, k
    assert cell.driver().__file__ == os.path.join(
        ROOT, "benchmarks", "drivers", "ycsb_ri.py")
    assert {m["name"] for m in cell.end_to_end()} == {
        "commit_rate", "commit_p50_ms", "setup_s"}
    layer = {m["name"] for m in cell.per_layer()}
    assert NEW <= layer and "compiles_in_window" in layer
    for m in layer:
        assert callable(cell.reader(m).read)
    # the cells that were there read what they read before
    for name in ("ycsb-a-zipf", "served-100k-d256", "storm-1m-b256k"):
        assert not NEW & {m["name"] for m in harness.Cell(name).per_layer()}
