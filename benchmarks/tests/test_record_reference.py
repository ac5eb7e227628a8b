"""The record store's reference, generator and driver (``ycsb-a-3r-100k``)
on the CPU at a tiny size: 64 records, 8 threads, Zipf 0.99, 1 s.  The
system through ``drivers/ycsb.py`` against ``reference/record_store.py``
reads 0 everywhere; the reference with one guarantee taken away does not."""

import json
import os
import time

import numpy as np
import pytest

from benchmarks import loadgen_ycsb
from benchmarks.reference import record_store

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_ycsb")
ZIPF = {"threads": 8, "read_share": 0.5, "update_share": 0.5,
        "distribution": "zipfian", "zipf_constant": 0.99, "scrambled": True,
        "update_fields": 1, "read_fields": 10}
NEW = {"window_full_share", "window_wait_ms", "app_execute_ms",
       "wal_bytes_per_commit"}


@pytest.fixture
def ycsb_cell(monkeypatch):
    from benchmarks import harness
    from gigapaxos_tpu.paxos.paxosconfig import PC
    from gigapaxos_tpu.utils.config import Config
    Config.set(PC.FUSE_WAVES, "on")  # the handlers the chip run takes
    monkeypatch.setattr(harness, "TRACE_S", 0.3)
    cell = harness.Cell("tiny-ycsb-zipf", root=TINY)
    for const, value in (("RAMP_BURST_S", 0.1), ("WARMUP_BURST_S", 0.3),
                         ("QUIET_BURSTS", 1), ("MAX_BURSTS", 3)):
        monkeypatch.setattr(cell.driver(), const, value)
    return cell


# ---- the generator ------------------------------------------------------

def draws(seed, t, mix, n, k):
    plan = loadgen_ycsb.ThreadPlan(seed, t, mix,
                                   loadgen_ycsb.KeyChooser(mix, n), 10, 100)
    return [plan.next() for _ in range(k)]


def test_equal_seeds_draw_equal_operations():
    a = draws(2**31 + 77, 3, ZIPF, 1000, 600)
    assert a == draws(2**31 + 77, 3, ZIPF, 1000, 600)
    assert a != draws(2**31 + 77, 4, ZIPF, 1000, 600)
    assert a != draws(2**31 + 78, 3, ZIPF, 1000, 600)
    # a warm-up burst has operations of its own
    ch = loadgen_ycsb.KeyChooser(ZIPF, 1000)
    burst = loadgen_ycsb.ThreadPlan(2**31 + 77, 3, ZIPF, ch, 10, 100, burst=1)
    assert a[:50] != [burst.next() for _ in range(50)]
    reads = sum(p == b"R" for _r, p in a)
    assert 240 <= reads <= 360  # half of 600
    assert all(p == b"R" or (p[:1] == b"U" and p[1] < 10
                             and len(p) == 102) for _r, p in a)


@pytest.mark.parametrize("n", [64, 100_000])
def test_the_hottest_keys_share_is_the_zipfians(n):
    ch = loadgen_ycsb.KeyChooser(ZIPF, n)
    keys = ch.keys(np.random.default_rng(5), 400_000)
    assert keys.min() >= 0 and keys.max() < n
    share = np.bincount(keys, minlength=n).max() / len(keys)
    want = 1.0 / loadgen_ycsb.zeta(n, 0.99)
    assert abs(share / want - 1.0) < 0.10, (share, want)
    # scrambled: the hottest key is rank 0's hash, not key 0
    hot = int(loadgen_ycsb.fnv64(np.asarray([0]))[0] % np.uint64(n))
    assert int(np.bincount(keys, minlength=n).argmax()) == hot
    plain = loadgen_ycsb.KeyChooser(dict(ZIPF, scrambled=False), n)
    assert np.bincount(plain.keys(np.random.default_rng(5), 10_000)
                       ).argmax() == 0


def test_uniform_keys_and_a_mix_of_other_shapes_is_refused():
    mix = dict(ZIPF, distribution="uniform")
    keys = loadgen_ycsb.KeyChooser(mix, 50).keys(np.random.default_rng(1),
                                                 50_000)
    assert np.bincount(keys, minlength=50).max() / 50_000 < 0.03
    with pytest.raises(ValueError):
        loadgen_ycsb.check_mix(dict(ZIPF, update_fields=10), 10)
    with pytest.raises(ValueError):
        loadgen_ycsb.check_mix(dict(ZIPF, read_share=0.95), 10)
    with pytest.raises(ValueError):
        loadgen_ycsb.KeyChooser(dict(ZIPF, distribution="latest"), 50)


# ---- the reference on operations made by hand -------------------------------

def by_hand():
    """Two records of 2 fields x 2 bytes.  Record 0: update f0, read, update
    f0, read (places 1..4, sent one after the other); record 1: one read."""
    initial = np.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], np.uint8)
    payload = [b"U\x00aa", b"R", b"U\x00bb", b"R", b"R"]
    reply = [record_store.checkpoint_of(1, b""),
             record_store.checkpoint_of(2, b"aa\x03\x04"),
             record_store.checkpoint_of(3, b""),
             record_store.checkpoint_of(4, b"bb\x03\x04"),
             record_store.checkpoint_of(1, b"\x05\x06\x07\x08")]
    ops = {"record": np.asarray([0, 0, 0, 0, 1]),
           "t_send": np.asarray([0.0, 1.0, 2.0, 3.0, 0.5]),
           "t_recv": np.asarray([0.5, 1.5, 2.5, 3.5, 0.9]),
           "status": np.zeros(5, np.int16), "payload": payload,
           "reply": reply}
    cps = [{0: record_store.checkpoint_of(4, b"bb\x03\x04"),
            1: record_store.checkpoint_of(1, b"\x05\x06\x07\x08")}
           for _ in range(3)]
    return initial, ops, cps


def numbers(checks):
    return {n: v for n, v, _lim in checks}


def test_sound_operations_read_zero_everywhere():
    initial, ops, cps = by_hand()
    got = numbers(record_store.check(initial, ops, 2, cps, 0))
    assert set(got) == {"answers_wrong", "places_taken_twice",
                        "order_against_real_time", "answers_refused",
                        "never_answered", "replica_groups_diverged",
                        "writes_nobody_sent"}
    assert not any(got.values()), got
    # the machine itself, fed the order the replies state, gives them back
    order = record_store.order_of(ops)
    assert order == {0: [0, 1, 2, 3], 1: [4]}
    replies, sim_cps = record_store.simulate(initial, ops, 2, 3, order)
    assert replies == ops["reply"] and sim_cps == cps


def test_each_fault_shows_in_its_number():
    initial, ops, cps = by_hand()

    def got(**changed):
        o = dict(ops, **{k: v for k, v in changed.items() if k in ops})
        return numbers(record_store.check(
            initial, o, 2, changed.get("cps", cps), changed.get("un", 0)))
    # a read that returns the record before the update below it
    stale = list(ops["reply"])
    stale[3] = record_store.checkpoint_of(4, b"aa\x03\x04")
    assert got(reply=stale)["answers_wrong"] == 1
    # two operations of a record with one place
    dup = list(ops["reply"])
    dup[2] = record_store.checkpoint_of(2, b"")
    assert got(reply=dup)["places_taken_twice"] == 1
    # a place beyond the operations sent
    far = list(ops["reply"])
    far[4] = record_store.checkpoint_of(2, b"\x05\x06\x07\x08")
    g = got(reply=far)
    assert g["places_taken_twice"] == 1 and g["answers_wrong"] == 1
    # places 1 and 2 swapped: the read was answered from the seed record
    # with place 1, and the update took place 2 though it was answered
    # before the read was sent
    swap = list(ops["reply"])
    swap[0] = record_store.checkpoint_of(2, b"")
    swap[1] = record_store.checkpoint_of(1, b"\x01\x02\x03\x04")
    g = got(reply=swap)
    assert g["order_against_real_time"] == 1 and g["answers_wrong"] == 0
    # refused, never answered
    assert got(status=np.asarray([0, 0, 0, 0, 3], np.int16)
               )["answers_refused"] == 1
    g = got(t_recv=np.asarray([0.5, 1.5, 2.5, -1.0, 0.9]),
            cps=[{**c, 0: record_store.checkpoint_of(3, b"bb\x03\x04")}
                 for c in cps])
    assert g["never_answered"] == 1 and g["replica_groups_diverged"] == 0
    # one replica's record differs; a record nobody addressed changed
    bad = [dict(c) for c in cps]
    bad[2][0] = record_store.checkpoint_of(4, b"aa\x03\x04")
    assert got(cps=bad)["replica_groups_diverged"] == 1
    assert got(un=2)["writes_nobody_sent"] == 2


@pytest.mark.parametrize("broken", record_store.CONTROLS)
def test_each_control_by_hand_is_not_correct(broken):
    initial, ops, _cps = by_hand()
    order = record_store.order_of(ops)
    victim = record_store.pick_victim(initial, ops, 2, order, broken,
                                      np.random.default_rng(0))
    assert victim == (3 if broken == "stale_read" else 2)
    replies, cps = record_store.simulate(initial, ops, 2, 3, order, broken,
                                         victim)
    got = numbers(record_store.check(initial, dict(ops, reply=replies), 2,
                                     cps, 0))
    want = {"lost_update": "replica_groups_diverged",
            "stale_read": "answers_wrong", "doubled": "places_taken_twice",
            "reordered": "replica_groups_diverged"}[broken]
    assert got[want] > 0, got


def test_initial_records_come_from_the_seed():
    a = record_store.initial_records(2**31 + 5, 64, 10, 100)
    assert a.shape == (64, 1000) and a.dtype == np.uint8
    assert (a == record_store.initial_records(2**31 + 5, 64, 10, 100)).all()
    assert (a != record_store.initial_records(2**31 + 6, 64, 10, 100)).any()


def test_the_reference_and_the_generator_import_nothing_of_the_program():
    for rel in ("benchmarks/reference/record_store.py",
                "benchmarks/loadgen_ycsb.py"):
        src = open(os.path.join(ROOT, rel)).read()
        assert "gigapaxos_tpu" not in src.split('"""', 2)[2], rel


# ---- the system through the driver ------------------------------------------

def test_tiny_cell_is_correct_and_reports_the_new_metrics(ycsb_cell, measure):
    line = measure(ycsb_cell, seed=2**31 + 11, seconds=1.0)
    assert line["correct"] is True, line["checks"]
    assert line["failed"] == 0 and line["attempted"] > 8
    assert set(line["metrics"]) == {"commit_rate", "commit_p50_ms",
                                    "setup_s"}
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in line["checks"].values())
    assert {"places_taken_twice", "order_against_real_time",
            "engines_off_the_device", "sync_wal_off",
            "groups_paged_out"} <= set(line["checks"])


def test_a_traced_tiny_cell_reports_every_per_layer_metric(ycsb_cell,
                                                           measure):
    line = measure(ycsb_cell, seed=12, seconds=1.0, trace=True)
    assert line["correct"] is True, line["checks"]
    want = {m["name"] for m in ycsb_cell.per_layer()}
    assert NEW <= want and len(want) == 17
    # on the CPU no device plane is traced: the roofline has no busy time
    assert want - set(line["metrics"]) <= {"paxos_kernels_roofline"}
    got = {k: line["metrics"][k]["value"] for k in NEW}
    assert all(v >= 0 for v in got.values()), got
    assert got["app_execute_ms"] > 0
    # three replicas append every operation's accept: a read is a round
    # too (1 B of payload), an update carries its 100 B field
    assert 3 * 30 < got["wal_bytes_per_commit"] < 3 * 250
    assert got["window_full_share"] <= 100


def test_a_broken_app_comes_out_not_correct(ycsb_cell, measure, monkeypatch):
    from gigapaxos_tpu.paxos.interfaces import RecordApp
    sound = RecordApp.execute
    seen = {"n": 0}

    def forgets(self, name, req_id, payload, is_stop=False):
        # every 40th update is acknowledged and not applied
        if payload[:1] == b"U":
            seen["n"] += 1
            if seen["n"] % 40 == 0:
                payload = b"R"
                return sound(self, name, req_id, payload, is_stop)[:8]
        return sound(self, name, req_id, payload, is_stop)

    monkeypatch.setattr(RecordApp, "execute", forgets)
    line = measure(ycsb_cell, seed=13, seconds=1.0)
    assert line["correct"] is False
    bad = {k for k, c in line["checks"].items() if c["value"] > c["limit"]}
    assert bad & {"answers_wrong", "replica_groups_diverged"}


def test_controls_at_the_runs_own_size_are_not_correct(ycsb_cell):
    """What ``chip_control.py`` does on the chip, at a size a test holds."""
    driver = ycsb_cell.driver()
    run = driver.run(ycsb_cell, seed=21, seconds=1.0, trace=False,
                     t_start=time.perf_counter())
    assert all(v <= lim for _n, v, lim in run["checks"]), run["checks"]
    ctl = driver.controls(run, 21)
    assert set(ctl) == set(driver.CONTROLS) == set(record_store.CONTROLS)
    for broken, checks in ctl.items():
        assert any(v > lim for _n, v, lim in checks), broken


def test_the_new_cells_are_new_files_and_entries_only():
    """``test_harness_data.py``'s rule for what this configuration adds:
    the harness finds its driver, mixes and readers by the names in
    ``BENCHMARK.json``, beside the files that were there."""
    from benchmarks import harness
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    conf = {c["name"]: c for c in bench["configs"]}["ycsb-a-3r-100k"]
    on_file = harness.load_json(os.path.join(ROOT, conf["file"]))
    assert on_file["reduced"] == conf["reduced"] == []
    assert on_file["source"] == conf["source"]
    assert on_file["guarantees"]["reads_are_consensus_rounds"] is True
    served = harness.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "testpaxos-3r-100k.json"))
    for k, v in served["guarantees"].items():
        assert on_file["guarantees"][k] == v, k
    for name, dist in (("ycsb-a-zipf", "zipfian"),
                       ("ycsb-a-uniform", "uniform")):
        cell = harness.Cell(name)
        assert cell.chips == 1 and cell.traffic["distribution"] == dist
        assert cell.traffic["threads"] == 256
        loadgen_ycsb.check_mix(cell.traffic, cell.config["fields"])
        assert cell.driver().__file__ == os.path.join(
            ROOT, "benchmarks", "drivers", "ycsb.py")
        assert {m["name"] for m in cell.end_to_end()} == {
            "commit_rate", "commit_p50_ms", "setup_s"}
        layer = {m["name"] for m in cell.per_layer()}
        assert NEW <= layer and len(layer) == 17
        for m in layer:
            assert callable(cell.reader(m).read)
    # the cells that were there read what they read before
    old = harness.Cell("served-100k-d256")
    assert not NEW & {m["name"] for m in old.per_layer()}
    assert json.dumps(bench["workloads"][:3]) == json.dumps(
        [w for w in bench["workloads"]
         if w["name"] in ("served-100k-d256", "storm-1m-b256k",
                          "served-100k-d32")])
