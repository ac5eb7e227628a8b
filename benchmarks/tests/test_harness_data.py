"""A configuration, a traffic mix, a per-layer metric and a cell are each
added by new files plus new entries: no file that is there is edited, and
the harness finds the new ones by the names in ``BENCHMARK.json``."""

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_a_later_pr_adds_files_and_entries_only(tmp_path, measure):
    from benchmarks import harness

    # the later PR's checkout: the benchmark's data as it is ...
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    home = tmp_path / "benchmarks"
    for sub in ("configs", "traffic"):
        shutil.copytree(os.path.join(ROOT, "benchmarks", sub), home / sub)
    # ... plus four new files ...
    (home / "configs" / "storm-3r-64.json").write_text(json.dumps({
        "name": "storm-3r-64", "driver": "storm", "replicas": 3,
        "groups": 64, "window": 8, "oracle_sample_groups": 32,
        "reduced": ["groups"]}))
    (home / "traffic" / "storm-b128.json").write_text(json.dumps({
        "name": "storm-b128", "kind": "storm", "lanes_per_step": 128}))
    (home / "layer_metrics").mkdir()
    (home / "layer_metrics" / "storm_steps.py").write_text(
        '"""Synced steps of the window."""\n\n\n'
        'def read(run):\n    return run["window"].get("steps") or None\n')
    # ... and four new entries
    bench["configs"].append({
        "name": "storm-3r-64", "source": "a later PR",
        "file": "benchmarks/configs/storm-3r-64.json",
        "reduced": ["groups"], "why": "a later PR"})
    bench["workloads"].append({
        "name": "storm-64-b128", "config": "storm-3r-64",
        "traffic": "storm-b128", "chips": 1, "why": "a later PR"})
    bench["per_layer"].append({
        "name": "storm_steps", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "storm_rate", "workloads": ["storm-64-b128"]})
    for m in bench["end_to_end"]:
        if m["name"] == "storm_rate":  # an entry may list a new cell
            m["workloads"] = m["workloads"] + ["storm-64-b128"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.Cell("storm-64-b128", root=str(tmp_path))
    assert cell.config["groups"] == 64
    assert cell.traffic["lanes_per_step"] == 128
    assert {m["name"] for m in cell.end_to_end()} == {"storm_rate",
                                                      "setup_s"}
    assert [m["name"] for m in cell.per_layer()] == ["storm_steps"]
    # the new reader is found beside the new files, the driver and the old
    # readers where they always were
    assert cell.reader("storm_steps").__file__.startswith(str(tmp_path))
    assert cell.driver().__file__ == os.path.join(
        ROOT, "benchmarks", "drivers", "storm.py")
    assert cell.reader("storm_step_ms").__file__.startswith(ROOT)

    line = measure(cell, seed=9, seconds=0.2)
    assert line["correct"] is True and set(line["metrics"]) == {
        "storm_rate", "setup_s"}
    # the old cells are untouched by the new entries
    old = harness.Cell("storm-1m-b256k", root=str(tmp_path))
    assert [m["name"] for m in old.per_layer()] == [
        "storm_step_roofline", "storm_step_ms"]


def test_every_cell_of_the_benchmark_resolves():
    from benchmarks import harness
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = harness.Cell(w["name"])
        assert cell.driver().run
        e2e = {m["name"] for m in cell.end_to_end()}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = cell.per_layer()
        assert layer and all(m["moves"] in e2e for m in layer)
        for m in layer:
            assert callable(cell.reader(m["name"]).read)
