"""``BENCHMARK.json`` against the limits of the benchmark's contract that a
file can be held to without a run."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line_ok(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_keeps_to_the_contract():
    path = os.path.join(ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    b = json.load(open(path))
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(b["paths"]) <= 16 and all(PATH.match(p)
                                              for p in b["paths"])
    assert len(b["command"]) <= 32 and all(line_ok(w) for w in b["command"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51

    configs = {}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in configs
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        on_file = json.load(open(os.path.join(ROOT, c["file"])))
        assert on_file["reduced"] == c["reduced"]
        assert on_file["source"] == c["source"]
        configs[c["name"]] = c
    assert len({c["file"] for c in b["configs"]}) == len(configs) <= 24

    cells, pairs = {}, set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line_ok(w["why"]) and w["name"] not in cells
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        cells[w["name"]] = w
    assert 1 <= len(cells) <= 24
    assert {w["config"] for w in b["workloads"]} == set(configs)
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(cells) // 2)

    names, e2e = set(), {}
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and m["name"] not in names
        assert set(m.get("workloads", cells)) <= set(cells)
        names.add(m["name"])
        e2e[m["name"]] = set(m.get("workloads", cells))
    assert e2e["setup_s"] == set(cells) and 1 <= len(e2e) <= 16
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert line_ok(m["layer"]) and m["name"] not in names
        names.add(m["name"])
        # every cell that reads it reports the metric it should move
        assert set(m.get("workloads", e2e[m["moves"]])) <= e2e[m["moves"]]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    assert 1 <= len(b["per_layer"]) <= 128
    for cell in cells:  # setup_s, one more end to end, one per layer
        assert sum(cell in ws for ws in e2e.values()) >= 2
        assert any(cell in m.get("workloads", e2e[m["moves"]])
                   for m in b["per_layer"])

    # files under paths are named from the characters of a name and "/"
    for p in b["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert PATH.match(rel), rel


def test_check_fits_the_time_allowed():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = 24  # later PRs add cells and may not change run_seconds
    runs = 2 + 14 * cells
    assert runs * (b["run_seconds"] + 60) + cells * 2 * 90 + 1200 <= 43200


def test_perf_md_states_the_bounds_that_are_committed():
    """One set of bounds: the table of ``PERF.md`` section 2 (a row per
    end-to-end metric, the bound in a column of its own) and ``run_seconds``
    there are those of ``BENCHMARK.json``."""
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    perf = open(os.path.join(ROOT, "PERF.md")).read()
    section = perf.split("\n## 2.")[1].split("\n## 3.")[0]
    head = next(ln for ln in section.splitlines()
                if ln.startswith("| metric"))
    col = [c.strip() for c in head.strip("|").split("|")].index("bound")
    stated = {}
    for ln in section.splitlines():
        cells = [c.strip() for c in ln.strip("|").split("|")]
        m = re.match(r"^`([\w.\-]+)`$", cells[0])
        if ln.startswith("|") and m:
            stated[m.group(1)] = float(cells[col])
    assert stated == {m["name"]: m["bound"] for m in b["end_to_end"]}
    said = re.search(r"`run_seconds` is (\d+)", section)
    assert said and int(said.group(1)) == b["run_seconds"]
