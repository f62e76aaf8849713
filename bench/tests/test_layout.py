"""Every cell in BENCHMARK.json resolves to its files, and the file keeps to
the shape the harness reads: a later cell or metric needs only files and
entries, no edit of the harness."""
import json
import re

import pytest

import harness

B = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("w", B["workloads"], ids=lambda w: w["name"])
def test_workload_resolves_to_its_files(w):
    cell = harness.resolve(w["name"], B)
    assert harness.driver_class(cell).__name__ == "Driver"
    for m in cell.per_layer:
        assert callable(harness.metric_reader(m["name"]))
    assert cell.end_to_end and cell.per_layer
    assert "setup_s" in [m["name"] for m in cell.end_to_end]
    assert any(v is not None for v in cell.limits.values())
    assert all(v is None or v > 0 for v in cell.limits.values())


@pytest.mark.parametrize("c", B["configs"], ids=lambda c: c["name"])
def test_config_file_matches_entry_and_program(c):
    from arch import program_config

    f = harness.load_json(harness.ROOT / c["file"])
    assert f["reduced"] == c["reduced"]
    assert set(f["published"]) == set(c["reduced"])
    assert all(f[k] != f["published"][k] for k in c["reduced"])
    assert c["source"] in f["source"]
    program_config(f)  # exits on a size that differs from the program's


def test_names_units_and_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in B[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(x["name"] for x in B["end_to_end"] + B["per_layer"])) \
        == len(B["end_to_end"]) + len(B["per_layer"])
    e2e = {m["name"] for m in B["end_to_end"]}
    cells = {w["name"] for w in B["workloads"]}
    for m in B["end_to_end"] + B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0 < m["bound"] <= 0.25
    for m in B["per_layer"]:
        assert m["moves"] in e2e
        movers = {w for w in cells if harness.applies(
            next(e for e in B["end_to_end"] if e["name"] == m["moves"]), w)}
        assert set(m["workloads"]) <= movers
    assert sum(w["chips"] == 4 for w in B["workloads"]) <= max(1, len(B["workloads"]) // 2)
    assert len(json.dumps(B)) < 64 * 1024
