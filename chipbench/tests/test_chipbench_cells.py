"""BENCHMARK.json keeps its contract, and every cell finds its files."""
import json
import re

import pytest

from chipbench import harness

BENCH = harness.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_names_units_and_entry_keys():
    names = []
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names.append(c["name"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves(name):
    cell = harness.resolve(name)
    assert cell.config["records"] > 0 and cell.config["pool"]
    for key in ("source", "guarantee", "assumed", "device_bytes"):
        assert cell.config[key], key
    assert isinstance(cell.config["reduced"], dict)
    assert harness.make_keys(cell.config) is None   # keys that never alias
    gen = harness.load_file(harness.HERE / "generators" /
                            f"{cell.mix['generator']}.py")
    assert sum(gen.op_counts(cell.mix["ops"], cell.mix["clients"]).values()) \
        == cell.mix["clients"]
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in e2e
        reader = harness.load_file(harness.HERE / "layer_metrics" /
                                   f"{m['name']}.py")
        assert callable(reader.read)
        if m["name"].endswith("_roofline"):
            kernel = m["name"][:-len("_roofline")]
            assert callable(harness.load_file(
                harness.HERE / "costs" / f"{kernel}.py").bytes_needed)


@pytest.mark.parametrize("path", sorted(
    (harness.HERE / "configs").glob("*.json")), ids=lambda p: p.stem)
def test_config_file_states_its_device_bytes(path):
    cfg = json.loads(path.read_text())
    for key in ("source", "guarantee", "assumed", "values_held",
                "key_bytes"):
        assert cfg[key], key
    assert isinstance(cfg["reduced"], dict)
    p, b = cfg["pool"], cfg["device_bytes"]
    assert b["index"] == p["replicas"] * p["n_buckets"] * \
        p["slots_per_bucket"] * 4
    assert b["log"] == p["n_pages"] * 16
    assert b["chain"] == p["n_pages"] * 8
    assert b["free_bitmap"] == p["n_pages"]
    assert b["total"] == b["index"] + b["log"] + b["chain"] + \
        b["free_bitmap"]


def test_reduced_keys_match_the_config_files():
    for c in BENCH["configs"]:
        cfg = json.loads((harness.REPO / c["file"]).read_text())
        assert set(c["reduced"]) == set(cfg["reduced"])


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        harness.resolve("no-such-cell")


def test_ycsb_keys_are_ycsbs_own():
    """YCSB's CoreWorkload names record 0 user6284781860667377211."""
    assert [harness.ycsb_key(r) for r in (1, 2, 3)] == [
        "user6284781860667377211", "user8517097267634966620",
        "user1820151046732198393"]
    keys = harness.make_keys({"records": 1000, "key_format": "ycsb"})
    assert keys[0] is None and len(set(keys[1:])) == 1000
    with pytest.raises(ValueError):
        harness.make_keys({"records": 10, "key_format": "uuid"})


def test_every_configuration_file_is_a_cell_or_says_why_not():
    used = {c["file"] for c in BENCH["configs"]}
    for path in sorted((harness.HERE / "configs").glob("*.json")):
        cfg = json.loads(path.read_text())
        rel = str(path.relative_to(harness.REPO))
        assert rel in used or "not_a_cell" in cfg or "stands_in_for" in cfg
