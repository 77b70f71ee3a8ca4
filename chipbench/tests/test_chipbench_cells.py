"""BENCHMARK.json keeps its contract, and every cell finds its files."""
import copy
import json
import re

import pytest

from chipbench import harness
from chipbench.tests import tiny

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


def _check_cell(cell):
    """What a cell needs from its files.  Whether the program keeps the keys
    apart is for the run's check to say (``wrong_answers``,
    ``readback_wrong``), not for this."""
    records = cell.config["records"]
    assert records > 0 and cell.config["pool"]
    for key in ("source", "guarantee", "assumed", "device_bytes"):
        assert cell.config[key], key
    assert isinstance(cell.config["reduced"], dict)
    keys = harness.make_keys(cell.config)     # a format it knows
    if keys is not None:                      # one string key a record
        assert len(keys) == records + 1 and keys[0] is None
        assert len(set(keys[1:])) == records
        assert all(isinstance(k, str) for k in keys[1:])
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


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves(name):
    _check_cell(harness.resolve(name))


def _check_device_bytes(cfg):
    """The four arrays every pool has, each at its size; a layout may name
    further device arrays, and the total is the sum of all it names."""
    p, b = cfg["pool"], cfg["device_bytes"]
    assert b["index"] == p["replicas"] * p["n_buckets"] * \
        p["slots_per_bucket"] * 4
    assert b["log"] == p["n_pages"] * 16
    assert b["chain"] == p["n_pages"] * 8
    assert b["free_bitmap"] == p["n_pages"]
    assert all(isinstance(v, int) and v > 0 for v in b.values())
    assert b["total"] == sum(v for k, v in b.items() if k != "total")


@pytest.mark.parametrize("path", sorted(
    (harness.HERE / "configs").glob("*.json")), ids=lambda p: p.stem)
def test_config_file_states_its_device_bytes(path):
    cfg = json.loads(path.read_text())
    for key in ("source", "guarantee", "assumed", "values_held",
                "key_bytes"):
        assert cfg[key], key
    assert isinstance(cfg["reduced"], dict)
    _check_device_bytes(cfg)


def _tiny_ycsb_config(**device_bytes):
    """``tiny.CONFIG`` with YCSB's string keys, stated as a configuration
    file states itself, with one more device array than the four."""
    p = tiny.CONFIG["pool"]
    arrays = {"index": p["replicas"] * p["n_buckets"] *
              p["slots_per_bucket"] * 4, "log": p["n_pages"] * 16,
              "chain": p["n_pages"] * 8, "free_bitmap": p["n_pages"],
              "page_keys": p["n_pages"] * 24}
    arrays["total"] = sum(arrays.values())
    arrays.update(device_bytes)
    return dict(tiny.CONFIG, name="tiny-ycsb", key_format="ycsb",
                source="a test's stand-in", guarantee="as the cells'",
                assumed={"pool": "tiny.py's"}, values_held="host",
                key_bytes="YCSB's own", device_bytes=arrays, reduced={})


def test_string_key_workload_resolves(tmp_path):
    """A cell of YCSB's string keys over a fifth device array takes a new
    configuration file and new entries, and no edit to a file here."""
    path = tmp_path / "tiny-ycsb.json"
    path.write_text(json.dumps(_tiny_ycsb_config()))
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({"name": "tiny-ycsb", "source": "a test",
                             "file": str(path), "reduced": [], "why": "-"})
    name = "tiny-ycsb.a"
    bench["workloads"].append({"name": name, "config": "tiny-ycsb",
                               "traffic": "ycsb-a", "chips": 1, "why": "-"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append(name)
    cell = harness.resolve(name, bench)
    assert cell.config["key_format"] == "ycsb"
    _check_cell(cell)
    _check_device_bytes(cell.config)


def test_device_bytes_with_a_fifth_array_and_a_wrong_total():
    _check_device_bytes(_tiny_ycsb_config())
    cfg = _tiny_ycsb_config()
    with pytest.raises(AssertionError):        # the four alone
        _check_device_bytes(_tiny_ycsb_config(
            total=cfg["device_bytes"]["total"] -
            cfg["device_bytes"]["page_keys"]))
    with pytest.raises(AssertionError):
        _check_device_bytes(_tiny_ycsb_config(
            total=cfg["device_bytes"]["total"] + 1))


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
