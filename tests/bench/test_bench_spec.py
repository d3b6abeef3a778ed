"""BENCHMARK.json keeps to the benchmark's contract: its keys, names,
limits, and that every cell reports what it must."""
import json
import os
import re

import smoke_root

SPEC_PATH = os.path.join(smoke_root.REPO, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


def _spec():
    with open(SPEC_PATH) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_and_command():
    spec = _spec()
    assert os.path.getsize(SPEC_PATH) <= 64 * 1024
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(spec["paths"]) <= 16
    for p in spec["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(smoke_root.REPO, p))
    cmd = spec["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(c) for c in cmd)
    files = [c for c in cmd if os.path.exists(os.path.join(
        smoke_root.REPO, c))]
    assert all(any(f.startswith(p + "/") for p in spec["paths"])
               for f in files)
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 s
    assert 2 + 14 * 24 * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_configs():
    spec = _spec()
    names = [c["name"] for c in spec["configs"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    files = set()
    used = {w["config"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"])
        assert _line(c["why"]) and c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(smoke_root.REPO, c["file"])) as f:
            conf = json.load(f)
        assert len(c["reduced"]) <= 16
        assert conf["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank", "_size"))
            assert conf["published"][key] != conf[key]


def test_workloads():
    spec = _spec()
    names = [w["name"] for w in spec["workloads"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    assert sum(w["chips"] == 4 for w in spec["workloads"]) <= \
        max(1, len(names) // 2)


def test_metrics():
    spec = _spec()
    cells = {w["name"] for w in spec["workloads"]}
    seen = set()
    e2e_of = {c: set() for c in cells}
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        for c in m.get("workloads", cells):
            assert c in cells
            e2e_of[c].add(m["name"])
    setup = [m for m in spec["end_to_end"] if m["name"] == "setup_s"]
    assert setup and "workloads" not in setup[0]
    per_layer_of = {c: set() for c in cells}
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])
        for c in m["workloads"]:
            assert m["moves"] in e2e_of[c], (m["name"], c)
            per_layer_of[c].add(m["name"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%" and m["source"] == "device_trace"
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
    for c in cells:
        assert "setup_s" in e2e_of[c] and len(e2e_of[c]) >= 2
        assert per_layer_of[c]
    # beside every kernel roofline that moves a metric in a cell, the whole
    # step's share of peak moves the same metric there
    for m in spec["per_layer"]:
        if m["name"].split(".")[0].endswith("_roofline"):
            for c in m["workloads"]:
                assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                           and c in o["workloads"]
                           for o in spec["per_layer"]), (m["name"], c)
