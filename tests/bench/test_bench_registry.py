"""A traffic mix, a cell and a per-layer metric are added by new files and
new entries alone: the harness finds them by name."""
import json
import os
import shutil

import smoke_root

from bench import harness
from bench.registry import Bench


def test_new_traffic_and_metric_found_by_name(tmp_path):
    root = smoke_root.make(str(tmp_path))
    # the metric readers become a real directory holding a new reader
    metrics = os.path.join(root, "bench", "metrics")
    os.unlink(metrics)
    shutil.copytree(os.path.join(smoke_root.REPO, "bench", "metrics"),
                    metrics)
    with open(os.path.join(metrics, "admitted_requests.py"), "w") as f:
        f.write("def read(run):\n"
                "    return run.stats['scheduler']['admitted']\n")
    with open(os.path.join(metrics, "nothing_to_read.py"), "w") as f:
        f.write("def read(run):\n    return None\n")
    mix = dict(smoke_root.CHAT, arrivals={"process": "poisson",
                                          "rate_per_s": 4.0})
    with open(os.path.join(root, "bench", "traffic", "slow.json"), "w") as f:
        json.dump(mix, f)
    shutil.copy(os.path.join(root, "bench", "cells", "tiny.chat.json"),
                os.path.join(root, "bench", "cells", "tiny.slow.json"))
    spec_path = os.path.join(root, "BENCHMARK.json")
    with open(spec_path) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny.slow", "config": "tiny",
                              "traffic": "slow", "chips": 1, "why": "test"})
    for name in ("admitted_requests", "nothing_to_read"):
        spec["per_layer"].append({
            "name": name, "unit": "requests", "better": "higher",
            "source": "program_counter", "layer": "scheduler",
            "moves": "ttft_p95_ms", "workloads": ["tiny.slow"]})
    with open(spec_path, "w") as f:
        json.dump(spec, f)

    bench = Bench(root)
    assert bench.traffic("slow")["arrivals"]["rate_per_s"] == 4.0
    run = smoke_root.new_run(root, "tiny.slow", trace=True)
    harness.execute(run)
    res = harness.result(run)
    assert res["correct"], res["checks"]
    # a reader that finds nothing is left out of the line
    assert set(res["metrics"]) == {"admitted_requests"}
    assert res["metrics"]["admitted_requests"]["value"] == 8
    assert res["metrics"]["admitted_requests"]["unit"] == "requests"


def test_metrics_follow_their_workloads_key(tmp_path):
    root = smoke_root.make(str(tmp_path))
    bench = Bench(root)
    assert [m["name"] for m in bench.metrics_for("tiny.chat",
                                                 "per_layer")] == \
        ["profile_cache.hit_rate"]
    assert {m["name"] for m in bench.metrics_for("tiny.chat",
                                                 "end_to_end")} == \
        {"ttft_p95_ms", "output_tokens_per_s", "setup_s"}


def test_repository_benchmark_names_resolve():
    """Every cell of the repository's BENCHMARK.json finds its files, and
    every per-layer metric its reader."""
    bench = Bench(smoke_root.REPO)
    for w in bench.spec["workloads"]:
        assert bench.config(w["config"])["hidden_size"] > 0
        mix = bench.traffic(w["traffic"])
        assert bench.driver(mix["driver"]).run
        assert bench.cell(w["name"])["limits"]
    for m in bench.spec["per_layer"]:
        assert bench.reader(m["name"]).read
        for w in m["workloads"]:
            e2e = {e["name"] for e in bench.metrics_for(w, "end_to_end")}
            assert m["moves"] in e2e
