"""Each driver end to end at CPU size, Pallas kernels in interpret mode,
called as a function (the command itself refuses a CPU)."""
import smoke_root

from bench import harness


def _run(tmp_path, name, trace=False):
    root = smoke_root.make(str(tmp_path))
    run = smoke_root.new_run(root, name, trace=trace)
    harness.execute(run)
    return run, harness.result(run)


def test_serve_open_loop(tmp_path):
    run, res = _run(tmp_path, "tiny.chat")
    assert res["correct"], res["checks"]
    assert res["attempted"] == 16 and res["failed"] == 0
    m = res["metrics"]
    assert set(m) == {"ttft_p95_ms", "output_tokens_per_s", "setup_s"}
    assert m["ttft_p95_ms"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert run.info["compiles_in_window"] == 0, run.info
    assert run.info["check"]["tokens_compared"] >= 16
    assert list(res)[-1] == "checks"
    # every request due in the window finished
    assert run.stats["decode_tokens"] > 0
    # the aggregation each admission needed: its distinct missed profiles,
    # which the engine pads to a power of two
    from repro.utils import pow2_count
    agg = [a for _, a in run.extra["admissions"] if a["path"] == "sparse"]
    assert agg
    assert all(pow2_count(a["missed_profiles"]) == a["aggregated_profiles"]
               for a in agg if a["missed_profiles"]), agg


def test_serve_backlog_traced(tmp_path):
    run, res = _run(tmp_path, "tiny.backlog", trace=True)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"profile_cache.hit_rate"}
    assert res["metrics"]["profile_cache.hit_rate"]["value"] == 100.0
    assert run.info["compiles_in_window"] == 0, run.info
    assert run.info["backlog_empty_s"] is None       # the queue never ran dry
    dev = res["device"]
    assert dev["window_s"] > 0 and "busy_s" in dev
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
