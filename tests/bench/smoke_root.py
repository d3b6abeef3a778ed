"""A benchmark root at CPU size for the tests: a ``BENCHMARK.json`` and
``bench/`` whose configuration, traffic and cell files are tiny, and whose
drivers and metric readers are the real ones (linked)."""
from __future__ import annotations

import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = {
    "name": "tiny", "source": "test", "model_type": "qwen2",
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "vocab_size": 512,
    "max_position_embeddings": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-06, "hidden_act": "silu",
    "tie_word_embeddings": True, "torch_dtype": "float32", "qkv_bias": True,
    "xpeft": {"num_adapters": 8, "k": 2, "bottleneck": 4,
              "mask_type": "hard", "adapter_activation": "gelu",
              "max_profiles": 16, "gumbel_tau": 1.0, "gumbel_nu": 1.0},
    "reduced": [],
}
CHAT = {
    "driver": "serve_open_loop",
    "arrivals": {"process": "poisson", "rate_per_s": 8.0},
    "prompt_tokens": {"dist": "lognormal", "median": 12, "sigma": 0.6,
                      "min": 4, "max": 40},
    "output_tokens": {"dist": "uniform", "min": 2, "max": 8},
    "profiles": {"count": 16, "popularity": "zipf", "s": 1.0},
    "block": 8,
}
BACKLOG = {
    "driver": "serve_backlog",
    "arrivals": {"process": "backlog", "max_rate_per_s": 400.0},
    "prompt_tokens": {"dist": "uniform", "min": 20, "max": 40},
    "output_tokens": {"dist": "uniform", "min": 4, "max": 12},
    "profiles": {"count": 4, "popularity": "uniform"},
    "block": 8,
}
ONBOARD = {
    "driver": "onboard",
    "seq_len": 8,
    "profiles": {"count": 4096},
}
ONBOARD_CELL = {
    "roster": {"slots": 2, "per_slot": 2, "graduate_at": 6, "sync_every": 3,
               "lr": 1e-3, "clip": 1.0},
    # float32 on the CPU: the program's steps are the reference's to
    # rounding
    "limits": {"loss_gap": 1e-4, "grad1_gap": 1e-3, "change3_gap": 1e-3},
}
CELL = {
    "engine": {"slots": 4, "max_seq": 64, "page_size": 16, "sync_every": 4,
               "cache_bytes": 6 * 4200},
    "check": {"tokens": 64, "max_requests": 8},
    # float32 on the CPU: the program's greedy tokens are the reference's
    # own (gap 0); the float8 control reads 0.03-0.3
    "limits": {"logit_gap_max": 0.01},
}


def make(root: str, cells=None, per_layer=None) -> str:
    """Write the tiny benchmark under ``root``; returns ``root``."""
    cells = cells or {"tiny.chat": ("chat", CHAT), "tiny.backlog":
                      ("backlog", BACKLOG)}
    b = os.path.join(root, "bench")
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(b, sub), exist_ok=True)
    for sub in ("drivers", "metrics"):
        dst = os.path.join(b, sub)
        if not os.path.exists(dst):
            os.symlink(os.path.join(REPO, "bench", sub), dst)
    with open(os.path.join(b, "configs", "tiny.json"), "w") as f:
        json.dump(TINY, f)
    workloads = []
    for name, (traffic, mix, *cell) in cells.items():
        with open(os.path.join(b, "traffic", traffic + ".json"), "w") as f:
            json.dump(mix, f)
        with open(os.path.join(b, "cells", name + ".json"), "w") as f:
            json.dump(cell[0] if cell else CELL, f)
        workloads.append({"name": name, "config": "tiny", "traffic": traffic,
                          "chips": 1, "why": "test"})
    names = list(cells)
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": workloads,
        "end_to_end": [
            {"name": "ttft_p95_ms", "unit": "ms", "better": "lower",
             "bound": 0.1, "source": "host_clock"},
            {"name": "output_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.1, "source": "host_clock"},
            {"name": "onboard_tokens_per_s", "unit": "tokens/s",
             "better": "higher", "bound": 0.1, "source": "host_clock",
             "workloads": ["tiny.onboard"]},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": per_layer or [
            {"name": "profile_cache.hit_rate", "unit": "%",
             "better": "higher", "source": "program_counter",
             "layer": "admission and hydration", "moves": "ttft_p95_ms",
             "workloads": names}],
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def new_run(root: str, name: str, seed: int = 7, seconds: float = 2.0,
            trace: bool = False, impl: str = "interpret"):
    """A ``harness.Run`` of the tiny benchmark, off the chip."""
    import time

    from bench import device, harness
    from bench.registry import Bench

    run = harness.Run(bench=Bench(root), name=name, seed=seed,
                      seconds=seconds, trace=trace,
                      t_start=time.perf_counter(), kernel_impl=impl).load()
    run.device = {"platform": "cpu", "kind": "cpu", "count": 1}
    run.peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    run.clock = device.CompileClock()
    run.trace_dir = os.path.join(root, "trace")
    return run
