"""A tiny cell on the CPU: a scratch root beside the real benchmark's
files, with a small model, small mixes and the TPU check stubbed, for
the harness's own tests."""

from __future__ import annotations

import json
import os
import shutil

import jax

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

CONFIG = {
    "name": "tiny", "source": "test", "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "attention_bias": True, "qk_norm": True, "tie_word_embeddings": True,
    "reduced": [],
    "pool": {"slot_tokens": 256, "page_size": 16, "slots": 4},
    "scheduler": {"unit_quantum": 4},
}
OPEN = {
    "generator": "generator", "loop": "open", "rate_req_s": 12.0,
    "prompt": {"dist": "lognormal", "log_mean": 2.8, "log_std": 0.4,
               "lo": 8, "hi": 24},
    "output": {"dist": "lognormal", "log_mean": 2.3, "log_std": 0.4,
               "lo": 4, "hi": 16},
    "base_seed": 7, "drain_s": 60, "trace_seconds": 1,
    "limits": {"norm_ttft_ms_per_token": 3000.0, "tpot_ms": 5000.0},
    "server": {"max_prefill_batch": 2, "fused": False},
}
CLOSED = {
    "generator": "generator", "loop": "closed",
    "prompt": {"dist": "lognormal", "log_mean": 2.5, "log_std": 0.3,
               "lo": 8, "hi": 16},
    "output": {"dist": "uniform", "lo": 8, "hi": 12},
    "requests_per_client": 64, "lead_in_s": 0.5, "base_seed": 8,
    "drain_s": 0, "trace_seconds": 1,
    "limits": {"norm_ttft_ms_per_token": 3000.0, "tpot_ms": 5000.0},
    "server": {"max_prefill_batch": 2, "fused": False},
}


def make_root(tmp: str, *, limit: float = 1.0) -> str:
    """A root holding a BENCHMARK.json of two tiny cells, the real
    metrics, generator and peaks, and a peak entry for this host's
    device kind (test data only: no result carries it)."""
    bench = os.path.join(tmp, "bench")
    for sub in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(bench, sub), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bench, "metrics"), dirs_exist_ok=True)
    shutil.copy(os.path.join(BENCH, "traffic", "generator.py"),
                os.path.join(bench, "traffic", "generator.py"))
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    peaks["kinds"][jax.devices()[0].device_kind] = {
        "name": "test-host", "bf16_flops": 1e12, "hbm_bytes_per_s": 1e11,
        "hbm_bytes": 1 << 34}
    _dump(os.path.join(bench, "peaks.json"), peaks)
    _dump(os.path.join(bench, "configs", "tiny.json"), CONFIG)
    _dump(os.path.join(bench, "traffic", "tiny-open.json"), OPEN)
    _dump(os.path.join(bench, "traffic", "tiny-closed.json"), CLOSED)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    cells = [{"name": "tiny.open", "config": "tiny", "traffic": "tiny-open",
              "chips": 1, "why": "test"},
             {"name": "tiny.closed", "config": "tiny",
              "traffic": "tiny-closed", "chips": 1, "why": "test"}]
    for c in cells:
        _dump(os.path.join(bench, "limits", c["name"] + ".json"),
              {"sample_requests": 64, "widest_gap": limit})
    cell_of = {"qwen3-1.7b.sharegpt-poisson": "tiny.open",
               "qwen1.5-4b.reasoning-closed": "tiny.closed",
               "qwen3-1.7b.reasoning-closed": "tiny.closed"}
    for m in real["end_to_end"] + real["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({cell_of[w] for w in m["workloads"]})
    real["workloads"] = cells
    _dump(os.path.join(tmp, "BENCHMARK.json"), real)
    return tmp


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
