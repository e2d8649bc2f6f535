"""The harness's arithmetic: kernel costs against hand counts, the trace
reduction on a recorded excerpt, the generator, and the metric readers
on a hand-built window. CPU only, no model.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import json
import os
import types

import numpy as np
import pytest

from harness import cell as cells
from harness import costs, trace
from harness.record import Run, percentile
from harness.serve import Step, Window

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
GEN = cells.load_module(os.path.join(BENCH, "traffic", "generator.py"),
                        "traffic_generator_test")
CONF = {"hidden_size": 8, "intermediate_size": 16, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 2,
        "vocab_size": 10}


def test_paged_decode_hand_count():
    # 2 slots at 10 and 20 tokens, 4 heads over 2 KV heads of width 8
    f, b = costs.paged_decode([10, 20], 4, 2, 8)
    assert f == 4 * 4 * 8 * 30                     # QK and PV, per token
    assert b == 2 * 2 * 8 * 2 * 30 + 2 * 4 * 8 * 2 * 2   # K+V, q and out


def test_flash_prefill_hand_count():
    # one prompt of 3 tokens: 1 + 2 + 3 = 6 query-key pairs
    f, b = costs.flash_prefill([3], 2, 1, 4)
    assert f == 4 * 2 * 4 * 6
    assert b == (2 * 2 + 2 * 1) * 4 * 2 * 3


def test_model_flops_hand_count():
    # per layer: q 8x8, k and v 8x4 each, o 8x8, gate/up/down 3x8x16
    mm = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert costs.matmul_params(CONF) == mm
    assert costs.decode_token_flops(CONF, 5) == (
        2 * 2 * mm + 2 * 4 * 4 * 2 * 5 + 2 * 8 * 10)
    assert costs.prefill_flops(CONF, 3) == (
        2 * 2 * mm * 3 + 2 * 4 * 4 * 2 * 6 + 2 * 8 * 10)


def test_least_time_names_its_bound():
    peak = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert costs.least_time(1000.0, 50.0, peak) == (10.0, "compute")
    assert costs.least_time(100.0, 50.0, peak) == (5.0, "memory")


def test_trace_reduction_on_recorded_excerpt():
    """An excerpt of a v5e trace of the ShareGPT cell: device operations
    and the benchmark's host spans, as ``trace.read_file`` returns them."""
    with open(os.path.join(os.path.dirname(__file__), "data",
                           "trace_excerpt.json")) as f:
        ex = json.load(f)
    ops = [tuple(o) for o in ex["ops"]]
    spans = [tuple(s) for s in ex["spans"]]
    lo, hi = ex["window_ns"]
    t = trace.reduce_events(ops, spans, lo, hi)
    busy = trace.union([(max(a, lo), min(b, hi)) for _, _, a, b in ops
                        if b > lo and a < hi])
    assert t.busy_s == pytest.approx(sum(b - a for a, b in busy) * 1e-9)
    assert 0 < t.busy_s < t.window_s == pytest.approx((hi - lo) * 1e-9)
    assert sum(t.idle_by_host.values()) == pytest.approx(
        t.window_s - t.busy_s)
    for k, want in ex["kernel_seconds"].items():
        assert t.kernel_seconds(k) == pytest.approx(want)
    assert len(t.breakdown()["device_ops"]) <= 10


def test_trace_reduction_hand_built():
    ops = [("fusion.1", "", 0, 10), ("paged_decode_x", "", 5, 20),
           ("copy", "flash kernel", 30, 40), ("late", "", 90, 200)]
    spans = [("bench.step", 0, 25), ("bench.on_cycle", 25, 28),
             ("bench.release", 28, 29), ("bench.step", 29, 80)]
    t = trace.reduce_events(ops, spans, 0, 100)
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s == pytest.approx(40e-9)         # 0-20, 30-40, 90-100
    assert t.kernel_seconds("paged_decode") == pytest.approx(15e-9)
    assert t.kernel_seconds("flash_prefill") == pytest.approx(10e-9)
    # gaps 20-30 (mid 25: on_cycle starts there), 40-90 (mid 65: step)
    assert t.idle_by_host == {"host: bench.on_cycle": pytest.approx(10e-9),
                              "host: bench.step": pytest.approx(50e-9)}


def _spec(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["sharegpt-poisson", "reasoning-closed"])
def test_generator_same_work_for_every_seed(mix):
    spec = _spec(mix)
    runs = [GEN.generate(spec, seed=s, seconds=30, slots=8, slot_tokens=2048,
                         vocab=1000) for s in (1, 2 ** 31 + 17)]
    sizes = [sorted((len(r.prompt), r.output_len) for r in t.all_requests()
                    if r.counted) for t in runs]
    assert sizes[0] == sizes[1]
    order = [[(len(r.prompt), r.output_len) for r in t.all_requests()]
             for t in runs]
    if spec.get("order", "permuted") == "fixed":
        assert order[0] == order[1]
    else:
        assert order[0] != order[1]
    assert any(not np.array_equal(a.prompt, b.prompt) for a, b in
               zip(runs[0].all_requests(), runs[1].all_requests()))
    for t in runs:
        for r in t.all_requests():
            assert len(r.prompt) + r.output_len <= 2048
            assert r.prompt.dtype == np.int32 and r.prompt.max() < 1000
    if spec["loop"] == "open":
        assert [r.due for r in runs[0].requests] == \
               [r.due for r in runs[1].requests]
    else:
        assert all(len(c) == spec["requests_per_client"]
                   for c in runs[0].clients) and len(runs[0].clients) == 8


def test_generator_matches_sharegpt_lengths():
    spec = _spec("sharegpt-poisson")
    t = GEN.generate(spec, seed=3, seconds=600, slots=8, slot_tokens=2048,
                     vocab=1000)
    p = np.array([len(r.prompt) for r in t.requests])
    assert p.min() >= 16 and p.max() <= 1024
    # lognormal(5, 1) has median e^5 ~ 148 before clipping
    assert 120 < np.median(p) < 180
    span = GEN.reachable_prompt_lengths(spec, 2048)
    assert span == {"max_prompt": 1024, "min_prompt": 16}


def _run(loop="open"):
    """A hand-built window: 3 requests due at 0, 1 and 2 s, window 0-10 s."""
    reqs = [GEN.Req(i, float(i), np.zeros(100, np.int32), 3)
            for i in range(3)]
    traffic = GEN.Traffic(loop, {}, requests=reqs)
    w = Window(t0=100.0, start=100.0, end=110.0, n_blocks=10)
    w.token_times = {0: [100.5, 100.6, 100.7],      # ttft 0.5, tpot 0.1
                     1: [102.0, 102.2, 102.4],      # ttft 1.0, tpot 0.2
                     2: [103.0]}                    # never finished
    w.admit_times = {0: 100.1, 1: 101.5}
    w.steps = [Step(100.0, 100.01, 1, 0, 2, (5, 6), 4, ()),
               Step(100.01, 100.04, 1, 1, 2, (6, 7), 6, (100,)),
               Step(100.04, 100.05, 1, 0, 1, (7,), 2, ())]
    spec = {"limits": {"norm_ttft_ms_per_token": 6.0, "tpot_ms": 150.0}}
    return Run(types.SimpleNamespace(limits={}), CONF, spec, traffic, w, 7.0,
               {"bf16_flops": 1e6, "hbm_bytes_per_s": 1e6})


def _metric(name):
    return cells.load_module(os.path.join(BENCH, "metrics", name + ".py"),
                             "m_" + name.replace(".", "_")).read


def test_readers_on_a_hand_built_window():
    run = _run()
    # request 2 never finished: ranked above both finished ones
    assert _metric("ttft_p50_ms")(run) == pytest.approx(1e3 * 1.0)
    assert _metric("tpot_p50_ms")(run) == pytest.approx(1e3 * 0.2)
    # request 0 meets 6 ms/token x 100 tokens = 600 ms; 1 misses (1000 ms)
    assert _metric("slo_attainment")(run) == pytest.approx(100 / 3)
    assert _metric("output_tok_s")(run) == pytest.approx(7 / 10)
    assert _metric("setup_s")(run) == 7.0
    assert _metric("queue_wait_p50_ms")(run) == pytest.approx(100.0)
    assert _metric("decode_batch_mean")(run) == pytest.approx(5 / 3)
    assert _metric("kv_occupancy_mean")(run) == pytest.approx(40.0)
    assert _metric("prefill_cycle_share")(run) == pytest.approx(100 / 3)
    assert _metric("fused_cycle_share")(run) == 0.0
    run.window.steps[2].stats = {"fused_cycles": 1}
    assert _metric("fused_cycle_share")(run) == pytest.approx(100 / 3)
    run.window.steps[2].stats = {}
    assert _metric("decode_cycle_ms")(run) == pytest.approx(10.0)
    assert _metric("prefill_cycle_ms")(run) == pytest.approx(30.0)
    assert _metric("device_idle_share")(run) is None      # no trace
    assert _metric("paged_decode_roofline")(run) is None


def test_percentile_is_numpy_linear():
    v = [5.0, 1.0, 3.0, 10.0, 7.0]
    for q in (10, 50, 90):
        assert percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_benchmark_names_files_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        cell = cells.load(ROOT, w["name"])
        assert cell.chips == w["chips"]
        assert "setup_s" in cell.end_to_end and cell.per_layer
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.isfile(cells.reader_path(ROOT, m["name"]))
    # a suffixed name without a file of its own reads with its base reader
    assert cells.reader_path(ROOT, "decode_cycle_ms.closed") == \
        os.path.join(BENCH, "metrics", "decode_cycle_ms.py")


def test_benchmark_json_shape():
    """Names, units, lengths and keys as the benchmark's format allows."""
    import re
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    one_line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and one_line(c["why"])
        assert c["file"].startswith("bench/") and len(c["reduced"]) <= 16
    seen = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert one_line(w["why"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and one_line(m["layer"])
        moved = e2e[m["moves"]].get("workloads")
        assert moved is None or set(m["workloads"]) <= set(moved)
        layers.add(m["layer"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
