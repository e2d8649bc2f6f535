"""Observability layer (docs/OBSERVABILITY.md): histogram/percentile
math, Prometheus rendering, disabled-path no-ops, Chrome trace-event
schema validity under VirtualClock, span invariants across a
preempt→resume round-trip, and snapshot↔EngineStats reconciliation."""

import json
import math
from dataclasses import fields as dataclass_fields

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import ServerConfig
from repro.core.engine import BulletServer
from repro.kvcache.paged import PagedKVPool
from repro.models import init_params
from repro.obs import NULL_OBS, Observability
from repro.obs.metrics import (MetricsRegistry, NULL_INSTRUMENT,
                               _NullInstrument)
from repro.serving.frontend import (OnlineFrontend, VirtualClock,
                                    WallClock, estimator_cycle_cost)
from repro.serving.request import (Phase, Request, ServingMetrics, SLO,
                                   WORKLOAD_SLOS)
from repro.serving.workload import generate_trace


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("qwen3-1.7b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


@pytest.fixture(scope="module")
def replayed(setup):
    """One instrumented virtual-clock replay shared by the export tests:
    estimator-clocked so every cycle gets a recorded actual."""
    cfg, params = setup
    obs = Observability()
    server = BulletServer(cfg, params, slo=SLO(3.0, 150.0), max_slots=4,
                          max_len=48, obs=obs)
    trace = generate_trace("sharegpt", rate_req_s=200.0, duration_s=10.0,
                           seed=3, max_requests=6)
    rng = np.random.default_rng(3)
    for r in trace:
        r.prompt_len = max(4, min(r.prompt_len, 16))
        r.output_len = max(2, min(r.output_len, 8))
    fe = OnlineFrontend(server, VirtualClock(),
                        cycle_cost=estimator_cycle_cost)
    for r in trace:
        fe.submit(r, rng.integers(0, cfg.vocab_size, r.prompt_len,
                                  dtype=np.int32))
    m = fe.run()
    assert m.n_requests == len(trace)
    return server, trace, m


# -- histogram / percentile math ---------------------------------------

def test_histogram_buckets_and_cumulative():
    r = MetricsRegistry()
    h = r.histogram("t_seconds", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 1.5, 3.0, 9.0):
        h.observe(v)
    assert h.counts == [1, 2, 1, 1]          # last slot is +Inf
    assert h.cumulative() == [1, 3, 4, 5]
    assert h.count == 5
    assert h.sum == pytest.approx(15.5)
    assert h.mean == pytest.approx(3.1)


def test_histogram_quantile_interpolation():
    r = MetricsRegistry()
    h = r.histogram("t_seconds", buckets=(1.0, 2.0, 4.0))
    for _ in range(4):
        h.observe(1.5)                        # all land in (1, 2]
    # rank q*4 interpolated linearly inside the (1, 2] bucket
    assert h.quantile(0.5) == pytest.approx(1.5)
    assert h.quantile(1.0) == pytest.approx(2.0)
    h.observe(100.0)                          # +Inf bucket
    assert h.quantile(1.0) == 4.0             # clamps to last finite bound
    assert math.isnan(MetricsRegistry().histogram(
        "e_seconds", buckets=(1.0,)).quantile(0.5))


def test_histogram_rejects_duplicate_buckets():
    with pytest.raises(AssertionError):
        MetricsRegistry().histogram("bad_seconds", buckets=(1.0, 1.0))


def test_prometheus_render_and_snapshot():
    r = MetricsRegistry()
    c = r.counter("reqs_total", "requests", labels=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    r.gauge("occ", "occupancy").set(0.25)
    h = r.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    text = r.render()
    assert '# TYPE reqs_total counter' in text
    assert 'reqs_total{kind="a"} 3' in text
    assert 'reqs_total{kind="b"} 1' in text
    assert 'occ 0.25' in text
    # cumulative buckets ending in +Inf, plus _sum/_count
    assert 'lat_seconds_bucket{le="0.1"} 1' in text
    assert 'lat_seconds_bucket{le="1"} 2' in text
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert 'lat_seconds_count 2' in text
    snap = r.snapshot()
    assert snap['reqs_total{kind="a"}'] == 3
    assert snap["lat_seconds_count"] == 2
    assert snap["lat_seconds_sum"] == pytest.approx(0.55)
    assert r.value("reqs_total", kind="a") == 3
    assert r.value("reqs_total", kind="zzz") is None
    assert r.value("nope") is None


def test_registry_rejects_kind_or_label_redefinition():
    r = MetricsRegistry()
    r.counter("m_total", labels=("kind",))
    with pytest.raises(AssertionError):
        r.gauge("m_total")
    with pytest.raises(AssertionError):
        r.counter("m_total", labels=("other",))


def test_disabled_registry_is_noop():
    r = MetricsRegistry(enabled=False)
    c = r.counter("x_total")
    assert c is NULL_INSTRUMENT
    assert isinstance(c.labels(kind="a"), _NullInstrument)
    c.inc()
    r.gauge("g").set(5)
    r.histogram("h_seconds").observe(1.0)
    assert r.snapshot() == {}
    assert r.render() == ""
    # the NULL_OBS singleton: spans and traces append nothing
    NULL_OBS.spans.mark(0, "submit", 0.0)
    assert NULL_OBS.spans.all() == []
    assert len(NULL_OBS.trace) == 0


# -- ServingMetrics zero-finished sentinel ------------------------------

def test_serving_metrics_empty_sentinel():
    m = ServingMetrics.from_requests([], WORKLOAD_SLOS["sharegpt"])
    assert m.is_empty
    for f in dataclass_fields(ServingMetrics):
        v = getattr(m, f.name)
        assert v == 0 and not math.isnan(v), f.name
    assert "n=0" in m.row() and "NaN" not in m.row()
    # unfinished requests only -> same sentinel
    m2 = ServingMetrics.from_requests(
        [Request(rid=0, arrival=0.0, prompt_len=4, output_len=4)],
        WORKLOAD_SLOS["sharegpt"])
    assert m2.is_empty


# -- Chrome trace-event export ------------------------------------------

def test_chrome_trace_schema_valid(replayed):
    server, trace, _ = replayed
    doc = server.obs.chrome_trace()
    text = json.dumps(doc)                   # must be JSON-serializable
    doc = json.loads(text)
    evs = doc["traceEvents"]
    assert evs and doc["otherData"]["dropped_cycles"] == 0
    for e in evs:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e), e
        assert e["ph"] in {"X", "C", "M", "b", "e", "n"}, e
        assert e["ts"] >= 0
    # VirtualClock timestamps are monotone under the exporter's sort
    ts = [e["ts"] for e in evs]
    assert ts == sorted(ts)
    cycles = [e for e in evs if e["ph"] == "X"]
    assert cycles
    for e in cycles:
        assert e["dur"] >= 0
        assert e["name"].startswith("cycle:")
        # estimator-clocked replay: every cycle carries both durations
        assert e["args"]["predicted_ms"] is not None
        assert e["args"]["actual_ms"] is not None
    # one async begin/end pair per finished request
    b = [e for e in evs if e["ph"] == "b"]
    e_ = [e for e in evs if e["ph"] == "e"]
    assert len(b) == len(e_) == len(trace)
    assert {e["id"] for e in b} == {str(r.rid) for r in trace}


def test_counters_and_spans_cover_the_run(replayed):
    server, trace, _ = replayed
    obs = server.obs
    assert obs.registry.value(
        "bullet_requests_submitted_total") == len(trace)
    assert obs.registry.value(
        "bullet_requests_finished_total") == len(trace)
    for r in trace:
        span = obs.spans.get(r.rid)
        assert span.count("submit") == 1
        assert span.count("first_token") == 1
        assert span.count("finish") == 1
        bd = span.breakdown()
        assert bd["ttft_s"] >= 0 and bd["queue_s"] >= 0
        assert bd["ttft_s"] == pytest.approx(r.ttft)


def test_metrics_snapshot_reconciles_with_engine_stats(replayed):
    server, trace, m = replayed
    obs = server.obs
    obs.sync_engine_stats(server)
    snap = obs.registry.snapshot()
    for f in dataclass_fields(server.stats):
        assert snap[f"bullet_engine_{f.name}_total"] == float(
            getattr(server.stats, f.name)), f.name
    assert snap['bullet_kv_pool_ops_total{op="free"}'] == \
        server.pool.ops.frees
    # cycle histograms saw every observed cycle
    n_cycles = sum(v for k, v in snap.items()
                   if k.startswith("bullet_cycle_seconds_count"))
    assert n_cycles == len(obs.trace)
    assert snap["bullet_kv_free_blocks"] == server.pool.free_blocks
    # the rendered exposition carries the same numbers
    text = obs.render_metrics()
    assert (f"bullet_engine_decode_iterations_total "
            f"{server.stats.decode_iterations}") in text


def test_span_invariants_across_preempt_resume(setup):
    """The preemption recipe from test_frontend, instrumented: the
    victim's span accumulates preempt/resume marks, keeps exactly one
    first_token, and its breakdown stays attributable."""
    cfg, params = setup
    obs = Observability()
    server = BulletServer(cfg, params, slo=SLO(3.0, 150.0), max_slots=2,
                          max_len=40, max_prefill_batch=1, obs=obs)
    server.pool = PagedKVPool(48, block_size=16)
    rng = np.random.default_rng(1)
    young = Request(rid=0, arrival=1.0, prompt_len=8, output_len=12)
    server.submit(young, rng.integers(0, cfg.vocab_size, 8))
    now = 1.0
    while young.phase != Phase.DECODE:
        server.step(now)
        now += 1e-3
    for _ in range(3):
        server.step(now)
        now += 1e-3
    old = Request(rid=1, arrival=0.0, prompt_len=30, output_len=4)
    server.submit(old, rng.integers(0, cfg.vocab_size, 30))
    while old.phase == Phase.QUEUED:
        server.step(now)
        now += 1e-3
    assert server.stats.preempted == 1
    while not server.idle:                   # drain on the same clock
        server.step(now)
        now += 1e-3
    server.pool.check_invariants()
    assert young.phase == Phase.FINISHED

    span = obs.spans.get(young.rid)
    assert span.count("submit") == 1
    assert span.count("finish") == 1
    assert span.count("preempt") == 1
    assert span.count("resume") == 1
    assert span.count("admit") == 1          # initial admission only
    # resumed prefill does not re-emit the first token
    assert span.count("first_token") == 1
    ts = [e.t for e in span.events]
    assert ts == sorted(ts)
    bd = span.breakdown()
    assert bd["preempts"] == bd["resumes"] == 1
    assert bd["queue_s"] >= 0 and bd["ttft_s"] >= 0
    assert bd["decode_s"] >= 0
    assert span.end >= span.start
    # pool op counters saw the eviction
    obs.sync_engine_stats(server)
    assert obs.registry.value("bullet_kv_pool_ops_total", op="preempt") \
        == 1


def test_cycle_events_describe_the_cycle(replayed):
    server, _, _ = replayed
    kinds = {ev.kind for ev in server.obs.trace}
    assert kinds <= {"serial", "fused", "chip"} and kinds
    for ev in server.obs.trace:
        assert ev.predicted_s > 0
        assert ev.actual_s is not None and ev.actual_s > 0
        assert 0.0 <= ev.kv_occupancy <= 1.0
        assert ev.kv_used_blocks <= ev.kv_total_blocks
        assert ev.reason != ""
        assert ev.decode_batch >= 0 and ev.prefill_tokens >= 0
    # scheduler rationale counters cover every decision-carrying cycle
    snap = server.obs.registry.snapshot()
    decided = sum(v for k, v in snap.items()
                  if k.startswith("bullet_scheduler_decisions_total"))
    assert decided > 0


def test_wall_clock_cycles_carry_measured_durations(setup):
    """Under a WallClock every cycle event gets the step's measured host
    time, and nothing of it reaches the estimator: no (predicted, actual)
    pair is logged."""
    cfg, params = setup
    obs = Observability()
    server = BulletServer(cfg, params, config=ServerConfig(
        slo=SLO(3.0, 150.0), max_slots=4, max_len=48, obs=obs))
    trace = generate_trace("sharegpt", rate_req_s=200.0, duration_s=10.0,
                           seed=4, max_requests=4)
    rng = np.random.default_rng(4)
    fe = OnlineFrontend(server, WallClock(speed=1000.0))
    for r in trace:
        r.prompt_len = max(4, min(r.prompt_len, 16))
        r.output_len = max(2, min(r.output_len, 8))
        fe.submit(r, rng.integers(0, cfg.vocab_size, r.prompt_len,
                                  dtype=np.int32))
    assert fe.run().n_requests == len(trace)
    assert len(obs.trace) > 0
    for ev in obs.trace:
        assert ev.actual_s is not None and ev.actual_s > 0
    assert not server.pred_actual
    snap = obs.registry.snapshot()
    assert sum(v for k, v in snap.items()
               if k.startswith("bullet_cycle_seconds_count")) == \
        len(obs.trace)
