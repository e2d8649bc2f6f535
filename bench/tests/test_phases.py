"""The split of the device's idle time by engine phase, on hand-built
operation and span lists: each idle nanosecond goes to the innermost span
open at that instant, the parts sum to the idle total, and a trace
without engine spans reads as nothing.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import os
import types

import pytest

from harness import phases, trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

OPS = [("fusion", "", 0, 5), ("paged_decode", "", 15, 18),
       ("copy", "", 42, 44), ("while", "", 70, 120)]
SPANS = [("engine.step", 0, 100), ("engine.schedule", 10, 20),
         ("engine.decode", 30, 50), ("engine.readback", 40, 45),
         ("engine.emit", 60, 80), ("bench.step", 0, 101)]


def test_idle_goes_to_the_innermost_span():
    p = phases.reduce_events(OPS, SPANS, 0, 150)
    ns = pytest.approx
    # gaps 5-15, 18-42, 44-70, 120-150
    assert p.idle_s == {
        "engine.step": ns(25e-9),         # 5-10, 20-30, 50-60
        "engine.schedule": ns(7e-9),      # 10-15, 18-20
        "engine.decode": ns(15e-9),       # 30-40, 45-50
        "engine.readback": ns(3e-9),      # 40-42, 44-45, inside decode
        "engine.emit": ns(10e-9),         # 60-70
        phases.OUTSIDE: ns(30e-9),        # 120-150, after the step
    }
    assert p.idle_share("schedule") == ns(100 * 7 / 150)
    assert p.idle_share("decode_host") == ns(100 * 25 / 150)
    assert p.idle_share("readback") == ns(100 * 3 / 150)
    assert p.idle_share("step_other") == ns(100 * 25 / 150)
    assert p.idle_share("prefill_host") == 0.0
    assert p.idle_share("outside_step") == ns(100 * 30 / 150)
    assert p.span_s["engine.schedule"] == [ns(10e-9)]


@pytest.mark.parametrize("window", [(0, 150), (12, 90), (41, 43)])
def test_parts_sum_to_the_idle_share(window):
    """Against the benchmark's own reduction, over whole and cut
    windows."""
    lo, hi = window
    p = phases.reduce_events(OPS, SPANS, lo, hi)
    t = trace.reduce_events(OPS, [], lo, hi)
    idle = 100.0 * (1.0 - t.busy_s / t.window_s)
    assert sum(p.idle_share(g) for g in phases.GROUPS) == \
        pytest.approx(idle)


def test_equal_starts_charge_the_shorter_span():
    p = phases.reduce_events([], [("engine.step", 0, 10),
                                  ("engine.admit", 0, 4)], 0, 10)
    assert p.idle_s == {"engine.admit": pytest.approx(4e-9),
                        "engine.step": pytest.approx(6e-9)}


def test_no_engine_span_reads_nothing(tmp_path):
    bench_only = [s for s in SPANS if not s[0].startswith("engine.")]
    assert phases.reduce_events(OPS, bench_only, 0, 150) is None
    # engine spans wholly outside the window
    assert phases.reduce_events(OPS, SPANS, 101, 150) is None
    assert phases.read(str(tmp_path)) is None           # no trace file
    run = types.SimpleNamespace(trace=None)
    reader = os.path.join(BENCH, "metrics", "idle_schedule_share.py")
    assert phases.idle_share(run, reader, "schedule") is None
    assert run.phases is None


def test_span_names_match_the_program():
    """The copy the readers match by; the program's tuple is imported
    here only to compare."""
    from repro.obs.phases import ENGINE_PHASES
    assert set(phases.ENGINE_SPANS) == set(ENGINE_PHASES)
    grouped = {n for g in phases.GROUPS.values() for n in g}
    assert grouped == set(phases.ENGINE_SPANS) | {phases.OUTSIDE}


def test_host_syncs_per_step_over_steps_with_device_work():
    from harness import cell as cells
    from harness.serve import Step
    read = cells.load_module(
        os.path.join(BENCH, "metrics", "host_syncs_per_step.py"),
        "m_host_syncs_per_step").read

    def step(decode, prefill, syncs):
        return Step(start=0.0, end=1.0, decode=decode, prefill=prefill,
                    batch=decode, contexts=(), kv_used=0,
                    prefill_lengths=(), stats={"host_syncs": syncs})

    steps = [step(1, 0, 3), step(1, 1, 4), step(0, 0, 0), step(0, 1, 1)]
    run = types.SimpleNamespace(window_steps=lambda: steps)
    assert read(run) == pytest.approx(8 / 3)
    for s in steps:                 # a program without the counter
        s.stats = {}
    assert read(run) is None
