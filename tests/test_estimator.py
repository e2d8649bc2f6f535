"""Performance estimator: Eq. 1/2 behavior, profile-fit recovery, and
property tests on monotonicity/contention invariants."""


import hypothesis.strategies as st
import pytest
from hypothesis import given, settings

from repro.configs import get_config
from repro.core.estimator import (HardwareSpec, PerfEstimator, fit_params,
                                  wave_quantization_idle)
from repro.core.profiler import (SurrogateMachine, TRUE_PARAMS,
                                 run_profiling)

CFG = get_config("llama3.1-8b")
HW = HardwareSpec()


# -- Eq. 1 -------------------------------------------------------------------

def test_wave_quantization_exact_values():
    # paper §2.2.1: g=109 tiles on 108 SMs wastes ~half the second wave
    assert wave_quantization_idle(108, 108) == 0.0
    assert abs(wave_quantization_idle(109, 108) - (1 - 109 / 216)) < 1e-12
    assert wave_quantization_idle(1, 108) == pytest.approx(1 - 1 / 108)
    assert wave_quantization_idle(0, 108) == 0.0


@settings(max_examples=200, deadline=None)
@given(st.integers(1, 4096), st.integers(1, 512))
def test_wave_quantization_bounds(g, m):
    s = wave_quantization_idle(g, m)
    assert 0.0 <= s < 1.0
    # perfect fills have zero idle
    if g % m == 0:
        assert s == pytest.approx(0.0)


# -- Eq. 2 -------------------------------------------------------------------

def test_more_units_never_slower_at_fixed_grid():
    est = PerfEstimator(HW)
    t_prev = float("inf")
    for u in range(2, HW.total_units + 1, 2):
        t = est.kernel_time(1e12, 1e9, u, grid=10 ** 6)
        assert t <= t_prev * 1.0001
        t_prev = t


def test_colocation_contention_slows_down():
    est = PerfEstimator(HW)
    t_iso = est.decode_iter_time(CFG, 16, 1024, 16, colocated=False)
    t_col = est.decode_iter_time(CFG, 16, 1024, 16, colocated=True)
    assert t_col > t_iso


def test_oversubscription_slows_down():
    est = PerfEstimator(HW)
    t1 = est.prefill_time(CFG, 2048, HW.total_units, colocated=True)
    t2 = est.prefill_time(CFG, 2048, HW.total_units, colocated=True,
                          oversub=2.0)
    assert t2 > t1 * 1.3


def test_decode_superlinear_prefill_sublinear():
    """Paper Fig. 7: decode scales super-linearly with units, prefill
    sub-linearly (per unit)."""
    est = PerfEstimator(HW, TRUE_PARAMS)
    # decode at half units should be LESS than 2x slower (super-linear bw)
    td_full = est.decode_iter_time(CFG, 32, 4096, HW.total_units)
    td_half = est.decode_iter_time(CFG, 32, 4096, HW.total_units // 2)
    assert td_half < 2.0 * td_full
    # prefill at half units should be MORE than 2x slower-ish per Eq. 2
    tp_full = est.prefill_time(CFG, 4096, HW.total_units)
    tp_half = est.prefill_time(CFG, 4096, HW.total_units // 2)
    assert tp_half > 1.9 * tp_full


# -- profile fitting ---------------------------------------------------------

def test_fit_recovers_surrogate_parameters():
    samples = run_profiling(CFG, HW, max_sl=4096, max_bs=32, max_cl=4096)
    assert len(samples) > 50
    fitted = fit_params(samples, CFG, HW, iters=30)
    assert abs(fitted.alpha_c - TRUE_PARAMS.alpha_c) < 0.1
    assert abs(fitted.sustained_compute - TRUE_PARAMS.sustained_compute) < 0.08
    assert abs(fitted.p_c - TRUE_PARAMS.p_c) < 0.08


def test_fitted_estimator_accuracy_held_out():
    """Paper Fig. 15: mean relative error ~19% suffices; we require <15%."""
    samples = run_profiling(CFG, HW, max_sl=4096, max_bs=32, max_cl=4096)
    fitted = fit_params(samples, CFG, HW, iters=30)
    est = PerfEstimator(HW, fitted)
    truth = SurrogateMachine(HW, seed=99)
    errs = []
    for sl, bs, cl, pm in [(1500, 12, 1500, 20), (3000, 24, 2000, 16),
                           (700, 8, 700, 26), (5000, 40, 1000, 10)]:
        dm = HW.total_units - pm
        errs.append(abs(est.prefill_time(CFG, sl, pm, colocated=True)
                        / truth.measure_prefill(CFG, sl, pm, colocated=True)
                        - 1))
        errs.append(abs(est.decode_iter_time(CFG, bs, cl, dm, colocated=True)
                        / truth.measure_decode(CFG, bs, cl, dm, colocated=True)
                        - 1))
    assert sum(errs) / len(errs) < 0.15


def test_online_feedback_corrects_bias():
    est = PerfEstimator(HW)
    pred0 = est.decode_iter_time(CFG, 8, 512, 16)
    for _ in range(20):
        est.observe("decode", pred0, pred0 * 2.0)   # consistently 2x slower
    pred1 = est.decode_iter_time(CFG, 8, 512, 16)
    assert pred1 > pred0 * 1.5


# -- lockstep model (chunked prefill baseline physics) ------------------------

def test_lockstep_serializes_phases():
    """The hybrid-batch time must exceed the max of its phase components
    (paper §2.3: lock-step underutilizes both resources)."""
    est = PerfEstimator(HW, TRUE_PARAMS)
    t_hybrid = est.lockstep_iter_time(CFG, [(2048, 0)], ds=64, ctx_d=2048)
    t_prefill_only = est.lockstep_iter_time(CFG, [(2048, 0)], ds=0, ctx_d=0)
    t_decode_only = est.lockstep_iter_time(CFG, [], ds=64, ctx_d=2048)
    assert t_hybrid > max(t_prefill_only, t_decode_only)
    assert t_hybrid < t_prefill_only + t_decode_only + 1e-3


def test_chunked_reload_increases_cost():
    est = PerfEstimator(HW, TRUE_PARAMS)
    t0 = est.lockstep_iter_time(CFG, [(1024, 0)], 0, 0)
    t_late = est.lockstep_iter_time(CFG, [(1024, 15 * 1024)], 0, 0)
    assert t_late > t0 * 1.05          # paper Fig. 4: later chunks slower


# -- the served path's spec comes from the devices it holds -----------------

class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


def test_hardware_for_prices_the_devices_in_use():
    from repro.core.estimator import hardware_for
    hw = hardware_for([_Dev("TPU v5 lite")] * 4)
    assert (hw.n_chips, hw.peak_flops, hw.hbm_bw) == (4, 197e12, 819e9)
    assert hardware_for([_Dev("TPU v5 lite")]).total_units == \
        hw.units_per_chip


@pytest.mark.parametrize("kinds", [["TPU v9 imaginary"],
                                   ["TPU v5 lite", "cpu"]])
def test_hardware_for_rejects_unknown_or_mixed_kinds(kinds):
    from repro.core.estimator import hardware_for
    with pytest.raises(ValueError):
        hardware_for([_Dev(k) for k in kinds])
