"""Engine phase spans and the host-sync counter (docs/OBSERVABILITY.md,
"Engine phase spans"): under ``jax.profiler.trace`` a small
``BulletServer`` writes host events named only from ``ENGINE_PHASES``,
every one inside an ``engine.step``; each kind of step adds a stated
number of device->host reads to ``EngineStats.host_syncs``; and tracing
does not change the served tokens."""

import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.config import CacheConfig, ExecConfig, ServerConfig
from repro.core.engine import BulletServer
from repro.models import init_params
from repro.obs.phases import ENGINE_PHASES
from repro.serving.request import Phase, Request, SLO


@pytest.fixture(scope="module")
def setup():
    """Three pattern repeats: a prompt takes three prefill steps."""
    cfg = get_config("qwen3-1.7b").reduced(n_layers=3)
    params = init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    return cfg, params


def mk_server(cfg, params, fused=False):
    return BulletServer(cfg, params, config=ServerConfig(
        slo=SLO(3.0, 150.0), max_slots=4, max_len=48,
        cache=CacheConfig(paged=True, page_size=16),
        execution=ExecConfig(fused=fused)))


def submit(server, cfg, rid, prompt_len, output_len):
    req = Request(rid=rid, arrival=0.0, prompt_len=prompt_len,
                  output_len=output_len)
    rng = np.random.default_rng(rid)
    server.submit(req, rng.integers(0, cfg.vocab_size, prompt_len))
    return req


def serve(server, cfg):
    """Four requests, two admitted while others decode; returns the
    steps run."""
    for rid, (p, o) in enumerate([(8, 6), (12, 4)]):
        submit(server, cfg, rid, p, o)
    steps = 0
    while not server.idle:
        server.step(0.0)
        steps += 1
        if steps == 3:
            for rid, (p, o) in enumerate([(5, 7), (9, 3)], start=2):
                submit(server, cfg, rid, p, o)
    return steps


@pytest.fixture(scope="module")
def traced(setup, tmp_path_factory):
    """One serve under the profiler, after an untraced one compiled
    every shape; its outputs, step count and host events."""
    cfg, params = setup
    serve(mk_server(cfg, params), cfg)
    server = mk_server(cfg, params)
    out = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(out):
        steps = serve(server, cfg)
    path = glob.glob(os.path.join(out, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                events += [(ev.name, ev.start_ns,
                            ev.start_ns + ev.duration_ns)
                           for ev in line.events
                           if ev.name.startswith("engine.")]
    return server.outputs, steps, events


def test_phase_spans_nest_inside_the_step(traced):
    _, steps, events = traced
    names = {n for n, _, _ in events}
    assert names <= set(ENGINE_PHASES)
    # every phase of the serial paged path appears (refit never solves
    # without measured cycle times)
    assert names == set(ENGINE_PHASES) - {"engine.refit"}
    outer = sorted((a, b) for n, a, b in events if n == "engine.step")
    assert len(outer) == steps
    for name, a, b in events:
        if name == "engine.step":
            continue
        inside = [(s, e) for s, e in outer if s <= a and b <= e]
        assert len(inside) == 1, (name, a, b)
    # spans per phase per step, not per slot: a serial step schedules
    # twice (prefill group, decode iteration) and reads at most four
    # arrays (first tokens, active, pos, next tokens)
    most = {"engine.admit": 1, "engine.schedule": 2, "engine.prefill": 1,
            "engine.migrate": 1, "engine.tables": 1, "engine.decode": 1,
            "engine.emit": 1, "engine.readback": 4}
    for s, e in outer:
        for name, cap in most.items():
            n = sum(1 for m, a, b in events if m == name and s <= a < e)
            assert n <= cap, (name, n)


def test_tokens_identical_with_profiler_on_and_off(setup, traced):
    cfg, params = setup
    server = mk_server(cfg, params)
    serve(server, cfg)
    assert server.outputs == traced[0]


@pytest.mark.parametrize("fused", [False, True], ids=["serial", "fused"])
def test_host_syncs_per_step(setup, fused):
    """A decode step reads ``pos`` and the sampled tokens back (2), and
    ``active`` only when it changed since it was last read. On a prompt's
    last group the serial step also reads the first tokens and the
    ``active`` their migration made (4); the fused cycle migrates after
    its decode, so it adds the first tokens (3) and its next step reads
    the new ``active`` (3)."""
    cfg, params = setup
    server = mk_server(cfg, params, fused=fused)
    a = submit(server, cfg, 0, 8, 20)
    while a.phase != Phase.DECODE:
        server.step(0.0)

    def syncs():
        before = server.stats.host_syncs
        server.step(0.0)
        return server.stats.host_syncs - before

    assert syncs() == 2
    b = submit(server, cfg, 1, 8, 4)
    got = [syncs()]
    while b.phase != Phase.DECODE:
        got.append(syncs())
    got.append(syncs())
    groups = cfg.n_pattern_repeats
    assert got == [2] * (groups - 1) + ([3, 3] if fused else [4, 2])
    assert server.stats.fused_cycles == (groups if fused else 0)
