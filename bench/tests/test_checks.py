"""``correct`` on the CPU: a whole run of a small cell through
``run.main`` with only the TPU check stubbed comes out correct; the same
run with the timed path broken underneath comes out not correct; and the
float8 control, put in the program's place, lands above the limit that
the program's own runs stay under.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import contextlib
import io
import json

import jax
import numpy as np
import pytest

import run as bench_run
import tiny
from harness import device, reference, model
from repro.core import engine

#: the small cell's limit: comparing every finished request, its sound
#: runs read at most 0.0124 and the float8 control at least 0.0498 (seeds
#: 1-16 closed, 1-6 open, CPU)
LIMIT = 0.028
SMALL = {"hidden_size": 256, "intermediate_size": 512,
         "num_hidden_layers": 4, "head_dim": 64, "vocab_size": 8192}


@pytest.fixture()
def root(tmp_path, monkeypatch):
    monkeypatch.setattr(device, "require_accelerator",
                        lambda chips, peaks: jax.devices()[:chips])
    monkeypatch.setattr(tiny, "CONFIG", dict(tiny.CONFIG, **SMALL))
    return tiny.make_root(str(tmp_path), limit=LIMIT)


def _main(root, cell, seed, trace=0):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                             "--seconds", "2", "--trace", str(trace)],
                            root=root)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_sound_run_is_correct(root, monkeypatch):
    """Kernels in interpret mode, traced, with a seed past 32 bits."""
    monkeypatch.setenv("REPRO_FORCE_PALLAS", "1")
    res = _main(root, "tiny.closed", 2 ** 31 + 5, trace=1)
    assert res["correct"] is True
    assert list(res)[-1] == "checks"
    assert res["checks"]["widest_gap"]["value"] <= LIMIT
    assert {"output_tok_s", "setup_s"} & set(res["metrics"]) == set()
    assert "decode_batch_mean" in res["metrics"]
    assert res["metrics"]["window_compiles"]["value"] == 0


def _token_altered(monkeypatch):
    real = engine._decode_iteration

    def step(*a, **k):
        toks, cache = real(*a, **k)
        return (toks + 1) % k["cfg"].vocab_size, cache
    monkeypatch.setattr(engine, "_decode_iteration", step)


def _state_unchanged(monkeypatch):
    keep = jax.jit(engine._decode_iteration_impl, static_argnames=("cfg",))

    def step(params, cache, *a, **k):
        toks, _ = keep(params, cache, *a, **k)
        return toks, cache
    monkeypatch.setattr(engine, "_decode_iteration", step)


def _served_weights(monkeypatch, change):
    real = model.program_weights
    monkeypatch.setattr(model, "program_weights",
                        lambda conf, seed: change(real(conf, seed)))


def _biases_dropped(monkeypatch):
    def change(tree):
        blk = dict(tree["blocks"][0])
        for b in ("bq", "bk", "bv"):
            blk[b] = jax.numpy.zeros_like(blk[b])
        return dict(tree, blocks=(blk,))
    _served_weights(monkeypatch, change)


def _norm_weights_ignored(monkeypatch):
    """The program stores a scale as ``scale - 1``: zero is the identity."""
    def change(tree):
        blk = dict(tree["blocks"][0])
        for n in ("ln1", "ln2", "q_norm", "k_norm"):
            blk[n] = jax.numpy.zeros_like(blk[n])
        return dict(tree, blocks=(blk,),
                    final_norm=jax.numpy.zeros_like(tree["final_norm"]))
    _served_weights(monkeypatch, change)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _biases_dropped, _norm_weights_ignored],
                         ids=["token_altered", "state_unchanged",
                              "biases_dropped", "norm_weights_ignored"])
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    """A decode step that alters a token or returns its KV pool unchanged,
    or the program served without the q/k/v biases or with every RMSNorm
    scale at 1 while the reference keeps them."""
    fault(monkeypatch)
    res = _main(root, "tiny.closed", 11)
    assert res["correct"] is False
    assert res["checks"]["widest_gap"]["value"] > LIMIT


def test_fp8_control_is_not_correct(root):
    """At each position of a sound run's prompts and served tokens, the
    token the float8 reference puts first lies further below the float32
    reference's best than the limit allows, on every seed tried."""
    import run as r
    from harness import cell as cells, serve
    from harness.record import Run
    cell = cells.load(root, "tiny.closed")
    counter = device.CompileCounter()
    pool = cell.conf["pool"]
    for seed in (1, 3, 5):
        params, server = r.set_up(cell, seed, counter)
        traffic = cell.generator.generate(
            cell.spec, seed=seed, seconds=3, slots=pool["slots"],
            slot_tokens=pool["slot_tokens"], vocab=cell.conf["vocab_size"])
        w = serve.run_window(server, traffic, seconds=3, drain_s=0,
                             counter=counter)
        run = Run(cell, cell.conf, cell.spec, traffic, w, 0.0, {})
        _, _, checks, correct = r.check(run, seed, control=True)
        assert correct, (seed, checks)
        assert checks["control_widest_gap"]["value"] > LIMIT


def test_reference_matches_the_program_forward():
    """The reference and the program's own float32 XLA forward agree on a
    small model's logits (the weights converted by ``to_program``)."""
    from repro.models import forward
    conf = dict(tiny.CONFIG, **SMALL)
    cfg = model.model_config(conf)
    w = model.published_weights(conf, 3)
    params = model.program_weights(conf, 3)
    toks = np.random.default_rng(0).integers(0, conf["vocab_size"], 48)
    with jax.default_matmul_precision("highest"):
        ref = reference.forward_logits(conf, w, jax.numpy.asarray(toks))
        f32 = jax.tree.map(lambda a: a.astype(np.float32), params)
        got, _ = forward(f32, jax.numpy.asarray(toks)[None], cfg,
                         kernels=False)
    got = np.asarray(got[0, :, :conf["vocab_size"]])
    np.testing.assert_allclose(got, np.asarray(ref), atol=2e-3, rtol=2e-3)
