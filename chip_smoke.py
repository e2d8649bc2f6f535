"""Smoke run of the served path on a TPU: the quickest proof that the
system still starts on the chip. It is not a benchmark; the seconds it
prints only make a run that went wrong visible.

    python chip_smoke.py              # one chip: kernel parity, then
                                      # Qwen3-1.7B served at published widths
    python chip_smoke.py --chips 4    # four chips: the chip-granular
                                      # partition against the one-chip path

Everything runs in this one process, which holds the chip. The script
exits nonzero and prints no result unless ``jax.devices()[0]`` is a TPU.
Its last line on stdout is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

ARCH = "qwen3-1.7b"
#: served traffic: prompt lengths (tokens) and one output length, all
#: arriving together so the second prefill batch co-resides with the
#: first batch's decode (fused cycles). Two padded prefill lengths at most
#: keep the cold compile count inside the run's 1200-second budget.
PROMPT_LENS = (128, 256, 384, 512, 640, 768, 896, 960)
OUTPUT_LEN = 32
#: every reference prompt is padded to this length (one compile)
REF_LEN = 1024
#: the KV pool of the one-chip phase (114,688 B/token in bf16: 1.9 GB)
MAX_SLOTS, MAX_LEN, PAGE_SIZE = 8, 2048, 16
#: kernel parity tolerance: bf16-level, as tests/test_kernels.py uses
KERNEL_TOL = 2e-2
#: a first token that differs from the float32 reference is exempt only
#: where the reference's top-two logits lie within this many logits: about
#: five times the largest bf16-vs-float32 last-token logit difference of
#: this config cut to 8 layers (0.053, XLA attention on a CPU host)
NEAR_TIE = 0.25


class SmokeFailure(Exception):
    """A phase check failed; the run exits nonzero."""


def _check(ok: bool, what: str) -> None:
    print(f"  [{'pass' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


class CompileCounter:
    """Counts backend compilations through JAX's monitoring events."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.n = 0
        self.seconds = 0.0
        self._event = BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self._event:
            self.n += 1
            self.seconds += duration


# ---------------------------------------------------------------------------
# phase 1: the main-path kernels against their oracles, on the chip
# ---------------------------------------------------------------------------

def kernel_parity(cfg, seed: int) -> None:
    """flash, paged decode and fused (bullet, paged) attention at the
    config's head widths, float32 inputs, against kernels/ref.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import (bullet_attention_paged_op, flash_attention_op,
                               paged_decode_attention_op)
    from repro.kernels import ref as R

    h, kh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // kh
    sp, bd, ps, n_b = 512, 8, PAGE_SIZE, 16
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))

    def rand(shape):
        # bf16-representable float32 values: products are exact in one
        # MXU pass, so the error left is the kernels' own
        x = jax.random.normal(next(keys), shape, jnp.float32)
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    q, k, v = rand((1, sp, h, d)), rand((1, sp, kh, d)), rand((1, sp, kh, d))
    qd = rand((bd, 1, h, d))
    n_pages = bd * n_b
    kpg, vpg = rand((n_pages + 1, ps, kh, d)), rand((n_pages + 1, ps, kh, d))
    rng = np.random.default_rng(seed)
    bt = jnp.asarray(rng.permutation(n_pages).reshape(bd, n_b), jnp.int32)
    pos = jnp.asarray(rng.integers(1, n_b * ps, bd), jnp.int32)

    with jax.default_matmul_precision("highest"):
        def flash_ref(q, k, v):
            qf = q[0].transpose(1, 0, 2)
            kx = jnp.repeat(k[0].transpose(1, 0, 2), g, 0)
            vx = jnp.repeat(v[0].transpose(1, 0, 2), g, 0)
            return R.flash_attention_ref(qf, kx, vx).transpose(1, 0, 2)[None]
        ref_p = flash_ref(q, k, v)
        ref_d = R.paged_decode_attention_ref(
            qd[:, 0].reshape(bd, kh, g, d), kpg, vpg, bt, pos
        ).reshape(bd, 1, h, d)

    def err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32) - b)))

    out_p = flash_attention_op(q, k, v, interpret=False)
    out_d = paged_decode_attention_op(qd, kpg, vpg, bt, pos, interpret=False)
    fp, fd = bullet_attention_paged_op(q, k, v, qd, kpg, vpg, bt, pos,
                                       interpret=False)
    for name, e in (("flash_attention", err(out_p, ref_p)),
                    ("paged_decode_attention", err(out_d, ref_d)),
                    ("bullet_attention_paged/prefill", err(fp, ref_p)),
                    ("bullet_attention_paged/decode", err(fd, ref_d))):
        _check(e <= KERNEL_TOL,
               f"{name}: max|kernel - ref| = {e!r} (tolerance {KERNEL_TOL})")


# ---------------------------------------------------------------------------
# phase 2: Qwen3-1.7B served at published widths in bf16
# ---------------------------------------------------------------------------

def make_requests(cfg, seed: int, prompt_lens=PROMPT_LENS,
                  output_len=OUTPUT_LEN):
    """(request, prompt tokens) pairs, all arriving at t=0."""
    import numpy as np
    from repro.serving.request import Request
    rng = np.random.default_rng(seed)
    return [(Request(rid=i, arrival=0.0, prompt_len=n, output_len=output_len),
             rng.integers(0, cfg.vocab_size, n, dtype=np.int32))
            for i, n in enumerate(prompt_lens)]


def reference_first_tokens(cfg, params, prompts, ref_len=REF_LEN):
    """Per prompt: (argmax, top-1 minus top-2 logit) of a float32 forward
    that keeps attention on the XLA ops, at full matmul precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import forward

    @jax.jit
    def last_logits(params, tokens, last):
        f32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        with jax.default_matmul_precision("highest"):
            logits, _ = forward(f32, tokens, cfg, kernels=False)
        row = logits[0, last]
        top2 = jax.lax.top_k(row, 2)[0]
        return jnp.argmax(row), top2[0] - top2[1]

    out = []
    for toks in prompts:
        padded = np.zeros((1, ref_len), np.int32)
        padded[0, :len(toks)] = toks
        tok, gap = last_logits(params, jnp.asarray(padded),
                               jnp.int32(len(toks) - 1))
        out.append((int(tok), float(gap)))
    return out


class StepRecorder:
    """Records the abstract arguments of the last call of the engine's
    decode and fused step functions, so the check can lower exactly the
    programs the run executed."""

    NAMES = ("_decode_iteration", "_fused_step")

    def __init__(self):
        from repro.core import engine
        self.engine = engine
        self.real = {n: getattr(engine, n) for n in self.NAMES}
        self.last = {}

    def __enter__(self):
        for name, fn in self.real.items():
            setattr(self.engine, name, self._wrap(name, fn))
        return self

    def __exit__(self, *exc):
        for name, fn in self.real.items():
            setattr(self.engine, name, fn)

    def _wrap(self, name, fn):
        import jax

        def abstract(a):
            if isinstance(a, jax.Array):
                return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                            sharding=a.sharding)
            return a

        def call(*args, **kw):
            self.last[name] = (jax.tree.map(abstract, args), kw)
            return fn(*args, **kw)
        return call

    def lowered_text(self, name) -> str:
        args, kw = self.last[name]
        return self.real[name].lower(*args, **kw).as_text()


def serve(cfg, params, requests, *, config):
    """Serve ``requests`` through OnlineFrontend on a wall clock; returns
    (server, wall seconds)."""
    from repro.core.engine import BulletServer
    from repro.serving.frontend import OnlineFrontend, WallClock

    server = BulletServer(cfg, params, config=config)
    fe = OnlineFrontend(server, WallClock())
    for req, toks in requests:
        fe.submit(req, toks)
    t0 = time.perf_counter()
    fe.run()
    wall = time.perf_counter() - t0
    _check(not fe.truncated, "the replay drained (not truncated)")
    return server, wall


def serve_config(dtype, **kw):
    """The smoke run's ServerConfig. The partition table is pinned to its
    one both-phases split (4+4 of 8 units) and the decode pause is off, so
    the fused cycles compile once per layer group, not once per group and
    split."""
    from repro.core.config import (CacheConfig, ControlConfig, ExecConfig,
                                   ServerConfig)
    from repro.core.scheduler import SchedulerConfig
    from repro.serving.request import SLO
    sched = SchedulerConfig(unit_quantum=4, min_decode_units=4,
                            min_prefill_units=4, max_decode_pause_cycles=0)
    execution = ExecConfig(**{k: kw.pop(k) for k in ("fused", "partition",
                                                     "devices") if k in kw})
    return ServerConfig(slo=SLO(norm_ttft_ms=3.0, tpot_ms=150.0),
                        dtype=dtype, cache=CacheConfig(page_size=PAGE_SIZE),
                        control=ControlConfig(sched=sched),
                        execution=execution, **kw)


def check_outputs(server, requests) -> None:
    from repro.serving.request import Phase
    done = [r for r, _ in requests
            if r.phase == Phase.FINISHED
            and len(server.outputs.get(r.rid, ())) == r.output_len]
    _check(len(done) == len(requests),
           f"{len(done)}/{len(requests)} requests finished with "
           f"{OUTPUT_LEN} tokens")
    clean = server.pool.available_blocks == server.pool.n_blocks
    _check(clean, f"KV pool clean: {clean}")


def serving_phase(cfg, params, requests, counter) -> int:
    """Serve the requests on one chip and check the result; returns the
    tokens served."""
    import jax.numpy as jnp

    t0 = time.perf_counter()
    ref = reference_first_tokens(cfg, params, [t for _, t in requests])
    print(f"  float32 reference: {time.perf_counter() - t0:.1f} s",
          flush=True)
    n0 = counter.n
    with StepRecorder() as rec:
        server, wall = serve(cfg, params, requests, config=serve_config(
            jnp.bfloat16, max_slots=MAX_SLOTS, max_len=MAX_LEN))
    tokens = sum(len(v) for v in server.outputs.values())
    print(f"  served {len(requests)} requests, {tokens} tokens in "
          f"{wall:.1f} s wall, {counter.n - n0} compilations "
          f"(smoke run, not a benchmark)", flush=True)
    print(f"  stats: fused_cycles={server.stats.fused_cycles} "
          f"decode_iterations={server.stats.decode_iterations} "
          f"prefill_cycles={server.stats.prefill_cycles} "
          f"paused_cycles={server.stats.paused_cycles}", flush=True)
    check_outputs(server, requests)
    _check(server.stats.fused_cycles > 0,
           f"fused_cycles = {server.stats.fused_cycles} > 0")
    for name in StepRecorder.NAMES:
        ran = name in rec.last
        has = ran and "tpu_custom_call" in rec.lowered_text(name)
        _check(has, f"{name} ran and its lowered text calls the Pallas "
                    "kernels (tpu_custom_call)")
    ties = 0
    for (req, _), (ref_tok, gap) in zip(requests, ref):
        got = server.outputs[req.rid][0]
        line = (f"rid {req.rid} ({req.prompt_len} tokens): first token "
                f"{got}, float32 reference {ref_tok}, top-2 gap {gap!r}")
        if got != ref_tok and gap <= NEAR_TIE:
            ties += 1
            print(f"  [near tie, exempt] {line}", flush=True)
            continue
        _check(got == ref_tok, line)
    print(f"  near ties exempt: {ties} of {len(requests)}", flush=True)
    return tokens


# ---------------------------------------------------------------------------
# --chips 4: the chip-granular partition against the one-chip tile path
# ---------------------------------------------------------------------------

def chip_partition_phase(cfg, params, requests_fn, devices) -> int:
    """Serve the same requests with ``--partition chip`` over all devices,
    then on the one-chip tile path (device 0, serial cycles), and compare.
    Returns the tokens served by the chip-partition run."""
    import jax.numpy as jnp

    # pools sized to the longest request: the chip-granular server keeps a
    # staging pool on the prefill sub-mesh besides the decode pool
    max_len = max(PROMPT_LENS) + OUTPUT_LEN
    chip_reqs = requests_fn()
    chip, wall = serve(cfg, params, chip_reqs, config=serve_config(
        jnp.bfloat16, max_slots=MAX_SLOTS, max_len=max_len,
        partition="chip", devices=tuple(devices)))
    print(f"  chip partition: {wall:.1f} s wall, chip_cycles="
          f"{chip.stats.chip_cycles} handoffs={chip.stats.handoffs}",
          flush=True)
    check_outputs(chip, chip_reqs)
    _check(chip.stats.handoffs > 0, f"handoffs = {chip.stats.handoffs} > 0")

    def devs(tree):
        import jax
        return {d for a in jax.tree.leaves(tree) for d in a.sharding.device_set}
    p_devs, d_devs = devs(chip.cache_p), devs(chip.cache)
    _check(bool(p_devs) and bool(d_devs) and not p_devs & d_devs,
           f"prefill outputs on devices {sorted(d.id for d in p_devs)}, "
           f"decode outputs on {sorted(d.id for d in d_devs)}: disjoint")
    chip_streams = {rid: list(v) for rid, v in chip.outputs.items()}
    tokens = sum(len(v) for v in chip_streams.values())
    del chip
    gc.collect()            # free the chip server's per-mesh copies first

    tile_reqs = requests_fn()
    tile, wall = serve(cfg, params, tile_reqs, config=serve_config(
        jnp.bfloat16, max_slots=MAX_SLOTS, max_len=max_len,
        partition="tile", fused=False, devices=(devices[0],)))
    print(f"  one-chip tile path (device {devices[0].id}, serial): "
          f"{wall:.1f} s wall", flush=True)
    check_outputs(tile, tile_reqs)
    same = tile.outputs == chip_streams
    _check(same, "chip-partition token streams equal the one-chip path's")
    return tokens


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernel parity and Qwen3-1.7B serving on one "
                         "chip; 4: the chip-granular partition phase only")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, prompts and inputs")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]

    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params

    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    kind = devices[0].device_kind
    print(f"chip smoke run (not a benchmark): {kind} x {len(devices)}, "
          f"compile cache {cache_dir}", flush=True)
    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    params = init_params(cfg, jax.random.PRNGKey(args.seed), jnp.bfloat16)
    jax.block_until_ready(params)
    print(f"set-up: {ARCH} ({cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads x {cfg.head_dim}) bf16 "
          f"weights from seed {args.seed} in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    try:
        if args.chips == 4:
            print("phase: chip-granular partition vs one-chip tile path",
                  flush=True)
            tokens = chip_partition_phase(
                cfg, params, lambda: make_requests(cfg, args.seed), devices)
        else:
            print("phase: kernel parity", flush=True)
            kernel_parity(cfg, args.seed)
            print("phase: serving", flush=True)
            tokens = serving_phase(cfg, params,
                                   make_requests(cfg, args.seed), counter)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total: {time.perf_counter() - t0:.1f} s wall, {tokens} tokens "
          f"served, {counter.n} compilations ({counter.seconds:.1f} s "
          "compiling)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
