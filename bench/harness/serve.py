"""Build the server, warm every shape the cell can reach, and drive the
measured window through ``OnlineFrontend`` -> ``BulletServer.step`` on a
wall clock.

What the window records (host clock, ``time.perf_counter``):

- per engine step, at the ``on_cycle`` hook: when the step began and
  ended, and the ``EngineStats`` delta, decode batch and KV blocks in
  use — the step's kind is read from the delta;
- per token, at the ``on_token`` hook: when the host received it.

Host spans (``jax.profiler.TraceAnnotation``) mark the benchmark's own
calls — ``bench.release``, ``bench.step`` and ``bench.on_cycle`` — so a
device trace can say what the host did in each idle gap.
"""

from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np


class WindowClosed(Exception):
    """Raised from the ``on_cycle`` hook to end ``OnlineFrontend.run``."""


@dataclass
class Step:
    start: float               # host time its release began
    end: float                 # host time the step returned (on_cycle)
    decode: int                # decode iterations it ran
    prefill: int               # prefill layer groups it ran
    batch: int                 # slots the decode iteration ran
    contexts: tuple            # their live contexts
    kv_used: int               # pool blocks in use after it
    prefill_lengths: tuple     # prompt lengths of the batch its group ran
    queued: int = 0            # requests released and not yet admitted
    #: the whole ``EngineStats`` delta of the step, by field name
    stats: Dict[str, int] = field(default_factory=dict)


@dataclass
class Window:
    """Everything the window recorded, in host seconds."""
    t0: float                  # the clock's zero (requests are due from it)
    start: float               # window start
    end: float                 # window end
    steps: List[Step] = field(default_factory=list)
    token_times: Dict[int, List[float]] = field(default_factory=dict)
    admit_times: Dict[int, float] = field(default_factory=dict)
    outputs: Dict[int, List[int]] = field(default_factory=dict)
    #: programs lowered while serving (lead-in, window and drain)
    compiles_in_window: int = 0
    trace_span: Optional[tuple] = None   # (start, stop) of the trace
    n_blocks: int = 0


def server_config(conf: dict, spec: dict):
    import jax.numpy as jnp
    from repro.core.config import (CacheConfig, ControlConfig, ExecConfig,
                                   ServerConfig)
    from repro.core.scheduler import SchedulerConfig
    from repro.serving.request import SLO
    lim, srv, pool = spec["limits"], spec["server"], conf["pool"]
    return ServerConfig(
        slo=SLO(norm_ttft_ms=lim["norm_ttft_ms_per_token"],
                tpot_ms=lim["tpot_ms"]),
        dtype=jnp.bfloat16, max_slots=pool["slots"],
        max_len=pool["slot_tokens"],
        max_prefill_batch=srv["max_prefill_batch"],
        cache=CacheConfig(paged=True, page_size=pool["page_size"]),
        execution=ExecConfig(fused=srv["fused"]),
        control=ControlConfig(sched=SchedulerConfig(
            unit_quantum=conf["scheduler"]["unit_quantum"])))


def build_server(cfg, params, conf: dict, spec: dict):
    from repro.core.engine import BulletServer
    return BulletServer(cfg, params, config=server_config(conf, spec))


def _request(rid: int, prompt_len: int, output_len: int, due: float = 0.0):
    from repro.serving.request import Request
    return Request(rid=rid, arrival=due, prompt_len=prompt_len,
                   output_len=output_len)


def warm_plan(spec: dict, conf: dict, span: dict, pad) -> dict:
    """Every prefill batch size x padded length and every decode page
    bucket the mix can reach. ``span`` is the generator's
    ``reachable_prompt_lengths``; ``pad`` is the program's own
    ``prefill_length`` (a multiple of the kernels' 128-token block on a
    TPU, the exact length elsewhere)."""
    pool = conf["pool"]
    lengths = sorted({pad(n) for n in range(span["min_prompt"],
                                            span["max_prompt"] + 1)})
    batches = list(range(1, spec["server"]["max_prefill_batch"] + 1))
    max_blocks = -(-pool["slot_tokens"] // pool["page_size"])
    buckets, b = [], 1
    while b < max_blocks:
        buckets.append(b)
        b <<= 1
    buckets.append(max_blocks)
    return {"prefill": [(b, n) for n in lengths for b in batches],
            "decode_buckets": buckets, "max_prompt": span["max_prompt"]}


def warm(server, plan: dict, vocab: int, rng: np.random.Generator,
         page_size: int) -> int:
    """Drive each shape of ``plan`` through the server's own path: one
    request per decode page bucket (so a decode step that does not fit in
    memory fails before the long prefill warm-up), then prefill batches
    of each size and padded length (one output token, so no decode).
    Returns the engine steps run."""
    rid, steps = 10 ** 9, 0

    def drain():
        nonlocal steps
        while not server.idle:
            server.step(time.perf_counter())
            steps += 1

    for bucket in plan["decode_buckets"]:
        # the first decode context is prompt + 1: land it in the bucket's
        # (bucket/2, bucket] pages, or decode up into them
        lo = (bucket // 2) * page_size
        prompt = min(bucket * page_size - 1, plan["max_prompt"])
        out = 2 if prompt + 1 > lo else lo - prompt + 2
        server.submit(_request(rid, prompt, out),
                      rng.integers(0, vocab, prompt, dtype=np.int32))
        rid += 1
        drain()
    for b, n in plan["prefill"]:
        for _ in range(b):
            server.submit(_request(rid, n, 1),
                          rng.integers(0, vocab, n, dtype=np.int32))
            rid += 1
        drain()
    server.pool.check_invariants()
    return steps


def run_window(server, traffic, *, seconds: float, drain_s: float,
               counter, trace_dir: Optional[str] = None,
               trace_seconds: float = 0.0) -> Window:
    """Serve ``traffic``: open loop, release each request at its due time
    and close when every request due in the window has finished (or
    ``drain_s`` past the window); closed loop, each client sends its next
    request when the last one ends, and the window is the ``seconds``
    after the lead-in."""
    import jax
    from repro.serving.frontend import OnlineFrontend, WallClock
    from repro.serving.request import Phase

    closed = traffic.loop == "closed"
    lead = traffic.lead_in_s if closed else 0.0
    rec = Window(t0=0.0, start=0.0, end=0.0, n_blocks=server.pool.n_blocks)
    counted = {r.rid for r in traffic.all_requests() if r.counted}
    unadmitted: Dict[int, object] = {}
    pending_counted = set(counted) if not closed else set()
    next_of: Dict[int, object] = {}
    if closed:
        for reqs in traffic.clients:
            for a, b in zip(reqs, reqs[1:]):
                next_of[a.rid] = b
    fe = OnlineFrontend(server, WallClock())
    ann = jax.profiler.TraceAnnotation
    prev = {"stats": _stats(server), "t_start": None, "lengths": ()}
    tracing = {"on": False}
    engine_step, release = server.step, fe._release

    def step(now):
        before = server.ptask
        with ann("bench.step"):
            did = engine_step(now)
        task = before or server.ptask
        prev["lengths"] = (tuple(r.prompt_len for r in task.batch)
                           if task is not None else ())
        return did

    def release_annotated(now):
        prev["t_start"] = time.perf_counter()
        with ann("bench.release"):
            release(now)

    server.step = step
    fe._release = release_annotated

    def on_token(req, token, now):
        t = time.perf_counter()
        rec.token_times.setdefault(req.rid, []).append(t)
        rec.outputs.setdefault(req.rid, []).append(int(token))
        done = (req.generated >= req.output_len
                or req.prompt_len + req.generated >= server.max_len)
        if done:
            pending_counted.discard(req.rid)
            nxt = next_of.get(req.rid)
            if nxt is not None:
                r = _request(nxt.rid, len(nxt.prompt), nxt.output_len,
                             due=fe.clock.now())
                fe.submit(r, nxt.prompt)
            elif closed and time.perf_counter() < rec.end:
                raise RuntimeError(
                    "a client ran out of requests inside the window: "
                    "raise the mix's requests_per_client")

    fe.on_token = on_token

    def on_cycle(srv, now):
        t = time.perf_counter()
        with ann("bench.on_cycle"):
            st = _stats(srv)
            d = {k: st[k] - prev["stats"][k] for k in st}
            prev["stats"] = st
            last = srv.last_decode
            ran = d["decode_iterations"] > 0 and last is not None
            rec.steps.append(Step(
                start=prev["t_start"],
                end=t, decode=d["decode_iterations"],
                prefill=d["prefill_cycles"],
                batch=last.batch if ran else 0,
                contexts=tuple(last.contexts) if ran else (),
                kv_used=srv.pool.allocated_blocks,
                prefill_lengths=prev["lengths"] if d["prefill_cycles"]
                else (), queued=len(srv.pending), stats=d))
            now_c = fe.clock.now()
            for rid in [rid for rid, r in unadmitted.items()
                        if r.arrival <= now_c and r.phase != Phase.QUEUED]:
                # admitted at the top of this step (the ``admit`` span)
                rec.admit_times[rid] = rec.steps[-1].start
                del unadmitted[rid]
            if trace_dir and not tracing["on"] and trace_seconds > 0 \
                    and t >= rec.end - trace_seconds and t < rec.end:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0      # the bench spans only
                opts.enable_hlo_proto = False
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing["on"] = True
                rec.trace_span = (time.perf_counter(), None)
            if tracing["on"] and t >= rec.end:
                jax.block_until_ready(srv.cache)
                stop = time.perf_counter()
                jax.profiler.stop_trace()
                tracing["on"] = False
                rec.trace_span = (rec.trace_span[0], stop)
            if closed and t >= rec.end:
                raise WindowClosed
            if not closed and t >= rec.end and (
                    not pending_counted or t >= rec.end + drain_s):
                raise WindowClosed

    fe.on_cycle = on_cycle
    if closed:
        for reqs in traffic.clients:
            r = reqs[0]
            fe.submit(_request(r.rid, len(r.prompt), r.output_len), r.prompt)
    else:
        for r in traffic.requests:
            req = _request(r.rid, len(r.prompt), r.output_len, r.due)
            unadmitted[r.rid] = req
            fe.submit(req, r.prompt)
    fe.clock = WallClock()
    rec.t0 = time.perf_counter() - fe.clock.now()
    rec.start = rec.t0 + lead
    rec.end = rec.start + seconds
    n0 = counter.lowerings
    try:
        fe.run(max_cycles=10 ** 9)
    except WindowClosed:
        pass
    rec.compiles_in_window = counter.lowerings - n0
    if tracing["on"]:
        jax.profiler.stop_trace()
    del server.step, fe._release
    return rec



def _stats(server) -> dict:
    return {k: v for k, v in dataclasses.asdict(server.stats).items()
            if isinstance(v, int)}
