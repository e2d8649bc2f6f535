"""Serving launcher.

Modes:
- host (default): run the real Bullet runtime (concurrent engines, paged KV
  pool, SLO scheduler) over a reduced variant on the local devices.
- replay: online trace replay on the real runtime — a generate_trace
  workload (capped at --requests, lengths fitted to the reduced context)
  is released into the engine by arrival timestamp (wall or virtual
  clock), with streaming, preemption, and per-request SLO accounting;
  prints the same ServingMetrics row format as --mode sim. For an
  apples-to-apples replay-vs-sim comparison on one identical trace, run
  `python -m benchmarks.run replay_vs_sim`.
- sim: estimator-driven discrete-event comparison vs baselines at scale.
- simulate-fleet: event-driven multi-replica cluster simulation — N
  simulated Bullet instances behind a pluggable router replay a
  multi-tenant closed-loop trace (docs/SIMULATOR.md); --fault-plan specs
  become replica outage windows.
- dryrun: lower+compile prefill/decode for the production mesh.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
      --requests 16
  PYTHONPATH=src python -m repro.launch.serve --mode replay \
      --dataset sharegpt --rate 8 --duration 5
  PYTHONPATH=src python -m repro.launch.serve --mode sim --dataset sharegpt \
      --rate 40
  PYTHONPATH=src python -m repro.launch.serve --mode simulate-fleet \
      --replicas 4 --router prefix-affinity --sessions 2000 --rate 120
"""

import argparse
import os
import sys


def _resilience_kwargs(args):
    """Build the faults=/guard= engine kwargs from the CLI flags.

    Returns an empty dict when no resilience flag was given so the
    engine keeps its NULL_FAULTS / guard-free defaults.
    """
    kw = {}
    if args.fault_plan:
        from repro.resilience import FaultInjector, FaultPlan
        kw["faults"] = FaultInjector(FaultPlan.from_json(args.fault_plan))
    if (args.deadline_ttft is not None or args.deadline_total is not None
            or args.max_queue is not None):
        from repro.resilience import GuardConfig, SLOGuard
        gkw = {}
        if args.deadline_ttft is not None:
            gkw["deadline_ttft_s"] = args.deadline_ttft
        if args.deadline_total is not None:
            gkw["deadline_total_s"] = args.deadline_total
        if args.max_queue is not None:
            gkw["max_queue"] = args.max_queue
        kw["guard"] = SLOGuard(GuardConfig(**gkw))
    return kw


def _write_obs_outputs(args, server) -> None:
    """Shared --trace-out / --metrics-out export for host and replay."""
    if args.trace_out:
        server.obs.write_trace(args.trace_out)
        print(f"wrote Chrome trace ({len(server.obs.trace)} cycles) to "
              f"{args.trace_out} (open in https://ui.perfetto.dev)")
    if args.metrics_out:
        server.obs.write_metrics(args.metrics_out, server=server)
        print(f"wrote metrics snapshot to {args.metrics_out}")


def _model(args):
    """(cfg, params, dtype) to serve: the reduced variant in float32, or
    with --published the published config with bf16 params and KV pool."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.models import init_params

    cfg = get_config(args.arch)
    dtype = jnp.bfloat16 if args.published else jnp.float32
    if not args.published:
        cfg = cfg.reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0), dtype), dtype


def _host(args):
    import numpy as np
    from repro.core.engine import BulletServer
    from repro.obs import Observability
    from repro.obs.report import run_report
    from repro.serving.request import Request, SLO

    from repro.core.config import build_server_config

    cfg, params, dtype = _model(args)
    res = _resilience_kwargs(args)
    server = BulletServer(cfg, params, config=build_server_config(
        args, slo=SLO(args.slo_ttft, args.slo_tpot), obs=Observability(),
        faults=res.get("faults"), guard=res.get("guard"), dtype=dtype))
    rng = np.random.default_rng(args.seed)
    reqs = []
    for rid in range(args.requests):
        plen = int(rng.integers(4, args.max_len // 3))
        out = int(rng.integers(2, args.max_len // 4))
        r = Request(rid=rid, arrival=0.0, prompt_len=plen, output_len=out)
        server.submit(r, rng.integers(0, cfg.vocab_size, plen))
        reqs.append(r)
    outputs = server.run()
    done = sum(len(v) for v in outputs.values())
    print(run_report(server, header=(
        f"served {len(outputs)} requests, {done} tokens total")))
    _write_obs_outputs(args, server)


def _replay(args):
    import jax
    from repro.core.engine import BulletServer
    from repro.core.estimator import HardwareSpec, PerfEstimator
    from repro.core.profiler import SurrogateMachine
    from repro.obs import Observability
    from repro.obs.report import run_report
    from repro.serving.frontend import (OnlineFrontend, VirtualClock,
                                        WallClock, estimator_cycle_cost,
                                        oracle_cycle_cost)
    from repro.serving.request import WORKLOAD_SLOS
    from repro.serving.workload import fit_trace_to_context, generate_trace

    cfg, params, dtype = _model(args)
    # replay scores against the dataset's Table-2 SLO, same as --mode sim,
    # so the two rows are directly comparable (--slo-* applies to host mode)
    slo = WORKLOAD_SLOS[args.dataset]
    # off the chip: the same modelled hardware as --mode sim (the sim
    # additionally calibrates via profiling and runs the full-size model on
    # the unclamped trace — benchmarks/replay_vs_sim.py holds both sides
    # identical). On a TPU the engine prices the devices it runs on.
    from repro.core.config import build_server_config

    est = (None if jax.default_backend() == "tpu"
           else PerfEstimator(HardwareSpec(n_chips=args.chips)))
    res = _resilience_kwargs(args)
    tenancy = None
    if args.tenants > 0:
        from repro.serving.tenancy import (TenancyConfig, TenancyController,
                                           make_apps)
        tenancy = TenancyController(
            make_apps(args.tenants, rate_limit=args.rate_limit),
            TenancyConfig(credit=args.credit))
    server = BulletServer(cfg, params, config=build_server_config(
        args, slo=slo, est=est, refit=not args.no_refit,
        obs=Observability(), tenancy=tenancy,
        faults=res.get("faults"), guard=res.get("guard"), dtype=dtype))
    trace = fit_trace_to_context(
        generate_trace(args.dataset, args.rate, args.duration,
                       seed=args.seed, max_requests=args.requests),
        args.max_len)
    if args.clock == "virtual":
        clock = VirtualClock()
        # --oracle replays against the surrogate machine's hidden-truth
        # timings instead of the engine's own estimate: predicted-vs-actual
        # error becomes non-trivial and the OnlineRefitter closes the loop
        cost = (oracle_cycle_cost(SurrogateMachine(server.est.hw,
                                                   seed=args.seed))
                if args.oracle else estimator_cycle_cost)
        fe = OnlineFrontend(server, clock, cycle_cost=cost)
    else:
        fe = OnlineFrontend(server, WallClock(speed=args.time_scale))
    if args.stream:
        fe.on_token = lambda r, tok, t: print(
            f"  [{t:8.3f}s] rid={r.rid} tok#{r.generated}={tok}")
    if tenancy is not None:
        # multi-tenant replay: a Zipf-skewed closed-loop interaction
        # trace instead of the flat open-loop one (docs/MULTITENANCY.md)
        from repro.serving.tenancy import generate_tenant_interactions
        sessions = generate_tenant_interactions(
            list(tenancy.apps.values()),
            n_sessions=max(args.requests, 1), rate_s=args.rate,
            seed=args.seed)
        fe.submit_interactions(sessions, cfg.vocab_size, seed=args.seed)
        n_submitted = len(sessions)
        kind = "sessions"
    else:
        fe.submit_trace(trace, cfg.vocab_size, seed=args.seed)
        n_submitted = len(trace)
        kind = "requests"
    m = fe.run()
    if fe.truncated:
        print("WARNING: replay hit max_cycles with unfinished requests; "
              "metrics cover the completed subset only")
    print(run_report(server, metrics=m, header=(
        f"replay({args.clock}) {args.dataset} rate={args.rate}/s "
        f"dur={args.duration}s -> {n_submitted} {kind}")))
    if tenancy is not None:
        tenancy.check_oit()
        for app_id, st in sorted(tenancy.stats.items()):
            print(f"  tenant {tenancy._label(app_id):8s} "
                  f"credit={tenancy.credit(app_id):.2f} "
                  f"admitted={st.admitted} throttled={st.throttled} "
                  f"finished={st.finished} goodput={st.goodput}")
    _write_obs_outputs(args, server)


def _sim(args):
    from repro.configs import get_config
    from repro.core.estimator import HardwareSpec, PerfEstimator, fit_params
    from repro.core.profiler import SurrogateMachine, run_profiling
    from repro.core.simulate import SimConfig, ServingSimulator
    from repro.serving.request import WORKLOAD_SLOS
    from repro.serving.workload import generate_trace

    cfg = get_config(args.arch)
    hw = HardwareSpec(n_chips=args.chips)
    samples = run_profiling(cfg, hw, max_sl=4096, max_bs=32, max_cl=4096)
    est = PerfEstimator(hw, fit_params(samples, cfg, hw, iters=30))
    slo = WORKLOAD_SLOS[args.dataset]
    for system in args.systems.split(","):
        trace = generate_trace(args.dataset, args.rate, args.duration,
                               seed=args.seed)
        s = ServingSimulator(SimConfig(model=cfg, hw=hw, slo=slo), est,
                             SurrogateMachine(hw, seed=7), system)
        m = s.run(trace)
        print(f"{system:16s} {m.row()}")


def _fleet(args):
    from repro.configs import get_config
    from repro.core.estimator import HardwareSpec, PerfEstimator, fit_params
    from repro.core.profiler import run_profiling
    from repro.core.scheduler import SchedulerConfig
    from repro.core.simulate import SimConfig
    from repro.resilience import FaultPlan
    from repro.serving.request import WORKLOAD_SLOS
    from repro.serving.tenancy import generate_fleet_interactions
    from repro.sim import ClusterConfig, ClusterSimulator, tail_point

    arch = "llama3.1-8b" if args.arch == "qwen3-1.7b" else args.arch
    cfg = get_config(arch)
    hw = HardwareSpec(n_chips=args.chips)
    samples = run_profiling(cfg, hw, max_sl=4096, max_bs=32, max_cl=4096)
    est = PerfEstimator(hw, fit_params(samples, cfg, hw, iters=30))
    slo = WORKLOAD_SLOS[args.dataset]
    work = generate_fleet_interactions(args.sessions, args.rate,
                                       seed=args.seed)
    faults = (FaultPlan.from_json(args.fault_plan)
              if args.fault_plan else None)
    # fleet-scale fidelity/speed knobs, same as benchmarks/capacity_plan.py
    cc = ClusterConfig(
        sim=SimConfig(model=cfg, hw=hw, slo=slo,
                      scheduler=SchedulerConfig(layer_group=8),
                      sched_every=4, refit_interval=512,
                      sched_pending_cap=64),
        n_replicas=args.replicas, router=args.router, faults=faults,
        seed=args.seed)
    res = ClusterSimulator(cc, est).run(work)
    pt = tail_point(res.requests, slo)
    print(f"fleet {args.replicas}x{arch} router={args.router} "
          f"{len(res.requests)} requests ({len(work)} sessions) "
          f"@ {args.rate:.0f} req/s")
    print(f"  {res.metrics.row()}")
    print(f"  attainment={pt['attainment']:.3f} "
          f"p99_norm_ttft={pt['p99_norm_ttft_ms']:.1f}ms "
          f"p99_tpot={pt['p99_tpot_ms']:.2f}ms "
          f"slo_holds={pt['holds']} rerouted={res.rerouted} "
          f"cancelled_no_replica={res.cancelled_no_replica}")
    for i, (cycles, refits, reused) in enumerate(res.replica_stats):
        print(f"  replica {i}: cycles={cycles} refits={refits} "
              f"reused_prefill_tokens={reused}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("host", "replay", "sim",
                                       "simulate-fleet", "dryrun"),
                    default="host")
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--slo-ttft", type=float, default=3.0)
    ap.add_argument("--slo-tpot", type=float, default=150.0)
    ap.add_argument("--dataset", default="sharegpt",
                    choices=("sharegpt", "azure-code", "arxiv-summary"))
    ap.add_argument("--rate", type=float, default=40.0)
    ap.add_argument("--duration", type=float, default=30.0)
    ap.add_argument("--chips", type=int, default=2,
                    help="chips of the modelled v5e slice (sim, "
                         "simulate-fleet, and replay off a TPU); on a TPU "
                         "the server prices the devices it runs on")
    ap.add_argument("--published", action="store_true",
                    help="host/replay: serve the published config of "
                         "--arch with bf16 params and KV pool instead of "
                         "the reduced float32 variant")
    ap.add_argument("--systems",
                    default="bullet,chunked-1024,chunked-2048,naive")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=4,
                    help="fleet size for --mode simulate-fleet: number of "
                         "simulated Bullet replicas behind the router")
    ap.add_argument("--router", default="prefix-affinity",
                    help="cluster routing policy (simulate-fleet mode): "
                         "round-robin, least-kv, prefix-affinity, or "
                         "tenant-aware (docs/SIMULATOR.md)")
    ap.add_argument("--sessions", type=int, default=2000, metavar="N",
                    help="closed-loop turn budget for the simulate-fleet "
                         "multi-tenant trace (sessions are drawn until "
                         "their turns total at least N)")
    ap.add_argument("--clock", choices=("virtual", "wall"), default="virtual",
                    help="replay clock: deterministic virtual time or "
                         "(scaled) wall time")
    ap.add_argument("--time-scale", type=float, default=1.0,
                    help="wall-clock replay speedup (trace seconds per "
                         "wall second)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they stream back (replay mode)")
    ap.add_argument("--partition", choices=("tile", "chip", "auto"),
                    default="tile",
                    help="partition granularity (docs/PARTITIONS.md): tile "
                         "= fused spatial sharing on every chip; chip = "
                         "disjoint prefill/decode sub-meshes with KV "
                         "handoff (needs >= 2 devices); auto = per-task "
                         "combined-table argmin")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page in the paged pool")
    ap.add_argument("--share-prefix", action="store_true",
                    help="ref-counted shared-prefix KV page reuse: "
                         "requests whose prompt matches resident pages "
                         "map them read-only instead of re-prefilling "
                         "(paged pool, tile partition only; "
                         "docs/KV_SHARING.md)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the engine's per-cycle Chrome trace-event "
                         "JSON here (host/replay modes; open in Perfetto "
                         "— docs/OBSERVABILITY.md)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a Prometheus-style metrics snapshot here "
                         "at the end of the run (host/replay modes)")
    ap.add_argument("--deadline-ttft", type=float, default=None,
                    metavar="SECONDS",
                    help="cancel a request whose first token has not "
                         "streamed by this trace-time age (SLOGuard; "
                         "docs/RESILIENCE.md)")
    ap.add_argument("--deadline-total", type=float, default=None,
                    metavar="SECONDS",
                    help="cancel a request still unfinished at this "
                         "trace-time age, freeing its KV pages")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the pending queue; the frontend retries "
                         "rejected submissions, then sheds "
                         "(admission backpressure)")
    ap.add_argument("--fault-plan", default=None, metavar="JSON",
                    help="inject a seeded deterministic fault plan: a "
                         "JSON file path or inline JSON object "
                         "(schema in docs/RESILIENCE.md)")
    ap.add_argument("--tenants", type=int, default=0, metavar="N",
                    help="multi-tenant replay: N apps with Zipf-skewed "
                         "traffic over a 50k-user id space, gated by the "
                         "tenant admission layer "
                         "(docs/MULTITENANCY.md; replay mode)")
    ap.add_argument("--credit", action="store_true",
                    help="credit-biased admission order and preemption-"
                         "victim choice (per-tenant SLO-violation / "
                         "tail-latency history; needs --tenants)")
    ap.add_argument("--rate-limit", type=int, default=0, metavar="N",
                    help="per-tenant sliding-window budget of new "
                         "interactions per second (0 = unlimited); "
                         "mid-conversation turns are never throttled")
    ap.add_argument("--no-refit", action="store_true",
                    help="pin the estimator's offline params (disable the "
                         "online refit loop; see docs/TUNING.md)")
    ap.add_argument("--oracle", action="store_true",
                    help="virtual replay advances on the hidden-truth "
                         "surrogate timings instead of the engine's own "
                         "estimate (demonstrates the refit loop)")
    args = ap.parse_args()
    if args.credit and args.tenants <= 0:
        ap.error("--credit biases the tenant admission layer; "
                 "needs --tenants N")
    if args.tenants > 0 and args.mode != "replay":
        ap.error("--tenants drives the multi-tenant interaction replay; "
                 "use --mode replay")
    if args.oracle and args.clock != "virtual":
        ap.error("--oracle replays on surrogate-truth timings, which only "
                 "the virtual clock can advance on; use --clock virtual")
    if args.mode == "dryrun":
        from subprocess import run
        code = 0
        for shape in ("prefill_32k", "decode_32k"):
            # a CPU harness (512 virtual devices): pinned to the CPU so it
            # never contends for a chip this host may hold
            code |= run([sys.executable, "-m", "repro.launch.dryrun",
                         "--arch", args.arch, "--shape", shape],
                        env={**os.environ, "JAX_PLATFORMS": "cpu"}).returncode
        sys.exit(code)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.mode == "sim":
        args.arch = "llama3.1-8b" if args.arch == "qwen3-1.7b" else args.arch
        _sim(args)
    elif args.mode == "simulate-fleet":
        _fleet(args)
    elif args.mode == "replay":
        _replay(args)
    else:
        _host(args)


if __name__ == "__main__":
    main()
