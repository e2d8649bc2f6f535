"""Share, %, of the requests due in the window that met both latency
limits of the mix (TTFT per prompt token and TPOT); an unfinished one
missed them."""


def read(run):
    reqs = run.counted()
    if not reqs:
        return None
    return 100.0 * sum(run.meets_limits(r) for r in reqs) / len(reqs)
