"""The one traffic generator: every mix is a JSON file of parameters.

A mix says whether its loop is open (arrivals on a schedule: Poisson,
conditioned on the window's count) or closed (one client per KV slot, each sending its next request when
the last one ends), and how prompt and output lengths are drawn.

The *set* of sizes and arrival times is drawn from the mix's own
``base_seed``, so every run of a cell does the same work; ``--seed``
changes the prompt tokens, the weights and, unless the mix says
``"order": "fixed"``, which request gets which sizes. An open loop's
queue makes the order part of the work: a mix fixes it where it must
be the same for every seed. Lengths are clamped into one KV slot as
``repro.serving.workload.fit_trace_to_context`` does: the prompt to at
most half the slot, the output to what is left.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np


@dataclass
class Req:
    """One request as the benchmark sends it."""
    rid: int
    due: float                 # seconds after the clock starts
    prompt: np.ndarray         # int32 token ids
    output_len: int
    client: int = -1           # closed loop: the client that sends it
    counted: bool = True       # open loop: due inside the window


@dataclass
class Traffic:
    loop: str                  # "open" | "closed"
    spec: dict
    #: open loop: every request, in due order (those past the window
    #: keep the load up while the window's requests drain)
    requests: List[Req] = field(default_factory=list)
    #: closed loop: each client's requests, in the order it sends them
    clients: List[List[Req]] = field(default_factory=list)
    lead_in_s: float = 0.0

    def all_requests(self) -> List[Req]:
        if self.loop == "open":
            return list(self.requests)
        return [r for c in self.clients for r in c]


def sample_lengths(dist: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """Lengths from ``{"dist": "lognormal", "log_mean", "log_std", "lo",
    "hi"}`` (truncated as ``LengthDist.sample`` does) or ``{"dist":
    "uniform", "lo", "hi"}`` (inclusive)."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = rng.lognormal(dist["log_mean"], dist["log_std"], size=n)
        return np.clip(x.astype(np.int64), dist["lo"], dist["hi"])
    if kind == "uniform":
        return rng.integers(dist["lo"], dist["hi"] + 1, size=n)
    raise ValueError(f"unknown length distribution {kind!r}")


def fit_to_slot(prompt: int, output: int, slot_tokens: int):
    """``fit_trace_to_context``'s clamp onto one KV slot."""
    prompt = max(4, min(prompt, slot_tokens // 2))
    output = max(2, min(output, slot_tokens - prompt - 1))
    return prompt, output


def _tokens(rng: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, n, dtype=np.int32)


def generate(spec: dict, *, seed: int, seconds: float, slots: int,
             slot_tokens: int, vocab: int) -> Traffic:
    """The requests of one run: the mix ``spec`` for ``seconds`` of
    window, on a server of ``slots`` slots of ``slot_tokens`` tokens."""
    base = np.random.default_rng(spec["base_seed"])
    mine = np.random.default_rng(seed)
    if spec["loop"] == "open":
        return _open(spec, base, mine, seconds, slot_tokens, vocab)
    if spec["loop"] == "closed":
        return _closed(spec, base, mine, seconds, slots, slot_tokens, vocab)
    raise ValueError(f"unknown loop {spec['loop']!r}")


def _open(spec, base, mine, seconds, slot_tokens, vocab) -> Traffic:
    """Poisson arrivals at ``rate_req_s``, conditioned on their count:
    ``rate x seconds`` requests due at uniform times in the window (the
    order statistics of a Poisson process given its count), then
    ``rate x drain_s`` more after it to keep the load up while the
    window's requests finish. Sizes are permuted by the seed within each
    part, unless the mix's ``order`` is ``"fixed"``."""
    rate = spec["rate_req_s"]
    inside = int(round(rate * seconds))
    after = int(round(rate * spec["drain_s"]))
    due = np.concatenate([
        np.sort(base.uniform(0.0, seconds, inside)),
        seconds + np.sort(base.uniform(0.0, spec["drain_s"], after))])
    n = len(due)
    p = sample_lengths(spec["prompt"], base, n)
    o = sample_lengths(spec["output"], base, n)
    if spec.get("order", "permuted") == "fixed":
        order = np.arange(n)
    else:
        order = np.concatenate([mine.permutation(inside),
                                inside + mine.permutation(n - inside)])
    reqs = []
    for i in range(n):
        pl, ol = fit_to_slot(int(p[order[i]]), int(o[order[i]]), slot_tokens)
        reqs.append(Req(i, float(due[i]), _tokens(mine, pl, vocab), ol,
                        counted=i < inside))
    return Traffic("open", spec, requests=reqs)


def _closed(spec, base, mine, seconds, slots, slot_tokens, vocab) -> Traffic:
    """``slots`` clients. Each one's first request stands for a request
    already running when the window opens: drawn like the others and cut
    at a uniform point, its prompt carries the part already generated and
    its output is the rest, so the window opens with contexts and
    completions spread as in steady state (the initial prefills finish
    within ``lead_in_s``)."""
    per_client = spec["requests_per_client"]
    n = slots * per_client
    p = sample_lengths(spec["prompt"], base, n)
    o = sample_lengths(spec["output"], base, n)
    cut = base.uniform(0.0, 1.0, size=slots)
    # the first ``slots`` draws, cut, are the running requests; the rest
    # are the later ones: each set is permuted over the clients by the seed
    first = mine.permutation(slots)
    later = slots + mine.permutation(n - slots)
    clients: List[List[Req]] = []
    rid = 0
    for c in range(slots):
        picks = [first[c]] + list(later[c * (per_client - 1):
                                        (c + 1) * (per_client - 1)])
        reqs = []
        for k, i in enumerate(picks):
            pl, ol = int(p[i]), int(o[i])
            if k == 0:
                done = int(math.floor(cut[i] * (ol - 1)))
                pl, ol = pl + done, ol - done
            pl = min(pl, slot_tokens - 2)
            ol = max(1, min(ol, slot_tokens - pl - 1))
            reqs.append(Req(rid, 0.0, _tokens(mine, pl, vocab), ol, client=c))
            rid += 1
        clients.append(reqs)
    return Traffic("closed", spec, clients=clients,
                   lead_in_s=float(spec["lead_in_s"]))


def reachable_prompt_lengths(spec: dict, slot_tokens: int) -> Dict[str, int]:
    """The longest prompt a mix can send (the warm-up covers every padded
    prefill length up to it)."""
    hi_p, hi_o = spec["prompt"]["hi"], spec["output"]["hi"]
    if spec["loop"] == "open":
        return {"max_prompt": fit_to_slot(hi_p, hi_o, slot_tokens)[0],
                "min_prompt": fit_to_slot(spec["prompt"]["lo"], 2,
                                          slot_tokens)[0]}
    return {"max_prompt": min(hi_p + hi_o - 2, slot_tokens - 2),
            "min_prompt": spec["prompt"]["lo"]}


def summary(traffic: Traffic) -> Optional[str]:
    reqs = traffic.all_requests()
    if not reqs:
        return None
    pl = [len(r.prompt) for r in reqs]
    ol = [r.output_len for r in reqs]
    return (f"{traffic.loop} loop: {len(reqs)} requests, prompt tokens "
            f"mean {np.mean(pl):.0f} max {max(pl)}, output tokens mean "
            f"{np.mean(ol):.0f} max {max(ol)}")
