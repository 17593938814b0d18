"""One general traffic generator, driven by the mix's data file.

Open loop: arrival times come from the mix and never depend on the server. The
shapes are those of ``tpufw/load/genload.py`` (Poisson or two-state MMPP
arrivals, clipped-Pareto lengths, a pool of shared prefixes, multi-turn
sessions), but every draw is stratified and the mix, not the seed, fixes
the schedule: when each request is due and how long its prompt and its
answer are (``shape_seed`` in the mix's file orders them). The seed draws
what is IN the requests, the token ids, and in the runner the weights. So
every seed offers the same work at the same moments, and two seeds differ
as two runs of one seed do. Measured on the chip (PERF.md, PR 23): letting
the seed reorder one window's 22 requests moved tokens/s by 13% and the
TPOT tail by 30% between seeds, far more than any change a PR would claim.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import random

import numpy as np


@dataclasses.dataclass(frozen=True)
class Offered:
    """One request: when it is due (seconds from the window's opening,
    negative during the ramp), its prompt and how many tokens it asks."""

    t: float
    prompt: tuple
    max_new: int


def pareto_lengths(n: int, base: int, alpha: float, cap: int, quantum: int = 1) -> list:
    """The n stratified quantiles of min(cap, base * Pareto(alpha)), each
    rounded to the nearest multiple of ``quantum`` (at least one)."""
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        x = min(float(cap), base * (1.0 - u) ** (-1.0 / alpha))
        q = max(quantum, int(round(x / quantum)) * quantum)
        out.append(min(q, cap))
    return out


def exp_gaps(n: int, total: float) -> list:
    """n stratified quantiles of an exponential, scaled to sum to total."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = total / sum(raw)
    return [g * scale for g in raw]


def _poisson_times(rate: float, span: float, rng: random.Random) -> list:
    n = int(round(rate * span))
    if n <= 0:
        return []
    gaps = exp_gaps(n, span)
    rng.shuffle(gaps)
    # Start half a gap in, so the last arrival lies inside the span.
    t, out = -gaps[0] / 2.0, []
    for g in gaps:
        t += g
        out.append(t)
    return out


def _mmpp_times(arr: dict, span: float, rng: random.Random) -> list:
    """Two-state Markov-modulated Poisson arrivals with mean rate
    ``rate_rps``: calm and burst states of equal mean dwell, the burst
    state ``burst_factor`` times as fast. Dwells and gaps are stratified
    per state, so each state's total time and count are fixed."""
    b, dwell = float(arr["burst_factor"]), float(arr["dwell_s"])
    calm_rate = 2.0 * arr["rate_rps"] / (1.0 + b)
    n_seg = max(1, int(round(span / (2.0 * dwell))))
    half = span / 2.0
    segments = {}
    for state in ("calm", "burst"):
        d = exp_gaps(n_seg, half)
        rng.shuffle(d)
        segments[state] = d
    first = "calm" if rng.random() < 0.5 else "burst"
    order = [first, "burst" if first == "calm" else "calm"]
    # Real-time start of each state's i-th segment.
    starts = {"calm": [], "burst": []}
    t = 0.0
    for i in range(n_seg):
        for state in order:
            starts[state].append(t)
            t += segments[state][i]
    out = []
    for state, rate in (("calm", calm_rate), ("burst", calm_rate * b)):
        own = _poisson_times(rate, half, rng)  # in this state's own time
        edges = np.cumsum([0.0] + segments[state])
        for tau in own:
            i = min(int(np.searchsorted(edges, tau, side="right")) - 1, n_seg - 1)
            out.append(starts[state][i] + (tau - edges[i]))
    return sorted(out)


def arrival_times(arr: dict, span: float, rng: random.Random) -> list:
    if arr["process"] == "poisson":
        return _poisson_times(float(arr["rate_rps"]), span, rng)
    if arr["process"] == "mmpp":
        return _mmpp_times(arr, span, rng)
    raise ValueError(f"unknown arrival process {arr['process']!r}")


def _phase(mix: dict, span: float, shape: random.Random, tok: np.random.Generator,
           vocab: int, prefixes: list) -> list:
    times = arrival_times(mix["arrivals"], span, shape)
    n = len(times)
    if n == 0:
        return []
    p, o = mix["prompt"], mix["output"]
    plens = pareto_lengths(n, p["base"], p["alpha"], p["cap"], p.get("quantum", 1))
    olens = pareto_lengths(n, o["base"], o["alpha"], o["cap"], o.get("quantum", 1))
    shape.shuffle(plens)
    shape.shuffle(olens)
    prompts = [tok.integers(1, vocab, size=m).tolist() for m in plens]
    pre = mix.get("prefix", {})
    if pre.get("ratio", 0) > 0 and prefixes:
        chosen = list(range(n))
        shape.shuffle(chosen)
        for i in chosen[: int(round(pre["ratio"] * n))]:
            shared = prefixes[shape.randrange(len(prefixes))][: plens[i] - 1]
            prompts[i][: len(shared)] = shared
    ses = mix.get("sessions", {})
    if ses.get("ratio", 0) > 0 and ses.get("turns", 1) > 1:
        # Later turns open with the whole prompt of the turn before them.
        members = sorted(shape.sample(range(n), int(round(ses["ratio"] * n))))
        turns = int(ses["turns"])
        for s in range(0, len(members), turns):
            group = members[s : s + turns]
            for prev, cur in zip(group, group[1:]):
                grown = prompts[prev] + prompts[cur][: int(ses.get("growth", 16))]
                prompts[cur] = grown[: p["cap"]]
    return [Offered(round(t, 6), tuple(pr), m) for t, pr, m in zip(times, prompts, olens)]


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> list:
    """Every request of one run, ramp first."""
    shape = random.Random(int(mix.get("shape_seed", 0)))
    tok = np.random.Generator(np.random.PCG64(int(seed)))
    pre = mix.get("prefix", {})
    prefixes = [
        tok.integers(1, vocab, size=int(pre["length"])).tolist()
        for _ in range(int(pre.get("count", 0)))
    ] if pre.get("ratio", 0) > 0 else []
    ramp_s = float(mix.get("ramp_s", 0))
    ramp = _phase(mix, ramp_s, shape, tok, vocab, prefixes) if ramp_s > 0 else []
    ramp = [dataclasses.replace(r, t=round(r.t - ramp_s, 6)) for r in ramp]
    return ramp + _phase(mix, float(seconds), shape, tok, vocab, prefixes)


def schedule_digest(reqs) -> str:
    h = hashlib.sha256()
    for r in reqs:
        h.update(json.dumps([r.t, list(r.prompt), r.max_new]).encode())
    return h.hexdigest()[:16]
