"""Arithmetic from client-side records to the end-to-end metrics, and from
the reference's per-position readings to the numbers ``correct`` compares."""

from __future__ import annotations

import math
import statistics


def quartile_spread(values) -> float:
    """(Q3 - Q1) / median, quartiles as ``statistics.quantiles(n=4)``."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def tpot_s(chunks, lo: float = -math.inf, hi: float = math.inf, min_tokens: int = 1):
    """Seconds per output token of one request: (last chunk - first chunk)
    over the tokens that arrived after the first chunk, over the chunks
    received in [lo, hi). Tokens arrive in decode chunks, so the gap
    between single tokens is not used. None below ``min_tokens``."""
    inside = [(t, n) for t, n in chunks if lo <= t < hi]
    if len(inside) < 2:
        return None
    later = sum(n for _, n in inside[1:])
    if later < min_tokens:
        return None
    return (inside[-1][0] - inside[0][0]) / later


#: Share of positions that may move for another reason than logit noise:
#: top-k routing is a step function, so where the router itself is near a
#: tie a sound computation may take the other expert and differ by that
#: expert's whole output, at any margin.
MOVED_OTHERWISE = 0.005


def logit_noise(top2, moved) -> float:
    """The program's logit noise, in logits, as the served ids show it. The
    ids are all that leaves the program, but where the reference's two best
    logits lie ``top2`` apart, a program whose logits carry noise of scale
    s serves another token than the reference's best with probability
    Phi(-top2 / s), and one position in 200 may move whatever its margin
    (``MOVED_OTHERWISE``). This is the s that makes the observed pattern
    (which positions ``moved``, at which margins) most likely, found on a
    geometric grid of 2% steps from 1e-4 to 10. It uses every position, not
    only those that moved, so it is far steadier than the mean gap: near
    ties that held count against a large s as much as flips count for it."""
    phi = lambda x: 0.5 * (1.0 + math.erf(x / math.sqrt(2.0)))
    e = MOVED_OTHERWISE
    best_s, best_ll = None, -math.inf
    s = 1e-4
    while s <= 10.0:
        ll = 0.0
        for m, f in zip(top2, moved):
            p = min(max(e + (1.0 - e) * phi(-m / s), 1e-12), 1.0 - 1e-12)
            ll += math.log(p) if f else math.log(1.0 - p)
        if ll > best_ll:
            best_s, best_ll = s, ll
        s *= 1.02
    return best_s


def gap_numbers(gaps, top2, routing_margins, routing_margin: float) -> dict:
    """The numbers ``correct`` compares, from per-position readings of the
    reference: ``gaps`` (how far the served token's reference logit lies
    below the reference's best; 0 where it is the best) and ``top2`` (the
    reference's own margin between its two best). ``logit_noise``, over
    every position, is held against a lower precision. ``gap_max`` and
    ``gap_mean``, over the positions that the reference routed at least
    ``routing_margin`` clear of a tie, are held against a token altered
    where it is produced."""
    kept = [(g, t) for g, t, m in zip(gaps, top2, routing_margins) if m >= routing_margin]
    if not kept:
        raise ValueError("no position left to compare")
    only = [g for g, _ in kept]
    return {
        "gap_max": max(only),
        "gap_mean": sum(only) / len(only),
        "logit_noise": logit_noise(top2, [g > 0 for g in gaps]),
        "tokens": len(kept),
        "left_out": len(gaps) - len(kept),
        "moved_share": sum(g > 0 for g in only) / len(only),
    }


def ttft_limit_ms(limits: dict, n_prompt: int) -> float:
    """The cell's TTFT limit for a prompt of this length: one number per
    length bucket, keyed by the bucket's upper end."""
    for edge in sorted(int(k) for k in limits):
        if n_prompt <= edge:
            return float(limits[str(edge)])
    return float(limits[str(max(int(k) for k in limits))])


def window_stats(records, t0: float, seconds: float, cutoff: float, limits: dict, chips: int) -> dict:
    """Everything the end-to-end metrics are made of. ``records`` are the
    client's; times are on its clock; a request due in [t0, t0+seconds)
    is "in the window". A request that came back an error, or that had no
    first token when the client stopped waiting (``cutoff``: the mix's
    ``drain_s`` after the window at the latest), counts as attempted and
    failed and as missing every limit; its TTFT is the time it had waited
    at cutoff.

    Three counts of tokens. ``offered_tokens``: what the requests due in
    the window ask for. ``tokens_of_due``: what those requests had been
    sent when the window closed, a failed one what it got;
    ``tokens_per_s_per_chip`` is this one over the window's seconds, so
    tokens and seconds are of one span: a stall inside the window lowers
    it, and since each request's count can only grow with the server's
    speed, a faster server never reads lower. The ramp's requests load the
    server and count for nothing. ``tokens_in_window``: the chunks of every
    record, the ramp's too, that arrived inside the window; it was the
    rate's numerator until PR 43, FALLS where a faster server ends the
    ramp's answers before the window opens, and is kept as a count so that
    earlier readings can be compared with a run of today."""
    t1 = t0 + seconds
    due_in = [r for r in records if t0 <= r["due"] < t1]
    tokens_in_window = sum(n for r in records for t, n in r["chunks"] if t0 <= t < t1)
    tokens_of_due = sum(n for r in due_in for t, n in r["chunks"] if t < t1)
    ttft, good, failed = [], 0, 0
    for r in due_in:
        first = r["chunks"][0][0] if r["chunks"] else None
        bad = r["status"] == "error" or first is None
        failed += bad
        wait = ((first if first is not None else cutoff) - r["due"]) * 1e3
        ttft.append(wait)
        if limits and not bad:
            tp = tpot_s(r["chunks"])
            good += wait <= ttft_limit_ms(limits["ttft_ms"], r["n_prompt"]) and (
                tp is None or tp * 1e3 <= limits["tpot_ms"]
            )
    tpots = [
        tp * 1e3
        for r in records
        if (tp := tpot_s(r["chunks"], t0, t1, min_tokens=16)) is not None
    ]

    def open_at(t):
        """Requests due by t and not finished by t: the backlog."""
        return sum(
            1 for r in records
            if r["due"] <= t and not (r["status"] == "ok" and r["done"] is not None and r["done"] <= t)
        )

    out = {
        "backlog_start": open_at(t0),
        "backlog_end": open_at(t1),
        "attempted": len(due_in),
        "failed": failed,
        "offered_tokens": sum(r["max_new"] for r in due_in),
        "tokens_of_due": tokens_of_due,
        "tokens_in_window": tokens_in_window,
        "tokens_per_s_per_chip": tokens_of_due / seconds / chips,
        "n_ttft": len(ttft),
        "n_tpot": len(tpots),
        "late_ms": [(r["sent"] - r["due"]) * 1e3 for r in due_in if r["sent"] is not None],
    }
    # A window holds a dozen requests, so a 95th percentile would be the one
    # worst of them: the median is what such a sample bears, and the worst
    # stands beside it under its own name.
    if ttft:
        out["ttft_p50_ms"] = statistics.median(ttft)
        out["ttft_max_ms"] = max(ttft)
    if tpots:
        out["tpot_p50_ms"] = statistics.median(tpots)
        out["tpot_max_ms"] = max(tpots)
    if limits and due_in:
        out["slo_good_share"] = 100.0 * good / len(due_in)
    return out
