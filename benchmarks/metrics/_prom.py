"""Helpers for readers of the server's ``/metrics`` text: deltas of
counters and histograms between the window's two scrapes."""

from __future__ import annotations

import re


def delta(obs: dict, name: str):
    a, b = obs["prom0"].get(name), obs["prom1"].get(name)
    if a is None or b is None:
        return None
    return b - a


def histogram_quantile(obs: dict, name: str, q: float):
    """Quantile q of the observations a histogram took between the two
    scrapes, by linear interpolation inside the bucket (seconds)."""
    edges = []
    for key in obs["prom1"]:
        m = re.fullmatch(re.escape(name) + r'_bucket\{le="([^"]+)"\}', key)
        if m and m.group(1) != "+Inf":
            edges.append((float(m.group(1)), key))
    total = delta(obs, name + "_count")
    if not edges or not total:
        return None
    rank, lo_edge, lo_cum = q * total, 0.0, 0.0
    for edge, key in sorted(edges):
        cum = obs["prom1"][key] - obs["prom0"].get(key, 0.0)
        if cum >= rank:
            span = cum - lo_cum
            return lo_edge + (edge - lo_edge) * ((rank - lo_cum) / span if span else 1.0)
        lo_edge, lo_cum = edge, cum
    return sorted(edges)[-1][0]
