"""The slot scheduler's ledger of passes, between the window's two
scrapes: deltas of ``tpufw_serve_pass_seconds_total{pass}``,
``tpufw_serve_pass_steps_total{pass}`` and
``tpufw_serve_pass_starved_seconds_total{pass, phase}`` by kind of pass
(``decode``: a decode chunk with nothing enqueued ahead of it in the
pass; ``decode_behind_prefill``: one behind a prefill program or insert
of the same pass; ``prefill_only``: no decode chunk). Passes run back to
back while anything is in service, so their seconds are the time in
service; starved seconds are those of them in which nothing the
scheduler's thread had enqueued was left on the device. A program
without the counters (every commit before they were added) gives None."""

from __future__ import annotations

import re

SECONDS = "tpufw_serve_pass_seconds_total"
STEPS = "tpufw_serve_pass_steps_total"
STARVED = "tpufw_serve_pass_starved_seconds_total"
DECODES = ("decode", "decode_behind_prefill")

_KIND = re.compile(r'\bpass="([^"]+)"')


def by_kind(obs: dict, family: str):
    """{kind of pass: the family's growth in the window}, summed over
    any other label; None where the program exposes no such series."""
    out = {}
    for key, after in obs["prom1"].items():
        if not key.startswith(family + "{"):
            continue
        m = _KIND.search(key)
        if m:
            grown = after - obs["prom0"].get(key, 0.0)
            out[m.group(1)] = out.get(m.group(1), 0.0) + grown
    return out or None


def decodes(obs: dict, family: str):
    """(in ``decode`` passes, in ``decode_behind_prefill`` passes) of the
    family's growth, or None without the series."""
    kinds = by_kind(obs, family)
    if kinds is None:
        return None
    return tuple(kinds.get(k, 0.0) for k in DECODES)
