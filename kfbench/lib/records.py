"""What the serving metrics read from a run's per-request records."""

from __future__ import annotations


def counted(facts):
    """The open loop's requests that were due inside the window."""
    return [r for r in facts["serve"]["requests"] if r["in_window"]]


def ttfts(facts):
    """Seconds from when each counted request was due to its first
    token (only requests that got one: a failed one counts in
    ``failed``)."""
    return [r["token_t"][0] - r["due"] for r in counted(facts)
            if r["token_t"]]


def itl_gaps(facts):
    """Every gap between successive tokens of one request whose later
    token fell inside the window, whichever request it belongs to."""
    s = facts["serve"]
    gaps = []
    for r in s["requests"]:
        t = r["token_t"]
        gaps += [b - a for a, b in zip(t, t[1:]) if s["t0"] < b <= s["t_end"]]
    return gaps
