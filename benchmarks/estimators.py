"""From request records to the judged numbers.  Pure functions on plain
records, so a test can hold them to hand-made inputs."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (q in 0..100) of a non-empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def in_window(records: list, phase: str = "window") -> list:
    return [r for r in records if r.get("phase") == phase]


def ttft_ms(rec: dict) -> float | None:
    """First streamed token received minus the time the request was due."""
    if rec.get("first_t") is None:
        return None
    return (rec["first_t"] - rec["due_t"]) * 1e3


def tpot_ms(rec: dict) -> float | None:
    """(last token - first token) / (tokens - 1)."""
    n = rec.get("n_tokens", 0)
    if n < 2 or rec.get("first_t") is None:
        return None
    return (rec["last_t"] - rec["first_t"]) / (n - 1) * 1e3


def tokens_by_arrival(records: list, t_open: float, t_close: float) -> int:
    """Output tokens whose arrival at the client lies in [t_open, t_close),
    whatever request they belong to and whenever it was sent: nothing is
    cut at a window edge but the tokens themselves."""
    return sum(1 for r in records for t in r.get("token_ts", ()) if t_open <= t < t_close)


def stratified_mean(records: list, ratio: dict, key: str = "kind",
                    value: str = "seconds") -> float | None:
    """Mean of each stratum, then the strata's means weighted by the mix's
    fixed ``ratio`` (so the result does not swing with how many of each
    kind happened to finish).  None when a stratum with weight is empty."""
    total_w = float(sum(ratio.values()))
    out = 0.0
    for kind, w in ratio.items():
        vals = [r[value] for r in records if r.get(key) == kind and r.get(value) is not None]
        if not vals:
            if w:
                return None
            continue
        out += (w / total_w) * (sum(vals) / len(vals))
    return out


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, as the driver reads it (statistics.quantiles, n=4)."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)
