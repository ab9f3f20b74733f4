"""Two shares from the counts the engine's dispatch annotations carry
(``utils/profiling.annotate``), read from ``host_phases``' plain form:
bursts dispatched while the device still had work queued (``ahead`` on
``engine.decode_burst``), and the columns of the padded prefill waves that
held no token (``new_tokens`` against ``padded_tokens`` on
``engine.prefill_batch``).  A trace without the stat reads as None."""

from __future__ import annotations

from benchmarks.readers import host_phases


def burst_ahead_share(plain: dict):
    ahead = [h[3]["ahead"] for h in plain["host"]
             if h[0] == "engine.decode_burst" and "ahead" in h[3]]
    return 100.0 * sum(1 for a in ahead if a) / len(ahead) if ahead else None


def prefill_pad_share(plain: dict):
    waves = [h[3] for h in plain["host"]
             if h[0] == "engine.prefill_batch" and h[3].get("padded_tokens")]
    padded = sum(w["padded_tokens"] for w in waves)
    return 100.0 * (1.0 - sum(w.get("new_tokens", 0) for w in waves) / padded) if padded else None


def read(ctx, what):
    plain = host_phases.phases_of(ctx)
    if plain is None:
        return None
    return {"burst_ahead_share": burst_ahead_share,
            "prefill_pad_share": prefill_pad_share}[what](plain)
