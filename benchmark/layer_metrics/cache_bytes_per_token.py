"""Bytes of cache a live context token costs at the fullest moment of the
window: the manager's ``cache_bytes_peak`` (pages and slots held by live
sequences, every kind of state) over ``tokens_at_peak`` (the live context
tokens at that moment), from the ``kv`` part of ``engine.snapshot()`` when the
window closed. The block's warm-up starts both anew, so the peak is the
window's. Nothing where the manager keeps no such counters. Source:
program_counter."""


def read(run):
    kv = (run.window.get("snap1") or {}).get("kv") or {}
    peak, tokens = kv.get("cache_bytes_peak"), kv.get("tokens_at_peak")
    return peak / tokens if peak and tokens else None
