"""Milliseconds to admit one request, from the engine's own
``batcher.admit`` spans (one per request: its prefill dispatch, page
reservation and scatter into the pool, and its first token on the host),
averaged over the admissions wholly inside the traced window."""

from bench.lib import scopes


def read(d):
    r = scopes.for_reading(d)
    admits = r and r["spans"].get("batcher.admit")
    if not admits:
        return None
    return sum(e - s for s, e in admits) / len(admits) / 1e6
