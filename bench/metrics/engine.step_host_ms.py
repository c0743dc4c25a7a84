"""Host milliseconds on a decode step's critical path, from the engine's
own spans: each ``batcher.step`` less its ``batcher.admit`` children
(admission inside the step) and its ``batcher.readback`` child (the host
waiting on the device), averaged over the steps wholly inside the traced
window. What is left is the host's own work between two decode programs:
the uploads and dispatch, the per-slot bookkeeping."""

from bench.lib import scopes


def read(d):
    r = scopes.for_reading(d)
    steps = r and r["spans"].get("batcher.step")
    if not steps:
        return None
    kids = (r["spans"].get("batcher.admit", [])
            + r["spans"].get("batcher.readback", []))
    host = sum(e - s - scopes.inside(kids, s, e) for s, e in steps)
    return host / len(steps) / 1e6
