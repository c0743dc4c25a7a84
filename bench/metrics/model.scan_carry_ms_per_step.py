"""Device milliseconds per decode step that the layer scan spends on
itself: the self time of the decode program's ops whose innermost named
scope is ``layer_scan`` (``models/decoder._stack``), which holds every op
of the scan outside the layer's body (``layer_body``): slicing each
block's parameters and pool out of the stack, writing the pool back, and
the copies the compiler makes of them. Over the decode programs in the
traced window."""

from bench.lib import scopes

DECODE = "jit_decode_paged"


def read(d):
    r = scopes.for_reading(d)
    sc = (r or {}).get("scopes_s", {}).get(DECODE, {})
    n = (r or {}).get("programs_n", {}).get(DECODE)
    if not n or "layer_scan" not in sc:
        return None
    return sc["layer_scan"] / n * 1e3
