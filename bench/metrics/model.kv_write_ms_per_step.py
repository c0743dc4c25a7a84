"""Device milliseconds per decode step spent writing the step's K/V into
the paged pool and building the attention mask from it: the self time of
the decode program's ops under the ``kv_write`` and ``kv_mask`` named
scopes (``models/attention.attn_decode_paged``), over the decode programs
in the traced window."""

from bench.lib import scopes

DECODE = "jit_decode_paged"


def read(d):
    r = scopes.for_reading(d)
    sc = (r or {}).get("scopes_s", {}).get(DECODE, {})
    n = (r or {}).get("programs_n", {}).get(DECODE)
    if not n or not ({"kv_write", "kv_mask"} & set(sc)):
        return None
    return (sc.get("kv_write", 0.0) + sc.get("kv_mask", 0.0)) / n * 1e3
