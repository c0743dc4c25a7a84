"""Host milliseconds per engine step: the wall time of the harness's
``bench.step`` spans (``ContinuousBatcher.step``: admission check, decode
dispatch, sampling read-back, bookkeeping) less the device time of the
decode program in the traced window, per step."""

from bench.lib.trace import program_time

DECODE = "jit_decode_paged"


def read(d):
    n = d.get("span_count", {}).get("step", 0)
    if not n:
        return None
    dev = program_time(d["trace"], DECODE)
    return (d["span_total_s"]["step"] - dev) / n * 1e3
