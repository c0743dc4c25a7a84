"""Model FLOP/s utilization of prefill: the model FLOPs of the traced
prompts at their true lengths (not their padded buckets) over the prefill
programs' device time times the chip's peak, in percent."""

from bench.lib.flops import prefill_flops
from bench.lib.trace import program_time

PREFILL = "jit_prefill"


def read(d):
    t = program_time(d["trace"], PREFILL)
    if not d.get("prefills") or t <= 0:
        return None
    flops = sum(prefill_flops(d["model"], T) for T in d["prefills"])
    return 100.0 * flops / (t * d["peaks"]["flops_per_s"])
