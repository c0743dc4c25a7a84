"""Model FLOP/s utilization of the decode steps: the model FLOPs of the
traced steps (each active slot's token through all layers and the head, and
its attention over the tokens it holds) over the decode program's device
time times the chip's peak, in percent."""

from bench.lib.flops import decode_step_flops
from bench.lib.trace import program_time

DECODE = "jit_decode_paged"


def read(d):
    t = program_time(d["trace"], DECODE)
    if not d.get("steps") or t <= 0:
        return None
    flops = sum(decode_step_flops(d["model"], held) for held in d["steps"])
    return 100.0 * flops / (t * d["peaks"]["flops_per_s"])
