"""The paged decode kernel's share of its roofline: the least time its work
needs on this chip (``bench/kernels/paged_decode.py``, summed over every
layer of every traced step) over its device time in the trace, in
percent."""

from bench.lib.trace import op_time


def read(d):
    k = d["kernel"]("paged_decode")
    t = op_time(d["trace"], k.PROGRAM, k.OP)
    if not d.get("steps") or t <= 0:
        return None
    m = d["model"]
    least = 0.0
    for held in d["steps"]:
        flops, nbytes = k.work(m, held)
        least += m["num_layers"] * k.least_time(flops, nbytes, d["peaks"])
    return 100.0 * least / t
