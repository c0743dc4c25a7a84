"""The flash-attention kernel's share of its roofline in prefill: the least
time the true prompts' attention needs on this chip
(``bench/kernels/flash_prefill.py``, every layer of every traced prompt)
over its device time in the trace, in percent."""

from bench.lib.trace import op_time


def read(d):
    k = d["kernel"]("flash_prefill")
    t = op_time(d["trace"], k.PROGRAM, k.OP)
    if not d.get("prefills") or t <= 0:
        return None
    m = d["model"]
    least = sum(m["num_layers"] * k.least_time(*k.work(m, T), d["peaks"])
                for T in d["prefills"])
    return 100.0 * least / t
