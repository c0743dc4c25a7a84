"""Share of the traced window's wall time spent handing requests to the
engine (``bench.admit`` spans: ``submit`` + ``admit``, which prefills and
scatters into the paged pool), in percent."""


def read(d):
    t = d.get("span_total_s", {}).get("admit")
    if not t:
        return None
    return 100.0 * t / d["trace"]["window_s"]
