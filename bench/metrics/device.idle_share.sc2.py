"""Share of the traced window in which no operation ran on the device, in
percent (replica cells)."""


def read(d):
    t = d["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
