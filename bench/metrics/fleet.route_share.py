"""Share of the fleet program's device time in the traced slice spent in
the tick scan's routing phase (``tick.route``: reroute-ring pops and the
tick's arrivals, each placed by the sequential probing router), in
percent: the self time of its ops over that of every op of the programs
that hold ``tick.*`` scopes."""

from bench.lib import scopes


def read(d):
    r = scopes.for_reading(d)
    return (r or {}).get("phase_share", {}).get("tick.route")
