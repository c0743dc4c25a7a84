"""Share of the fleet program's device time in the traced slice spent in
the tick scan's advance phase (``tick.advance``: hedge cancels, admission
from the queues into free slots, one decode tick per occupied slot), in
percent: the self time of its ops over that of every op of the programs
that hold ``tick.*`` scopes."""

from bench.lib import scopes


def read(d):
    r = scopes.for_reading(d)
    return (r or {}).get("phase_share", {}).get("tick.advance")
