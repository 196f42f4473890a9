"""The forest traversal's share of its roofline, in percent.

The least time the chip could take for the window's traversals is the
larger of their operations over the peak rate and their bytes over the peak
memory bandwidth (``bench/peaks.py``); the operations and bytes are counted
from the node visits of the plain reference descent (``bench/reference.py``,
``traversal_cost``).  The share is that least time over the device-busy time
inside the benchmark's spans around the calls.
"""

from bench.peaks import least_time_s

SPAN = "bench.table"


def read(run):
    s = (run.reduced or {}).get("spans", {}).get(SPAN)
    cost = run.stats.get("forest_cost")
    if not s or not cost or not s["device_s"] or run.peaks is None:
        return None
    t, _ = least_time_s(cost["ops"], cost["bytes"], run.peaks)
    return t / s["device_s"] * 100.0
