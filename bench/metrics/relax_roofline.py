"""Kernels (relaxation): the share of the HBM roofline that the traced
jobs' messages reach over the device's busy time.

Bytes are what the messages must move: every in-memory and network
message (``counters.mem_messages + counters.net_messages``, totals over
the cell's chips) times the bytes of its value, its arc's weight and its
neighbour's index.  ELL slots, bins and padding are never counted, so the
same work reads the same whatever implements the relaxation.  Time is the
union of device busy intervals over the traced window, averaged over the
cell's chips; the peak is one chip's HBM bandwidth times the chips.
"""


def read(run: dict):
    trace = run["trace"]
    if trace is None or trace["busy_s"] <= 0:
        return None
    msgs = sum(j["mem_messages"] + j["net_messages"] for j in run["jobs"])
    if msgs <= 0:
        return None
    peak = run["peaks"]["hbm_bytes_per_s"] * run.get("chips", 1)
    need_s = msgs * run["message_bytes"] / peak
    return 100.0 * need_s / trace["busy_s"]
