"""Exchange and delivery (``exec/iteration.py``, ``core/runtime.py``):
combined messages crossing the partition cut per job, the mean of
``counters.net_messages`` over the window's jobs."""


def read(run: dict):
    jobs = run["jobs"]
    return sum(j["net_messages"] for j in jobs) / len(jobs) if jobs else None
