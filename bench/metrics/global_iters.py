"""Executor layer (``exec/driver.py:run_engine``): global iterations per
job, the mean of the engine's ``counters.iterations`` over the window."""


def read(run: dict):
    jobs = run["jobs"]
    return sum(j["iterations"] for j in jobs) / len(jobs) if jobs else None
