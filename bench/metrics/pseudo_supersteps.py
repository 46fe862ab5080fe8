"""Local phase (``exec/local_phase.py``): pseudo-supersteps per job, the
sum over partitions of ``counters.pseudo_supersteps``, mean over the
window's jobs."""


def read(run: dict):
    jobs = run["jobs"]
    if not jobs:
        return None
    return sum(j["pseudo_supersteps"] for j in jobs) / len(jobs)
