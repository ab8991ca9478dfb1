"""Device seconds per local relaxation pass: each traced job's busy seconds
on the busiest chip over that job's `BSPStats.relax_passes`, mean over the
jobs. None where the trace's jobs and the window's records do not pair up,
or the program does not count passes. Engine mixes only."""


def read(obs):
    if obs["kind"] != "engine":
        return None
    jobs, records = obs["trace"]["jobs"], obs["records"]
    if not jobs or len(jobs) != len(records):
        return None
    per_job = []
    for job, r in zip(jobs, records):
        passes = getattr(r.stats, "relax_passes", 0)
        if not passes:
            return None
        per_job.append(max(job["busy_s"]) / passes)
    return sum(per_job) / len(per_job)
