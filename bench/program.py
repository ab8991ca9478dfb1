"""The program's own spans and counters (`repro.obs`), per job of the window.

The program records them while the profiler runs, on the host clock the
window's `Record`s use, so a span or instant belongs to the job whose
`[t0_ns, t1_ns]` holds it. Readers take them from `obs["program"]` where
it is given (`{"spans": [...], "instants": [...]}`, as tests do), else from
`repro.obs` in this process. A program without `repro.obs` has nothing to
read: the readers then return None.
"""
from __future__ import annotations

import importlib


def events(obs: dict):
    """`{"spans", "instants"}` of the program, or None where it has none."""
    if "program" in obs:
        return obs["program"]
    try:
        program_obs = importlib.import_module("repro.obs")
    except ImportError:
        return None
    return {"spans": program_obs.spans(), "instants": program_obs.instants()}


def span_seconds_per_job(obs: dict, names) -> float | None:
    """Mean over the window's jobs of the seconds spent in spans named
    `names` inside each job. None where a job holds none of them."""
    ev = events(obs)
    if ev is None or not obs["records"]:
        return None
    per_job = []
    for r in obs["records"]:
        inside = [t1 - t0 for name, t0, t1, *_ in ev["spans"]
                  if name in names and r.t0_ns <= t0 and t1 <= r.t1_ns]
        if not inside:
            return None
        per_job.append(sum(inside) / 1e9)
    return sum(per_job) / len(per_job)


def count_per_job(obs: dict, name: str) -> float | None:
    """Mean over the window's jobs of the counter `name`'s increments
    inside each job (0 where none)."""
    ev = events(obs)
    if ev is None or not obs["records"]:
        return None
    per_job = [sum(n for iname, t, n in ev["instants"]
                   if iname == name and r.t0_ns <= t <= r.t1_ns)
               for r in obs["records"]]
    return sum(per_job) / len(per_job)
