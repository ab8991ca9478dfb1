"""Seconds per PageRank job in the program's `engine.dispatch` span: the
call into the device program until it returns (trace, lower, cache load,
launch). Engine mixes only."""
from bench.program import span_seconds_per_job


def read(obs):
    if obs["kind"] != "engine":
        return None
    return span_seconds_per_job(obs, {"engine.dispatch"})
