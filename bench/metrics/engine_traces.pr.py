"""Times per PageRank job that JAX traced an engine program: the program's
`engine.trace` counter, which only a trace increments. Engine mixes only."""
from bench.program import count_per_job


def read(obs):
    if obs["kind"] != "engine":
        return None
    return count_per_job(obs, "engine.trace")
