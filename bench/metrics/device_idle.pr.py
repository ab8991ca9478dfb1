"""Device idle share of the traced window of PageRank jobs (the cells that
report `job_s.pr`): 1 - busy / window, busy averaged over the chips used."""
from bench.trace import idle_percent


def read(obs):
    if obs["kind"] != "engine":
        return None
    return idle_percent(obs["trace"])
