"""Device idle share of the traced window of partition jobs: 1 - busy / window."""
from bench.trace import idle_percent


def read(obs):
    if obs["kind"] != "partition":
        return None
    return idle_percent(obs["trace"])
