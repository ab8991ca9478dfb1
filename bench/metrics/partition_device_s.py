"""Partition device seconds per job: busy time of the chip inside each traced
partition job's host span, mean over the traced jobs. Partition mixes only."""
from bench.trace import mean_busiest


def read(obs):
    if obs["kind"] != "partition":
        return None
    return mean_busiest(obs["trace"]["jobs"], "busy_s")
