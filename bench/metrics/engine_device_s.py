"""Engine device seconds per job: busy time of the busiest chip inside each
traced job's host span, mean over the traced jobs. Engine mixes only."""
from bench.trace import mean_busiest


def read(obs):
    if obs["kind"] != "engine":
        return None
    return mean_busiest(obs["trace"]["jobs"], "busy_s")
