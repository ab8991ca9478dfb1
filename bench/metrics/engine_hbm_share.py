"""Share of peak HBM bandwidth: the job's lower-bound bytes (`bench.work`)
over peak bytes per second times `engine_device_s`. Engine mixes only."""
from bench.trace import mean_busiest


def read(obs):
    if obs["kind"] != "engine":
        return None
    seconds = mean_busiest(obs["trace"]["jobs"], "busy_s")
    if seconds <= 0:
        return None
    return 100.0 * obs["lower_bytes"] / (obs["peaks"]["hbm_bytes_per_s"] * seconds)
