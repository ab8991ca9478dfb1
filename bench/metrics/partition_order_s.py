"""Seconds per partition job in the paper's degree-sum order (the program's
`partition.order` span: the host sort and the gathers by it). Partition
mixes only."""
from bench.program import span_seconds_per_job


def read(obs):
    if obs["kind"] != "partition":
        return None
    return span_seconds_per_job(obs, {"partition.order"})
