"""Host seconds per partition job before the commit kernel can start: the
program's `partition.validate`, `partition.order` and `partition.upload`
spans. Partition mixes only."""
from bench.program import span_seconds_per_job


def read(obs):
    if obs["kind"] != "partition":
        return None
    return span_seconds_per_job(
        obs, {"partition.validate", "partition.order", "partition.upload"})
