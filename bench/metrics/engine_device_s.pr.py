"""`engine_device_s` of the PageRank cells, which report `job_s.pr`."""
from bench.metrics.engine_device_s import read  # noqa: F401
