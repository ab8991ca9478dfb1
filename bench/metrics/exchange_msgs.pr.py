"""`exchange_msgs` of the PageRank cells, which report `job_s.pr`."""
from bench.metrics.exchange_msgs import read  # noqa: F401
