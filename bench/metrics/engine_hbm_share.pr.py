"""`engine_hbm_share` of the PageRank cells, which report `job_s.pr`."""
from bench.metrics.engine_hbm_share import read  # noqa: F401
