"""The least work an engine job must do, counted from the graph data alone.

It counts what any implementation of the job has to move: each real edge
of the job's graph read once (two 4-byte endpoint ids, and a 4-byte weight
where the program reads weights) and each covered vertex's value read and
written once (4 bytes each way). It never reads iteration counts, block
sizes or padding, so it stays the same whatever implements the job.
"""
from __future__ import annotations

import numpy as np


def engine_job_bytes(src, dst, num_vertices: int, *, symmetrize: bool, weighted: bool) -> int:
    edges = int(np.asarray(src).shape[0]) * (2 if symmetrize else 1)
    covered = int(np.count_nonzero(
        np.bincount(src, minlength=num_vertices) + np.bincount(dst, minlength=num_vertices)))
    return edges * (8 + (4 if weighted else 0)) + covered * 4 * 2
