"""From the profiler's trace to device times.

`load` reads the `.xplane.pb` the JAX profiler writes into rows
`(plane, line, name, start_ns, dur_ns)`: the device planes' op lines and
the host's job spans. `reduce_events` turns rows into what the per-layer
readers use: for each traced job, the busy seconds of every chip inside
the job's host span; for the traced window, the busy seconds
averaged over the chips and the window's length; and the breakdown of the
device ops that took most time and of the longest idle gaps, each named by
the host event that was running in it.

Busy is the union of the intervals in which an op ran on a chip.
"""
from __future__ import annotations

import glob
import os

JOB_SPAN = "bench.job"
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10


def load(path: str, device_ids) -> list:
    """Rows of one `.xplane.pb`: every op on the chips `device_ids`, and
    every host event (the job spans among them)."""
    from jax.profiler import ProfileData

    wanted = {f"/device:TPU:{i}" for i in device_ids}
    rows = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name in wanted:
            lines = [ln for ln in plane.lines if ln.name == OP_LINE]
        elif plane.name == HOST_PLANE:
            lines = list(plane.lines)
        else:
            continue
        for line in lines:
            for ev in line.events:
                rows.append((plane.name, line.name, ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return rows


def op_name(event_name: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce_dir(trace_dir: str, device_ids) -> dict:
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    return reduce_events(load(path, device_ids), device_ids)


def union(intervals) -> list:
    """Merged, sorted [start, end) intervals."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def covered_ns(merged, a: int, b: int) -> int:
    """Nanoseconds of [a, b) that the merged intervals cover."""
    return sum(max(0, min(e, b) - max(s, a)) for s, e in merged)


def reduce_events(rows, device_ids) -> dict:
    planes = [f"/device:TPU:{i}" for i in device_ids]
    ops = {pl: [] for pl in planes}
    host = []
    for plane, line, name, start, dur in rows:
        if plane in ops:
            ops[plane].append((start, start + dur, name))
        else:
            host.append((start, start + dur, name))
    jobs = sorted((s, e) for s, e, n in host if n == JOB_SPAN)
    if not jobs:
        raise ValueError(f"the trace holds no {JOB_SPAN!r} host span")
    busy = {pl: union((s, e) for s, e, _ in ops[pl]) for pl in planes}
    w0, w1 = jobs[0][0], jobs[-1][1]
    per_job = [
        {"busy_s": [covered_ns(busy[pl], s, e) / 1e9 for pl in planes],
         "span_s": (e - s) / 1e9}
        for s, e in jobs
    ]
    busy_s = sum(covered_ns(busy[pl], w0, w1) for pl in planes) / len(planes) / 1e9
    return {"jobs": per_job, "busy_s": busy_s, "window_s": (w1 - w0) / 1e9,
            "breakdown": breakdown(ops[planes[0]], busy[planes[0]], host, w0, w1)}


def breakdown(ops, busy, host, w0: int, w1: int) -> dict:
    """Top device ops of one chip by self time (an op's time less the ops
    nested in it, as a while loop's body is), and its longest idle gaps in
    the window, each named by the shortest host event covering its middle."""
    totals = {}
    stack = []  # [end, name, self_ns] of the ops open around the current one
    for s, e, name in sorted((s, -e, n) for s, e, n in ops if s >= w0 and e <= w1):
        e = -e
        while stack and stack[-1][0] <= s:
            _, n, ns = stack.pop()
            totals[n] = totals.get(n, 0) + ns
        if stack:
            stack[-1][2] -= e - s
        stack.append([e, op_name(name), e - s])
    for _, n, ns in stack:
        totals[n] = totals.get(n, 0) + ns
    top_ops = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    gaps, t = [], w0
    for s, e in busy + [[w1, w1]]:
        if s > t and t < w1:
            gaps.append((t, min(s, w1)))
        t = max(t, e)
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    named = []
    for a, b in gaps:
        mid = (a + b) // 2
        around = [(e - s, n) for s, e, n in host if s <= mid < e]
        named.append([min(around)[1] if around else "no host event", (b - a) / 1e9])
    return {"device_ops": [[n, ns / 1e9] for n, ns in top_ops], "idle_gaps": named}


def idle_percent(trace: dict) -> float:
    """The device's idle share of the traced window, in %."""
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])


def mean_busiest(jobs: list, key: str) -> float:
    """Mean over traced jobs of the busiest chip's seconds under `key`."""
    return sum(max(j[key]) for j in jobs) / len(jobs)
