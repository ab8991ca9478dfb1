"""Superstep checkpoint/resume for the BSP engine: segmented execution
with bit-identical recovery.

`run_bsp_resilient` runs the SAME programs as `engine.run_bsp` (it is
what `run_bsp(..., checkpoint_every=k, ckpt_dir=...)` delegates to) but
drives the loop in segments: every `checkpoint_every` supersteps the
value carry plus the per-step `BSPStats` buffers are snapshotted through
`repro.checkpoint.ckpt`, and an injected `FaultPlan` crash kills the run
mid-flight with a `WorkerCrashError`. `resume_bsp` restores the latest
checkpoint and continues — final values AND stats are bit-identical to
an uninterrupted run (tests/test_resilience.py pins this for cc/sssp/pr
on both drivers).

Why segments compose exactly: with exchange_period=1 the fused driver's
delta-message reference (`count_ref`) is always the step's entry value,
so a step's message counts depend only on the state it starts from — a
checkpoint boundary is indistinguishable from any other step boundary.
With bounded staleness (period>1), checkpoints are restricted to
exchange-period boundaries (`checkpoint_every % exchange_period == 0`),
where the last step exchanged and the carried `last_ex` snapshot equals
the value itself. The fused engine additionally returns its converged
flag (see `engine._fused_bsp`) so a run that converges exactly on a
segment boundary stops instead of paying a phantom extra superstep.

Checkpoints hold EXEC-domain values (max-combine programs store the
negated view the superstep body runs on; negation is exact for int32 and
f32, so the round-trip is bitwise). A side `resume.json` in `ckpt_dir`
records the program, driver, backend, engine knobs, and a subgraph
fingerprint; `resume_bsp` validates the fingerprint before continuing so
a checkpoint cannot silently resume onto the wrong build.
"""
from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import ckpt
from repro.graph import engine
from repro.resilience.faults import FaultPlan, WorkerCrashError

RESUME_META = "resume.json"


@dataclasses.dataclass
class _SegState:
    """Host-side carry between segments (and across crash/resume)."""

    val: np.ndarray  # [p, max_v+1] EXEC-domain value carry (rank-encoded
    # when a two-level label-domain run carries a codec)
    done: int  # supersteps completed
    msgs: list  # list of [k, p] int64 per-segment message blocks
    iters: list  # list of [k, p] int64 per-segment inner-iter blocks
    converged: bool
    codec: object = None  # engine._ValueCodec for two-level label programs

    def stack(self, p: int) -> tuple[np.ndarray, np.ndarray]:
        if not self.msgs:
            z = np.zeros((0, p), np.int64)
            return z, z.copy()
        return np.concatenate(self.msgs, axis=0), np.concatenate(self.iters, axis=0)


def _sub_fingerprint(sub) -> dict:
    return {
        "num_parts": int(sub.num_parts),
        "max_v": int(sub.max_v),
        "max_e": int(sub.max_e),
        "max_msg": int(sub.max_msg),
        "addressing": str(sub.addressing),
    }


def _ckpt_tree(state: _SegState, p: int) -> dict:
    msgs, iters = state.stack(p)
    # The rank codec's table rides in the snapshot: the carry holds ENCODED
    # values, and the codec may have been built from a caller-supplied
    # init_val that resume cannot re-derive.
    uniq = np.asarray(state.codec.uniq if state.codec is not None else (), np.int32)
    return {
        "val": np.asarray(state.val),
        "msgs": msgs,
        "iters": iters,
        "converged": np.int32(state.converged),
        "codec_uniq": uniq,
    }


def _write_meta(ckpt_dir, sub, prog, knobs: dict) -> None:
    meta = {"program": prog.name, "sub": _sub_fingerprint(sub), **knobs}
    d = Path(ckpt_dir)
    d.mkdir(parents=True, exist_ok=True)
    (d / RESUME_META).write_text(json.dumps(meta, indent=2))


def _run_fused_segment(sub, exec_prog, state: _SegState, seg: int, *, inner_cap,
                       exchange_period, tol, num_vertices, compute_backend,
                       block_e=512) -> None:
    # _fused_bsp donates its value arg: feed it a fresh device buffer per
    # segment (the host copy in `state` is the one we keep).
    val_dev = jnp.asarray(np.ascontiguousarray(state.val))
    val, steps, converged, msgs_buf, iters_buf, _ = engine._fused_bsp(
        sub, val_dev, prog=exec_prog, max_supersteps=seg, inner_cap=inner_cap,
        exchange_period=exchange_period, tol=tol, num_vertices=num_vertices,
        backend=compute_backend, block_e=block_e,
    )
    obs.count("engine.dispatch.fused")
    val, steps, converged, msgs_sw, iters_sw = jax.device_get(
        (val, steps, converged, msgs_buf, iters_buf)
    )
    steps = int(steps)
    state.val = np.asarray(val)
    state.msgs.append(msgs_sw[:steps].astype(np.int64))
    state.iters.append(iters_sw[:steps].astype(np.int64))
    state.done += steps
    state.converged = bool(converged)


def _run_host_segment(sub, exec_prog, state: _SegState, seg: int, *, inner_cap,
                      exchange_period, tol, num_vertices, compute_backend,
                      block_e=512) -> None:
    val = jnp.asarray(state.val)
    # Segment boundaries are exchange-period boundaries, so the value IS
    # the last-exchanged snapshot the delta counter references.
    last_ex = val
    msg_steps, iters_steps = [], []
    for k in range(state.done, state.done + seg):
        do_exchange = (k % exchange_period) == exchange_period - 1
        before = val
        val, msgs, iters, delta = engine._jit_superstep_sim(
            exec_prog, sub, val, inner_cap, do_exchange, last_ex,
            num_vertices, compute_backend, block_e,
        )
        obs.count("engine.dispatch.host")
        if do_exchange:
            last_ex = val
        msg_steps.append(np.asarray(msgs, np.int64))
        iters_steps.append(np.asarray(iters, np.int64))
        if exec_prog.convergence == "tol":
            if tol and float(delta) < tol:
                state.converged = True
        elif do_exchange and not bool(jnp.any(val != before)):
            state.converged = True
        if state.converged:
            break
    state.val = np.asarray(val)
    p = state.val.shape[0]
    state.msgs.append(np.asarray(msg_steps).reshape(len(msg_steps), p))
    state.iters.append(np.asarray(iters_steps).reshape(len(iters_steps), p))
    state.done += len(msg_steps)


def _run_segments(sub, exec_prog, negate, state: _SegState, *, max_supersteps,
                  inner_cap, exchange_period, tol, num_vertices, compute_backend,
                  driver, checkpoint_every, ckpt_dir, fault_plan, block_e=512):
    p = state.val.shape[0]
    run_seg = _run_fused_segment if driver == "fused" else _run_host_segment
    crash_at = None
    if fault_plan is not None and fault_plan.crash_at_superstep is not None:
        crash_at = int(fault_plan.crash_at_superstep)
    if checkpoint_every and ckpt_dir is not None and state.done == 0:
        ckpt.save(ckpt_dir, 0, _ckpt_tree(state, p))

    while not state.converged and state.done < max_supersteps:
        if crash_at is not None and state.done >= crash_at:
            # The doomed superstep is due: the worker dies before it can
            # complete (everything since the last checkpoint is lost —
            # resume_bsp recomputes it bit-identically).
            raise WorkerCrashError(superstep=state.done, ckpt_dir=ckpt_dir)
        stop = max_supersteps
        if checkpoint_every:
            stop = min(stop, (state.done // checkpoint_every + 1) * checkpoint_every)
        if crash_at is not None:
            stop = min(stop, crash_at)
        run_seg(
            sub, exec_prog, state, stop - state.done, inner_cap=inner_cap,
            exchange_period=exchange_period, tol=tol, num_vertices=num_vertices,
            compute_backend=compute_backend, block_e=block_e,
        )
        if checkpoint_every and ckpt_dir is not None and state.done % checkpoint_every == 0:
            ckpt.save(ckpt_dir, state.done, _ckpt_tree(state, p))

    msgs_sw, iters_sw = state.stack(p)
    edges = np.asarray(sub.edge_mask.sum(axis=1), np.int64)
    stats = engine._assemble_stats(state.done, msgs_sw, iters_sw, edges, exec_prog, inner_cap)
    val = jnp.asarray(state.val)
    if state.codec is not None:
        val = state.codec.decode(val)
    return (-val if negate else val), stats


def _check_ft_args(checkpoint_every, ckpt_dir, exchange_period) -> None:
    if checkpoint_every is not None:
        if int(checkpoint_every) < 1:
            raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every!r}")
        if ckpt_dir is None:
            raise ValueError("checkpoint_every needs ckpt_dir= (where snapshots go)")
        if int(checkpoint_every) % int(exchange_period) != 0:
            raise ValueError(
                f"checkpoint_every={checkpoint_every} must be a multiple of "
                f"exchange_period={exchange_period}: segments only compose exactly "
                "at exchange boundaries (the delta-message reference is the "
                "exchanged snapshot)"
            )
    elif ckpt_dir is not None:
        raise ValueError("ckpt_dir needs checkpoint_every= (snapshot cadence)")


def run_bsp_resilient(
    sub,
    program,
    init_val=None,
    *,
    max_supersteps: Optional[int] = None,
    inner_cap: int = 10_000,
    exchange_period: int = 1,
    tol: float = 0.0,
    num_vertices: int = 0,
    source=None,
    compute_backend: str = "xla",
    driver: str = "fused",
    block_e: int = 512,
    checkpoint_every: Optional[int] = None,
    ckpt_dir=None,
    fault_plan: Optional[FaultPlan] = None,
):
    """`engine.run_bsp` with superstep checkpointing and deterministic
    fault injection — same (values, BSPStats) contract, bit-identical
    results (the non-checkpointed path IS run_bsp; this one runs the same
    loop in composable segments). Raises `WorkerCrashError` when the
    fault plan's crash comes due; `resume_bsp` continues from the last
    checkpoint in `ckpt_dir`."""
    prog = engine.get_program(program)
    engine.check_int32_kernel_labels(prog, sub, compute_backend)
    engine.check_pagerank_num_vertices(prog, num_vertices)
    engine.check_driver(driver)
    _check_ft_args(checkpoint_every, ckpt_dir, exchange_period)
    if max_supersteps is None:
        max_supersteps = prog.default_steps or 200
    if exchange_period > 1 and (prog.local != "fixpoint" or prog.convergence != "no_change"):
        raise ValueError(
            f"exchange_period>1 (bounded staleness) needs a fixpoint/no-change program; "
            f"{prog.name!r} is local={prog.local!r}, convergence={prog.convergence!r}"
        )
    if init_val is None:
        init_val = prog.init(sub, num_vertices=num_vertices, source=source)
    exec_prog, negate = engine._exec_view(prog)
    val = -init_val if negate else init_val
    # Same two-level value boundary as run_bsp: encode before the first
    # segment so every checkpoint holds kernel-ready (encoded) values.
    val, codec = engine._kernel_value_boundary(prog, sub, jnp.asarray(val), compute_backend)
    state = _SegState(
        val=np.asarray(val), done=0, msgs=[], iters=[], converged=False, codec=codec
    )
    if checkpoint_every and ckpt_dir is not None:
        _write_meta(ckpt_dir, sub, prog, {
            "driver": driver, "compute_backend": compute_backend,
            "max_supersteps": int(max_supersteps), "inner_cap": int(inner_cap),
            "exchange_period": int(exchange_period), "tol": float(tol),
            "num_vertices": int(num_vertices), "checkpoint_every": int(checkpoint_every),
            "block_e": int(block_e),
        })
    return _run_segments(
        sub, exec_prog, negate, state, max_supersteps=max_supersteps,
        inner_cap=inner_cap, exchange_period=exchange_period, tol=tol,
        num_vertices=num_vertices, compute_backend=compute_backend, driver=driver,
        checkpoint_every=checkpoint_every, ckpt_dir=ckpt_dir, fault_plan=fault_plan,
        block_e=block_e,
    )


def resume_bsp(
    sub,
    *,
    ckpt_dir,
    driver: Optional[str] = None,
    compute_backend: Optional[str] = None,
    fault_plan: Optional[FaultPlan] = None,
):
    """Restore the latest checkpoint in `ckpt_dir` and run the BSP loop to
    completion. Returns (values, BSPStats) bit-identical to the
    uninterrupted run — including the stats of the supersteps that ran
    BEFORE the crash (they are part of the snapshot).

    `driver` / `compute_backend` default to the crashed run's but may be
    overridden (driver/backend parity makes that answer-preserving —
    e.g. resume on the host driver after a fused-path crash)."""
    d = Path(ckpt_dir)
    meta_path = d / RESUME_META
    if not meta_path.exists():
        raise FileNotFoundError(
            f"no {RESUME_META} in {d} — was this run started with checkpoint_every=/ckpt_dir=?"
        )
    meta = json.loads(meta_path.read_text())
    prog = engine.get_program(meta["program"])
    backend = meta["compute_backend"] if compute_backend is None else compute_backend
    engine.check_int32_kernel_labels(prog, sub, backend)
    drv = engine.check_driver(meta["driver"] if driver is None else driver)
    fp = _sub_fingerprint(sub)
    if fp != meta["sub"]:
        raise ValueError(
            f"checkpoint in {d} was written for a different build: "
            f"checkpoint {meta['sub']} vs this SubgraphSet {fp}"
        )
    step = ckpt.latest_step(d)
    if step is None:
        raise FileNotFoundError(f"no published checkpoint under {d}")
    exec_prog, negate = engine._exec_view(prog)
    p = sub.gid.shape[0]
    dt = np.int32 if prog.dtype == "int32" else np.float32
    like = {
        "val": np.zeros((0,), dt),
        "msgs": np.zeros((0, 0), np.int64),
        "iters": np.zeros((0, 0), np.int64),
        "converged": np.int32(0),
        "codec_uniq": np.zeros((0,), np.int32),
    }
    tree = ckpt.restore(d, step, like)
    uniq = np.asarray(tree["codec_uniq"])
    codec = engine._ValueCodec(uniq=tuple(int(x) for x in uniq)) if uniq.size else None
    state = _SegState(
        val=np.asarray(tree["val"]),
        done=int(step),
        msgs=[np.asarray(tree["msgs"], np.int64)],
        iters=[np.asarray(tree["iters"], np.int64)],
        converged=bool(int(tree["converged"])),
        codec=codec,
    )
    if state.val.shape[0] != p:
        raise ValueError(
            f"checkpoint value carry has {state.val.shape[0]} workers, build has {p}"
        )
    if (
        codec is None
        and backend != "xla"
        and prog.dtype == "int32"
        and sub.addressing == "two_level"
    ):
        # No codec rode along (BFS-style unit-weight carries raw hop counts):
        # re-check the restored carry at the value boundary before resuming
        # onto an f32 kernel backend.
        mag = np.abs(state.val.astype(np.int64))
        finite = mag != int(engine.INF_I32)
        bound = int(mag[finite].max()) if finite.any() else 0
        if prog.weight == "unit":
            bound += int(np.asarray(sub.is_master).sum())
        engine.check_int32_kernel_values(prog, bound, backend)
    return _run_segments(
        sub, exec_prog, negate, state,
        max_supersteps=int(meta["max_supersteps"]), inner_cap=int(meta["inner_cap"]),
        exchange_period=int(meta["exchange_period"]), tol=float(meta["tol"]),
        num_vertices=int(meta["num_vertices"]), compute_backend=backend, driver=drv,
        checkpoint_every=int(meta["checkpoint_every"]), ckpt_dir=d, fault_plan=fault_plan,
        block_e=int(meta.get("block_e", 512)),
    )
