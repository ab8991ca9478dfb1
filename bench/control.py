"""Controls: what `correct` must refuse, put in the program's place.

    python3 -m bench.control --workload <cell> --seeds <n> [<n> ...] --seconds <s>

For each seed this runs the cell as the benchmark does, then again with
the control in the program's place, and prints both runs' compared numbers
(one JSON line each). The benchmark's own runs never run a control.

Each traffic mix's compare file, `bench/compares/<compare>.py`, holds its
control (`install_control`) beside its check and says what it breaks.
"""
from __future__ import annotations

import argparse
import json
import sys


def install(jobs) -> None:
    """Put the control of the traffic's compare in `jobs`' place (the hook
    of `bench.run.run_cell`)."""
    jobs.compare.install_control(jobs)


def answer_with(jobs, answer) -> None:
    """Make each engine job answer `answer(root)`, a per-vertex array, in
    place of the program's values; the program still runs each job, so the
    window and the counts stay the program's."""
    program_run = jobs.run

    def run(i):
        _, stats, root = program_run(i)
        return answer(root), stats, root

    jobs.run = run


def main(argv=None) -> int:
    from bench import run as bench_run

    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(bench_run.ROOT / "src"))
    bench = bench_run.Bench()
    bench_run.use_compile_cache()
    device = bench_run.check_device(bench.cell(args.workload)["chips"], bench.peaks())
    for seed in args.seeds:
        for side, hook in (("program", None), ("control", install)):
            res = bench_run.run_cell(bench, args.workload, seed, args.seconds, False, device, hook=hook)
            print(json.dumps({"workload": args.workload, "seed": seed, "side": side,
                              "correct": res["correct"], "attempted": res["attempted"],
                              "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
