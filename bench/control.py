"""Readings that set a cell's ``logit_gap`` limit: the program's and the
control's, on many seeds, in one process that holds the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed it makes one run of the cell as ``run.py`` does (the same
weights, traffic and window) and prints the program's widest gap; then,
on the same requests, the control's: the reference computed through fp8
weights in the program's place, the gap of the token it puts first.
The control's gap is held against the cell's limit as the program's is,
and its ``correct`` has to come out false on every seed.  The limit goes
between the largest program reading and the smallest control reading
(PERF.md records both).  The benchmark's own runs never run the control.
"""
import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)

    from harness import cell as run_cell
    from harness import check
    bench = run_cell.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run_cell.find_cell(bench, args.workload)
    conf = run_cell.config_file(bench, cell["config"])
    mix = run_cell.mix_file(cell["traffic"])
    rows = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        try:
            result, extra = run_cell.run(
                cell, conf, mix, seed=seed, seconds=args.seconds,
                trace=False, t_process=time.time(), per_layer=[],
                end_to_end=[], after_check=check.control_gap, log=log)
        except run_cell.NoChip as e:
            log(str(e))
            return 3
        ctl = extra["after_check"]
        checks = result["checks"]
        limit = checks["logit_gap"]["limit"]
        control_correct = check.control_decides(checks, ctl)
        log(f"[check] seed {seed}: program logit_gap = "
            f"{checks['logit_gap']['value']!r}, control logit_gap = "
            f"{ctl['gap']!r} (limit {limit!r})")
        row = {"seed": seed, "correct": result["correct"],
               "control_correct": control_correct,
               "program_gap": checks["logit_gap"]["value"],
               "tokens": checks["tokens_compared"]["value"],
               "control_gap": ctl["gap"], "control_flips": ctl["flips"],
               "limit": limit}
        rows.append(row)
        print(json.dumps(row), flush=True)
    prog = [r["program_gap"] for r in rows]
    ctl = [r["control_gap"] for r in rows]
    print(json.dumps({
        "program_max": max(prog), "control_min": min(ctl),
        "seeds": len(rows),
        "program_correct_on_all": all(r["correct"] for r in rows),
        "control_correct_on_none": not any(r["control_correct"]
                                           for r in rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
