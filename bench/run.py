"""Chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout, in one process that holds the chip.
The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``); per-layer metrics are read by
``bench/metrics/<metric>.py``.  Weights and requests come from ``--seed``.
With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of a
sub-window.  Without a TPU (or with fewer chips than the cell asks for)
it exits non-zero and prints no result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` (traced
runs) and ``checks``, each number compared with its limit; the last lines
of standard error repeat the checks.
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


def metrics_for(specs: list, cell: str) -> list:
    return [m for m in specs if cell in m.get("workloads", [cell])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    from harness import cell as run_cell
    bench = run_cell.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run_cell.find_cell(bench, args.workload)
    conf = run_cell.config_file(bench, cell["config"])
    mix = run_cell.mix_file(cell["traffic"])
    try:
        result, _ = run_cell.run(
            cell, conf, mix, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), t_process=T_PROCESS,
            per_layer=metrics_for(bench["per_layer"], cell["name"]),
            end_to_end=metrics_for(bench["end_to_end"], cell["name"]),
            log=log)
    except run_cell.NoChip as e:
        log(str(e))
        return 3
    for name, c in result["checks"].items():
        log(f"[check] {name} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
