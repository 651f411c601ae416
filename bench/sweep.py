"""Find a cell's knee: run its mix at several rates, one process.

    python3 bench/sweep.py --workload <cell> --seconds <s> --rates 0.1,0.2 \\
        --seed <n>

Each rate is one run of the cell as ``run.py`` makes it, with the mix's
``rate_rps`` replaced.  Per rate it prints what was offered and served,
and the queue wait of the first and the last third of the arrivals: the
knee is the highest rate whose wait does not grow over the window.  The
cell's rate is then written into its mix file by hand, as a number.
"""
import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
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
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)

    from harness import cell as run_cell
    from harness import derive
    bench = run_cell.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = run_cell.find_cell(bench, args.workload)
    conf = run_cell.config_file(bench, cell["config"])
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        mix = dict(run_cell.mix_file(cell["traffic"]), rate_rps=rate)
        try:
            result, extra = run_cell.run(
                cell, conf, mix, seed=args.seed + i, seconds=args.seconds,
                trace=False, t_process=time.time(), per_layer=[],
                end_to_end=[], log=log)
        except run_cell.NoChip as e:
            log(str(e))
            return 3
        ctx = extra["ctx"]
        recs = ctx.win.records
        waits = [((r.req.t_join or ctx.win.t_close)
                  if (r.req.t_join or 0) <= ctx.win.t_close
                  else ctx.win.t_close) - r.due for r in recs]
        third = max(len(waits) // 3, 1)
        row = {"rate_rps": rate, "due": len(recs),
               "joined": sum(r.req.t_join is not None
                             and r.req.t_join <= ctx.win.t_close
                             for r in recs),
               "finished": ctx.win.done_at_close,
               "tokens_per_s": derive.tokens_per_s(ctx),
               "wait_first_third_s": statistics.mean(waits[:third]),
               "wait_last_third_s": statistics.mean(waits[-third:]),
               "ttft_p90_ms": derive.ttft_p90_ms(ctx),
               "tpot_p90_ms": derive.tpot_p90_ms(ctx),
               "correct": result["correct"],
               "logit_gap": result["checks"]["logit_gap"]["value"]}
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
