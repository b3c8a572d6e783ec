"""Find the knee of an open-loop cell: the highest rate it sustains.

    python3 bench/sweep.py --config granite_3_2b_l1 --traffic chat_open \
        --rates 1,2,4,6,8 --seed 7 --seconds 30

For each offered rate it serves the open-loop mix `--traffic` on the
configuration `--config` (each a file under bench/) at that rate as
`bench/run.py` does and prints one JSON line: the requests due in the
window, TTFT p50 and p95 from the scheduled arrival, ITL p95, output
tokens per second, the requests still without a first token when the
window closed (the backlog), and how much later the last third of the
window's requests got their first token than the first third (a
backlog that grows).  A cell's fixed rate, 0.8 x the knee, is then
written into its traffic file by hand.
"""
import argparse
import json
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]
    import run as bench_run
    from harness import measure, spec
    from repro.launch.compile_cache import setup_compile_cache

    config = spec.load("configs", args.config)
    cell = spec.Cell(
        name=f"{args.config}.{args.traffic}", chips=1, config=config,
        reference=spec.config_reference(config),
        traffic=spec.load("traffic", args.traffic), check={},
        end_to_end=[], per_layer=[])
    setup_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return bench_run.NO_CHIP
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.traffic = dict(cell.traffic, rate=rate)
        got = bench_run.serve(argparse.Namespace(
            seed=args.seed, seconds=args.seconds, trace=0), cell, devs)
        win, served = got["win"], got["served"]
        due = sorted(measure.due(win, args.seconds),
                     key=lambda r: win.sent[r].scheduled)
        first = {r: served[r][1][0] for r in due if r in served}
        ttft = [first[r] - win.sent[r].scheduled for r in due if r in first]
        third = max(1, len(due) // 3)

        def med(rs):
            v = [first[r] - win.sent[r].scheduled for r in rs if r in first]
            return float(np.median(v)) if v else None

        early, late = med(due[:third]), med(due[-third:])
        print(json.dumps({
            "rate": rate, "due": len(due),
            "ttft_p50_s": measure.p(ttft, 50),
            "ttft_p95_s": measure.p(ttft, 95),
            "itl_p95_s": got["e2e"]["itl_p95_s"],
            "out_tok_s": got["e2e"]["out_tok_s"],
            "backlog_at_close": sum(1 for r in due
                                    if first.get(r, win.t1 + 1) > win.t1),
            "never_served": sum(1 for r in due if r not in first),
            "ttft_growth_s": (late - early) if early is not None
            and late is not None else None,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
