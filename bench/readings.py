"""Read, on the chip, the numbers a cell's correctness limit is set from.

    python3 bench/readings.py --workload granite_3_2b_l1.decode_closed \
        --seeds 101,102,103 --control-seeds 101,102,103 --seconds 30

For each seed it serves the cell as `bench/run.py` does (set-up, the
window, the program's state freed) and reads the check's numbers of the
program's served tokens.  For each control seed it also reads the
control's: the reference computed with every weight matrix and every
input of a matrix product rounded to `--bits` bits (4, the precision
below the configuration's int8), at the same prompts and tokens.  One
JSON line per seed on stdout.  The benchmark's own runs never run the
control.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--bits", type=int, default=4)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(os.path.dirname(BENCH), "src"), BENCH]
    import run as bench_run
    from harness import check, spec
    from repro.launch.compile_cache import setup_compile_cache

    cell = spec.cell(args.workload)
    setup_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return bench_run.NO_CHIP
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in [int(s) for s in args.seeds.split(",")]:
        run_args = argparse.Namespace(seed=seed, seconds=args.seconds,
                                      trace=0)
        got = bench_run.serve(run_args, cell, devs)
        ids, g = bench_run.gaps(cell, got, seed)
        line = {"seed": seed, "requests": len(ids),
                "program": check.numbers(g),
                "e2e": got["e2e"], "compiles_in_window": got["win"].jits}
        if seed in control:
            _, c = bench_run.gaps(cell, got, seed, bits=args.bits)
            line[f"control_w{args.bits}a{args.bits}"] = check.numbers(c)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
