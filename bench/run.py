"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload granite_3_2b_l1.decode_closed \
        --seed 1234 --seconds 30 --trace 0

The cell (`BENCHMARK.json` -> workloads) names a configuration and a
traffic mix, each a file under `bench/`.  The run loads or deploys the
configuration's integer tables, builds the program's `ServingEngine`,
warms it, brings the traffic to a steady state, measures for `--seconds`
seconds, then checks a sample of the served tokens against the plain
float32 reference.  Its last line on stdout is one JSON object: the end
-to-end metrics (`--trace 0`) or the per-layer ones read from a
profiler trace of the window (`--trace 1`), the device, and the numbers
of the correctness check beside their limits.  Without a TPU, or with
fewer chips than the cell asks for, it exits with code 3 and prints no
result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NO_CHIP = 3


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def phase_telemetry():
    """The engine's telemetry with each phase span also written into
    the profiler trace, so that idle gaps can be labelled."""
    import contextlib

    from jax.profiler import TraceAnnotation

    from repro.serving import Telemetry

    class PhaseTelemetry(Telemetry):
        def span(self, phase):
            stack = contextlib.ExitStack()
            stack.enter_context(super().span(phase))
            stack.enter_context(TraceAnnotation("phase/" + phase))
            return stack

    return PhaseTelemetry(profile_annotations=True)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), BENCH]
    # JAX's persistent compilation cache at a fixed path in the checkout,
    # unless the environment names one; the program reads the same
    # variable and keeps to it.  JAX reads it as it is imported, and
    # resolving the cell imports it (the reference module)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    from harness import spec

    try:
        cell = spec.cell(args.workload)
    except spec.SpecError as e:
        log(str(e))
        return 2
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX finds {len(devs)} "
            f"{devs[0].platform} device(s)")
        return NO_CHIP
    dev = devs[0]
    try:
        peaks = spec.peaks(dev.device_kind)
    except spec.SpecError as e:
        log(str(e))
        return NO_CHIP
    return run(args, cell, dev, devs, peaks)


def serve(args, cell, devs) -> dict:
    """Load the tables, build and warm the engine, run the traffic
    through set-up and the window; the program's state is freed before
    this returns.  -> what the window served and when, as a dict."""
    import jax

    from harness import deploy, measure, traffic, window, xtrace
    from repro.serving import SchedulerConfig, ServingConfig, ServingEngine

    eng = cell.config["engine"]
    mix = cell.traffic
    open_loop = mix["loop"] == "open"
    if traffic.max_context(mix) > eng["max_len"]:
        raise ValueError("the mix's longest request exceeds max_len")
    lm, tables, deploy_s = deploy.tables(cell.config, cell.reference,
                                         eng["max_len"], eng["kv_bits"], log)
    engine = ServingEngine(lm, tables, ServingConfig(
        n_slots=eng["n_slots"], max_len=eng["max_len"], paged=True,
        page_size=eng["page_size"], n_pages=eng["n_pages"],
        kv_bits=eng["kv_bits"],
        scheduler=SchedulerConfig(prefill_chunk=eng["prefill_chunk"]),
        telemetry=phase_telemetry() if args.trace else None,
    ))
    rec = window.Recorder(engine)
    jits = window.JitCounter()
    gcs = window.GcTimer()
    vocab = lm.cfg.vocab
    window.warm(engine, vocab)
    log(f"warm at {time.perf_counter() - T_START} s")

    trace_dir = None
    hooks = {}
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="trace-",
                                     dir=os.environ.get("TMPDIR"))
        ann = {}

        def opened():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # host annotations, no frames
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            ann["w"] = jax.profiler.TraceAnnotation(xtrace.WINDOW)
            ann["w"].__enter__()

        def closed():
            ann["w"].__exit__(None, None, None)
            jax.profiler.stop_trace()

        hooks = {"on_open": opened, "on_close": closed}

    if open_loop:
        specs = traffic.open_loop(mix, args.seed, vocab, args.seconds)
        win = window.open_(engine, specs, args.seconds, float(mix["ramp_s"]),
                           rec, jits, log, **hooks)
    else:
        clients = traffic.closed_loop(mix, args.seed, vocab)
        win = window.closed(engine, clients, args.seconds, rec, jits, log,
                            **hooks)
    setup_s = win.t0 - T_START
    log(f"window {win.t1 - win.t0} s, {len(win.dispatches)} steps, "
        f"{win.jits} compiles inside it, setup {setup_s} s")
    served = window.served(engine)
    out = {
        "win": win, "served": served,
        "prompts": {r: s.spec.prompt for r, s in win.sent.items()},
        "peak": max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                    for d in devs[:cell.chips]),
        "trace_dir": trace_dir,
        "e2e": measure.end_to_end(win, served, args.seconds, open_loop),
        "attempted_failed": measure.attempted_failed(
            win, served, args.seconds, open_loop),
    }
    out["e2e"]["setup_s"] = setup_s
    log("end_to_end " + json.dumps(out["e2e"]))
    log("longest gap %r s, ending %r s into the window"
        % measure.longest_gap(win, served))
    inside = gcs.within(win.t0, win.t1)
    log(f"gc in the window: {len(inside)} collections, "
        f"{sum(d for d, _ in inside)} s, longest (s, generation) "
        f"{max(inside, default=None)}")
    del engine, rec, tables, lm
    gc.collect()
    return out


def gaps(cell, got: dict, seed: int, bits=None):
    """The check's gaps over the sample of `got`'s served requests;
    with `bits`, the control's."""
    import jax

    from harness import check, reference, traffic

    ref = cell.reference
    dims = ref.Dims.from_config(cell.config["model"])
    key = jax.random.PRNGKey(cell.config["weights_seed"])
    ids = check.sample(got["served"], got["prompts"], seed,
                       cell.check["sample_requests"])
    length = reference.padded_length(traffic.max_context(cell.traffic))
    return ids, check.gaps(ref, key, dims, got["prompts"], got["served"],
                           ids, length, bits=bits)


def run(args, cell, dev, devs, peaks) -> int:
    from harness import check, measure, spec, xtrace

    got = serve(args, cell, devs)
    win = got["win"]
    attempted, failed = got["attempted_failed"]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": got["peak"]}
    result = {"attempted": attempted, "failed": failed}
    if args.trace:
        tr = xtrace.load(xtrace.find(got["trace_dir"]))
        shutil.rmtree(got["trace_dir"], ignore_errors=True)
        if tr is None:
            log("the trace holds no device window")
            return 1
        ref = cell.reference
        ctx = measure.Run(win, got["served"], args.seconds, tr,
                          ref.Dims.from_config(cell.config["model"]),
                          cell.config["engine"], peaks, spec.counts, ref)
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = xtrace.busy_s(tr)
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": xtrace.top_ops(tr),
                               "idle_gaps": xtrace.idle_gaps(tr)}
    else:
        e2e = got["e2e"]
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if e2e.get(m["name"]) is not None}

    # correctness: the run's served tokens against the reference
    t0 = time.perf_counter()
    ids, g = gaps(cell, got, args.seed)
    nums = check.numbers(g)
    limits = cell.check["limits"]
    correct = (bool(ids) and check.judge(nums, limits) and failed == 0
               and win.jits == 0)
    log(f"reference over {len(ids)} requests, {nums['tokens']} served "
        f"tokens, in {time.perf_counter() - t0} s; widest gap "
        f"{nums['widest_gap']}")
    compared = {k: {"value": nums[k], "limit": limits[k]} for k in limits}
    compared["compiles_in_window"] = {"value": win.jits, "limit": 0}
    compared["failed"] = {"value": failed, "limit": 0}
    out = {"correct": correct, **result, "metrics": metrics,
           "device": device, "check": compared}
    for k, v in compared.items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
