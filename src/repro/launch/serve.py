"""Integer-only serving entry point (the paper's deployment target).

The real serving loop lives in repro.serving.ServingEngine: a
continuous-batching scheduler over the ID-representation
prefill/decode_step — slot-pooled KV arena, FCFS admission, fused
per-slot-position decode, greedy argmax on int32 logits (DESIGN.md
§Serving).  This module is the thin CLI over it, plus `serve_batch`,
the original fixed-shape lockstep loop, kept as the parity reference
(tests/test_serving.py asserts the engine reproduces it token-for-token
for simultaneous same-length requests).

Scheduling policy (DESIGN.md §Scheduling): `--policy fcfs` (default)
reproduces strict FCFS admission; `--policy priority` ranks admission
by request priority class and preempts lower-class decodes on the
paged arena (preempted requests resume bit-exactly — the integer
path's determinism is the oracle).  `--arrival-rate QPS` switches from
closed-loop replay (submit everything, drain) to the OPEN-LOOP
harness: Poisson arrivals at the offered rate, with
`--slo-ttft-p99` / `--slo-itl-p99` (seconds) declaring the SLO targets
that define goodput — SLO-meeting completions per second — and the
sustained verdict (aggregate p99s within targets at this rate).

Multi-device serving (DESIGN.md §Serving ¶Multi-device): `--mesh N`
builds a ("data", "model") serving mesh with N devices on the model
axis, `--kv-shard` shards the KV arena along kv heads over it, and
`--dispatch-depth 1` overlaps host scheduling with the in-flight
device step.  On a CPU host `--mesh N` forces N XLA host devices
before jax initializes (the launch/dryrun.py trick); a platform that
still exposes fewer devices than asked makes make_serving_mesh raise.

Kernels compile with Mosaic on a TPU and run in the Pallas interpreter
on the CPU (kernels/platform.py).  On a TPU the paged arena's page size
must be a multiple of 128 (the default); `chip_smoke.py` at the repo
root serves the full-width model on one chip.

CPU-scale example:
  PYTHONPATH=src python -m repro.launch.serve --arch granite_3_2b \
      --reduced --requests 8 --slots 4 --prompt-len 16 --gen 16 --ragged \
      --mesh 2 --kv-shard --dispatch-depth 1
"""
from __future__ import annotations

import os
import sys


def _force_host_devices():
    """--mesh N on a CPU host: request N host-platform devices BEFORE
    any jax import (the device count locks on first backend init —
    same preamble trick as launch/dryrun.py).  Handles both
    `--mesh N` and `--mesh=N`."""
    n = 0
    for i, arg in enumerate(sys.argv):
        if arg == "--mesh" and i + 1 < len(sys.argv):
            val = sys.argv[i + 1]
        elif arg.startswith("--mesh="):
            val = arg.split("=", 1)[1]
        else:
            continue
        try:
            n = int(val)
        except ValueError:
            return
        break
    flags = os.environ.get("XLA_FLAGS", "")
    if n > 1 and "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={n} " + flags
        )


# only when this module IS the program: an importing program's argv
# must not leak device-count side effects into its jax init
if __name__ == "__main__":
    _force_host_devices()

import argparse  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.base import get_config  # noqa: E402
from repro.core.rep import Rep  # noqa: E402
from repro.data.synthetic import SyntheticConfig, SyntheticStream  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.models.lm import DecoderLM  # noqa: E402
from repro.serving import (  # noqa: E402
    Request,
    SchedulerConfig,
    ServingConfig,
    ServingEngine,
    Telemetry,
    make_policy,
    poisson_arrivals,
    run_open_loop,
    shared_prefix_workload,
)


def deploy_model(
    arch: str, *, reduced: bool, max_seq: int, calib_batch: int = 4
):
    """Random float params (seed 0) -> calibrate on a synthetic batch on
    the default device -> integer tables built on the host and placed
    on the default device.  The float params leave the device before
    the tables arrive: at granite_3_2b's full width they are 10.5 GB
    of float32 against 2.6 GB of tables, and one v5e holds 16 GiB."""
    cfg = get_config(arch)
    if reduced:
        cfg = cfg.reduced()
    lm = DecoderLM(cfg, max_seq=max_seq)
    key = jax.random.PRNGKey(0)
    p = lm.init(key)
    stream = SyntheticStream(SyntheticConfig(
        vocab=cfg.vocab, seq_len=min(64, max_seq - 1),
        global_batch=calib_batch))
    sample = jnp.asarray(stream.batch(0))[:, :-1]
    calib = lm.calibrate(p, sample)
    p = jax.tree.map(np.asarray, p)  # drops the device copy
    tables = lm.deploy(p, calib)
    del p
    tables = jax.tree.map(
        jnp.asarray, tables, is_leaf=lambda x: isinstance(x, np.ndarray))
    return lm, tables


def serve_batch(lm, tables, prompts, gen_len: int):
    """Lockstep reference: prompts (B, P) int32 -> (B, gen_len) int32.

    All slots prefill together and advance in lockstep at one shared
    scalar position — the pre-engine serving path, kept as the parity
    oracle for ServingEngine.
    """
    B, P = prompts.shape
    max_len = P + gen_len
    caches = lm.init_caches(B, max_len, Rep.ID)
    prefill = jax.jit(lm.prefill)
    decode = jax.jit(lm.decode_step)
    logits, caches = prefill(tables, prompts, caches)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    out = [tok]
    for i in range(gen_len - 1):
        logits, caches = decode(tables, tok, caches, P + i)
        tok = jnp.argmax(logits[:, 0], axis=-1)[:, None]
        out.append(tok)
    return jnp.concatenate(out, axis=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_3_2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0,
                    help="arena sequence capacity (0: prompt-len + gen)")
    ap.add_argument("--ragged", action="store_true",
                    help="vary prompt/gen lengths per request")
    ap.add_argument("--prefill-bucket", type=int, default=16)
    ap.add_argument(
        "--prefill-chunk",
        type=int,
        default=32,
        help="chunked-prefill chunk size (dense, moe); "
        "0 = whole-prompt bucketed prefill",
    )
    ap.add_argument(
        "--max-chunks-per-step",
        type=int,
        default=0,
        help="fairness knob: chunk rows per packed prefill "
        "dispatch (0: every prefilling slot)",
    )
    ap.add_argument(
        "--paged",
        action="store_true",
        help="paged KV arena (page budgets instead of "
        "worst-case slot rows)",
    )
    ap.add_argument(
        "--page-size",
        type=int,
        default=128,
        help="positions per KV page (a multiple of 128 on a TPU)",
    )
    ap.add_argument(
        "--kv-bits",
        type=int,
        default=8,
        choices=(8, 4),
        help="KV storage width (DESIGN.md §Serving ¶Sub-8-bit KV): "
        "8 = bit-exact int8 KV images; 4 = two int4 nibbles per "
        "pool cell — half the arena bytes, lossy vs int8 KV "
        "(needs --paged and the chunked prefill path)",
    )
    ap.add_argument(
        "--pages",
        type=int,
        default=0,
        help="page pool size (0: slots*max_len/page_size)",
    )
    ap.add_argument(
        "--prefix-cache",
        action="store_true",
        help="refcounted prefix caching with copy-on-write page "
        "sharing (DESIGN.md §Prefix-caching; needs --paged and "
        "the chunked prefill path)",
    )
    ap.add_argument(
        "--cache-keep-pages",
        type=int,
        default=0,
        help="warm-page retention budget: registered pages kept "
        "resident after their last reference drops, evicted LRU "
        "under pressure (0: evict immediately; needs "
        "--prefix-cache)",
    )
    ap.add_argument(
        "--shared-prefix",
        type=int,
        default=0,
        help="give every request the SAME random prefix of this "
        "many tokens (a system-prompt workload — what "
        "--prefix-cache shares; 0: fully independent prompts)",
    )
    ap.add_argument(
        "--paged-gather",
        action="store_true",
        help="paged decode through the write-then-gather "
        "jnp oracle instead of the fused "
        "paged-attention kernel (parity debugging)",
    )
    ap.add_argument(
        "--mesh",
        type=int,
        default=0,
        help="devices on the serving mesh's model axis "
        "(0: single-device; on CPU this forces that many "
        "host devices before jax init)",
    )
    ap.add_argument(
        "--kv-shard",
        action="store_true",
        help="shard the KV arena along kv heads over the "
        "mesh model axis (needs --mesh)",
    )
    ap.add_argument(
        "--dispatch-depth",
        type=int,
        default=0,
        choices=(0, 1),
        help="async dispatch queue depth: 1 overlaps host "
        "scheduling with the in-flight device step "
        "(0: synchronous)",
    )
    ap.add_argument(
        "--policy",
        default="fcfs",
        choices=("fcfs", "priority"),
        help="scheduling policy (DESIGN.md §Scheduling): fcfs "
        "reproduces strict arrival order; priority ranks "
        "admission by request class and preempts lower-class "
        "decodes (paged arena)",
    )
    ap.add_argument(
        "--arrival-rate",
        type=float,
        default=0.0,
        help="open-loop Poisson arrival rate in requests/s "
        "(0: closed-loop replay — submit everything, drain)",
    )
    ap.add_argument(
        "--slo-ttft-p99",
        type=float,
        default=0.0,
        help="TTFT SLO target in seconds for the open-loop "
        "goodput rollup (0: no TTFT SLO)",
    )
    ap.add_argument(
        "--slo-itl-p99",
        type=float,
        default=0.0,
        help="inter-token-latency SLO target in seconds for "
        "the open-loop goodput rollup (0: no ITL SLO)",
    )
    ap.add_argument(
        "--trace-out",
        default="",
        help="write the request-lifecycle trace as JSONL here "
        "(enables telemetry; tools/trace_summary.py reads it)",
    )
    ap.add_argument(
        "--metrics-out",
        default="",
        help="write aggregated step-phase metrics as JSON here "
        "(enables telemetry)",
    )
    ap.add_argument(
        "--profile-dir",
        default="",
        help="write a jax profiler trace of the serve here, with the "
        "engine's steps, phases and dispatches annotated in it "
        "(enables telemetry)",
    )
    args = ap.parse_args()
    setup_compile_cache()

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serving_mesh

        mesh = make_serving_mesh(args.mesh)
    elif args.kv_shard:
        ap.error("--kv-shard needs --mesh N")

    max_len = args.max_len or (args.prompt_len + args.gen)
    lm, tables = deploy_model(args.arch, reduced=args.reduced, max_seq=max_len)
    tel = None
    if args.trace_out or args.metrics_out or args.profile_dir:
        tel = Telemetry(profile_annotations=bool(args.profile_dir))
    engine = ServingEngine(lm, tables, ServingConfig(
        n_slots=args.slots, max_len=max_len,
        paged=args.paged, page_size=args.page_size,
        n_pages=args.pages or None,
        kv_bits=args.kv_bits,
        paged_kernel=not args.paged_gather,
        mesh=mesh, kv_shard=args.kv_shard,
        dispatch_depth=args.dispatch_depth,
        prefix_cache=args.prefix_cache,
        cache_keep_pages=args.cache_keep_pages,
        telemetry=tel,
        policy=make_policy(
            args.policy,
            **({"slo_ttft_s": args.slo_ttft_p99}
               if args.policy == "priority" and args.slo_ttft_p99
               else {})),
        scheduler=SchedulerConfig(
            prefill_bucket=args.prefill_bucket,
            prefill_chunk=args.prefill_chunk,
            max_chunks_per_step=args.max_chunks_per_step or None)))
    engine.warmup()  # precompile decode + every chunk row bucket
    rng = np.random.default_rng(0)
    if args.shared_prefix:
        if args.shared_prefix > args.prompt_len:
            ap.error("--shared-prefix must be <= --prompt-len")
        requests = shared_prefix_workload(
            args.requests, lm.cfg.vocab, rng,
            prefix_len=args.shared_prefix,
            suffix_len=args.prompt_len - args.shared_prefix,
            max_new_tokens=args.gen)
    else:
        requests = []
        for i in range(args.requests):
            if args.ragged:
                # p <= max_len - 1 keeps >= 1 position for generation
                hi = min(args.prompt_len, max_len - 1)
                p = int(
                    rng.integers(
                        max(1, min(args.prompt_len // 4, hi)), hi + 1)
                )
                g = int(rng.integers(1, min(args.gen, max_len - p) + 1))
            else:
                p, g = args.prompt_len, args.gen
            requests.append(Request(
                rng.integers(0, lm.cfg.vocab, size=(p,)),
                max_new_tokens=g,
                # under the priority policy, alternate classes so the
                # class-aware admission/preemption is visible from the
                # CLI
                priority=i % 2 if args.policy == "priority" else 0,
            ))
    open_loop = None
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    if args.arrival_rate > 0:
        open_loop = run_open_loop(
            engine, requests,
            poisson_arrivals(len(requests), args.arrival_rate, rng),
            slo_ttft_s=args.slo_ttft_p99 or None,
            slo_itl_s=args.slo_itl_p99 or None)
        completions = open_loop.completions
    else:
        for req in requests:
            engine.submit(req)
            engine.step()  # arrivals interleave with decoding
        completions = engine.run_until_drained()
    if args.profile_dir:
        jax.profiler.stop_trace()
    s = engine.stats()
    if mesh is not None:
        print(
            f"serving mesh {dict(mesh.shape)} "
            f"(kv_shard={args.kv_shard}, "
            f"dispatch_depth={args.dispatch_depth})"
        )
    print(
        f"drained {s['n_completed']} requests / "
        f"{s['n_generated']} tokens in {s['wall_s']:.2f}s "
        f"({s['throughput_tok_s']:.1f} tok/s integer-only, "
        f"mean TTFT {s['mean_ttft_s'] * 1e3:.0f} ms, "
        f"occupancy {s['mean_occupancy']:.2f}, "
        f"policy {s['policy']})"
    )
    if s["n_preempts"]:
        print(
            f"  preemptions: {s['n_preempts']} "
            "(every victim resumed bit-exactly — the resume parity "
            "oracle raises otherwise)"
        )
    if open_loop is not None:
        o = open_loop
        print(
            f"  open loop: offered {o.offered_qps:.2f} req/s, "
            f"goodput {o.goodput_qps:.2f} req/s "
            f"(SLO attainment {o.slo_attainment:.0%}"
            + (f", sustained={o.sustained}" if o.sustained is not None
               else "")
            + ")"
        )
    if args.paged:
        print(
            f"  paged arena: peak {s['max_pages_in_use']}/{s['n_pages']} "
            f"pages of {s['page_size']} positions, "
            f"peak concurrency {s['max_active']}"
        )
    if s.get("prefix_cache"):
        print(
            f"  prefix cache: {s['prefix_hits']} hits / "
            f"{s['prefix_misses']} misses, "
            f"{s['prefix_hit_pages']} shared pages reused, "
            f"{s['cow_splits']} cow splits, "
            f"{s['warm_pages']} warm retained "
            f"(keep {s['cache_keep_pages']}, "
            f"{s['warm_evictions']} evicted)"
        )
    # SLO rollup (DESIGN.md §Observability): latency percentiles plus
    # the queued/prefill/decode breakdown of where wall time went
    print(
        f"  TTFT p50/p95/p99 "
        f"{s['p50_ttft_s'] * 1e3:.0f}/{s['p95_ttft_s'] * 1e3:.0f}/"
        f"{s['p99_ttft_s'] * 1e3:.0f} ms, "
        f"ITL p50/p95/p99 "
        f"{s['p50_itl_s'] * 1e3:.1f}/{s['p95_itl_s'] * 1e3:.1f}/"
        f"{s['p99_itl_s'] * 1e3:.1f} ms"
    )
    print(
        f"  breakdown: queued {s['mean_queued_s'] * 1e3:.0f} ms, "
        f"prefill {s['mean_prefill_s'] * 1e3:.0f} ms, "
        f"decode {s['mean_decode_s'] * 1e3:.0f} ms "
        f"(admit rejects {s['admit_rejects']})"
    )
    for c in completions[: min(4, len(completions))]:
        print(
            f"  req {c.req_id}: P={c.prompt_len} "
            f"-> {c.n_generated} toks [{c.finish_reason}] "
            f"{np.asarray(c.tokens)[:8]}"
        )
    if args.profile_dir:
        print(f"  profiler trace -> {args.profile_dir}")
    if tel is not None:
        if args.trace_out:
            tel.export_trace(args.trace_out)
            print(
                f"  trace: {len(tel.events)} events -> {args.trace_out}"
            )
        if args.metrics_out:
            tel.export_metrics(args.metrics_out)
            print(
                f"  metrics: {len(tel.steps)} step records -> "
                f"{args.metrics_out}"
            )


if __name__ == "__main__":
    main()
