"""Serve granite_3_2b at its published widths on one TPU chip.

The serving path a user runs — `deploy_model` (random weights from
seed 0, calibration, integer tables) and a `ServingEngine` over the
paged int8 KV arena with chunked prefill and the unified paged-attention
kernel — serves eight ragged requests (prompts of 128 to 1024 tokens,
32 new tokens each).  Its greedy tokens must equal

  * `serve_batch`, the contiguous-cache lockstep reference, on the
    requests that share one prompt length, and
  * the same engine with `paged_kernel=False`, which attends through
    XLA's write-then-gather path instead of the Mosaic kernel.

The compiled unified step must hold the Mosaic kernel
(`tpu_custom_call`).  Each phase prints its seconds, tok/s, TTFT and the
device's peak bytes; the last line is one JSON object naming the device.
The script fails, printing no such line, when JAX finds no TPU.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # kv-sharded engine on a (1, 4)
                                      # mesh vs the single-device engine
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "granite_3_2b"
PROMPT_LENS = (1024, 512, 896, 128, 640, 512, 384, 256)
SAME_LEN = (1, 5)  # the requests serve_batch replays (prompt length 512)
GEN = 32
MAX_LEN = max(PROMPT_LENS) + GEN
SLOTS = len(PROMPT_LENS)
PAGE = 128
CHUNK = 128
# 600 pages x 128 positions x 40 layers x 2 x 8 kv heads x 64 = 3.1 GB
# of int8 KV pools, far past what VMEM could hold: the kernel copies
# pages out of HBM
N_PAGES = 600


class SmokeFailure(Exception):
    pass


def log(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def check_device(n_chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU: JAX runs on {devs[0].platform} ({devs[0]})"
        )
    if len(devs) < n_chips:
        raise SmokeFailure(f"{n_chips} chips asked, {len(devs)} found")
    return devs


def make_engine(lm, tables, **kw):
    from repro.serving import SchedulerConfig, ServingConfig, ServingEngine

    return ServingEngine(lm, tables, ServingConfig(
        n_slots=SLOTS, max_len=MAX_LEN, paged=True, page_size=PAGE,
        n_pages=N_PAGES, kv_bits=8,
        scheduler=SchedulerConfig(prefill_chunk=CHUNK), **kw,
    ))


def unified_hlo(engine) -> str:
    """The compiled HLO of the engine's unified (n_slots, CHUNK) step."""
    import jax.numpy as jnp
    import numpy as np

    from repro.layers.attention import INACTIVE_POS

    parked = jnp.full((SLOTS,), INACTIVE_POS, jnp.int32)
    lowered = engine._unified.lower(
        engine.tables, jnp.zeros((SLOTS, CHUNK), jnp.int32),
        engine.arena.decode_view(), parked,
        jnp.asarray(np.zeros(SLOTS, np.int32)),
    )
    return lowered.compile().as_text()


def serve(name, engine, prompts, dev, *, check_kernel=False):
    """Warm the engine, serve every prompt, return {request: tokens}."""
    t0 = time.perf_counter()
    engine.warmup()
    compile_s = time.perf_counter() - t0
    if check_kernel and "tpu_custom_call" not in unified_hlo(engine):
        raise SmokeFailure(f"{name}: no tpu_custom_call in the unified step")
    ids = [engine.submit(p, max_new_tokens=GEN) for p in prompts]
    t1 = time.perf_counter()
    done = engine.run_until_drained()
    serve_s = time.perf_counter() - t1
    s = engine.stats()
    out = {c.req_id: [int(t) for t in c.tokens] for c in done}
    if sorted(out) != sorted(ids) or any(len(t) != GEN for t in out.values()):
        raise SmokeFailure(f"{name}: incomplete completions")
    log(
        name, compile_s=compile_s, serve_s=serve_s,
        tok_s=s["throughput_tok_s"], ttft_p50_s=s["p50_ttft_s"],
        ttft_max_s=s["max_ttft_s"], itl_p50_s=s["p50_itl_s"],
        steps=s["steps"], peak_bytes=peak_bytes(dev),
    )
    return [out[i] for i in ids]


def margin(lm, tables, prompt, prefix):
    """Top-2 gap of the contiguous reference's int32 logits after
    `prompt + prefix`, and its top token."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.rep import Rep

    seq = np.concatenate([prompt, np.asarray(prefix, np.int32)])[None]
    caches = lm.init_caches(1, seq.shape[1], Rep.ID)
    logits, _ = jax.jit(lm.prefill)(tables, jnp.asarray(seq), caches)
    row = np.asarray(logits[0, -1]).astype(np.int64)
    top = np.argsort(row)[-2:]
    return int(row[top[1]] - row[top[0]]), int(top[1])


def compare(name, got, want, lm, tables, prompts, rows) -> list:
    """Token equality; returns the first diverging token of each request
    that differs, with the reference's logit margin there."""
    bad = []
    for r, g, w in zip(rows, got, want):
        if g != w:
            i = next(k for k, (a, b) in enumerate(zip(g, w)) if a != b)
            gap, top = margin(lm, tables, prompts[r], w[:i])
            bad.append(
                f"{name}: request {r} token {i}: {g[i]} vs {w[i]} "
                f"(reference top {top}, logit margin {gap})"
            )
    log(name, equal=not bad, requests=len(rows), diverged=bad)
    return bad


def kernel_vs_mirror():
    """The Mosaic kernel against its jnp mirror (kernels/ref.py) at
    granite's head widths, one GQA group: the interpreter matches the
    mirror at tolerance 0; on the chip the float island is Mosaic's
    against XLA's, and this counts the int32 accumulators that differ."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.paged_attention import paged_attention_pallas
    from repro.kernels.ref import paged_attention_ref
    from repro.layers.attention import INACTIVE_POS

    B, H, K, hd, S, pps = 4, 4, 1, 64, CHUNK, -(-MAX_LEN // PAGE)
    rng = np.random.default_rng(1)
    q = rng.integers(-127, 128, size=(B, H, S, hd)).astype(np.int8)
    pools = [
        rng.integers(-127, 128, size=(B * pps + 1, K, hd, PAGE))
        .astype(np.int8) for _ in range(2)
    ]
    table = (1 + rng.permutation(B * pps)).reshape(B, pps).astype(np.int32)
    pos = np.array([0, 300, pps * PAGE - S, INACTIVE_POS], np.int32)
    args = [jnp.asarray(x) for x in (q, *pools, table, pos)]
    # logits spread over a few units per row, as in the model
    kw = dict(score_scale=5e-5, group=H // K)
    got = np.asarray(jax.jit(lambda *a: paged_attention_pallas(*a, **kw))(
        *args))
    want = np.asarray(jax.jit(lambda *a: paged_attention_ref(*a, **kw))(
        *args))
    diff = got != want
    log("kernel_vs_mirror", equal=not diff.any(),
        differing=int(diff.sum()), of=int(diff.size),
        max_abs_diff=int(np.abs(got.astype(np.int64) - want).max()))


def one_chip(lm, tables, prompts, dev) -> list:
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import serve_batch

    engine = make_engine(lm, tables)
    kernel = serve("serve_kernel", engine, prompts, dev, check_kernel=True)
    del engine
    gc.collect()

    t0 = time.perf_counter()
    same = jnp.asarray(np.stack([prompts[r] for r in SAME_LEN]))
    ref = np.asarray(serve_batch(lm, tables, same, GEN)).tolist()
    log("serve_batch", seconds=time.perf_counter() - t0,
        requests=len(SAME_LEN), peak_bytes=peak_bytes(dev))
    bad = compare("kernel_vs_serve_batch", [kernel[r] for r in SAME_LEN],
                  ref, lm, tables, prompts, SAME_LEN)

    engine = make_engine(lm, tables, paged_kernel=False)
    gather = serve("serve_gather", engine, prompts, dev)
    del engine
    gc.collect()
    return bad + compare("kernel_vs_gather", kernel, gather, lm, tables,
                         prompts, range(len(prompts)))


def four_chips(lm, tables, prompts, dev):
    from repro.launch.mesh import make_serving_mesh

    engine = make_engine(lm, tables)
    single = serve("serve_single", engine, prompts, dev, check_kernel=True)
    del engine
    gc.collect()
    engine = make_engine(
        lm, tables, mesh=make_serving_mesh(4), kv_shard=True,
        dispatch_depth=1,
    )
    sharded = serve("serve_kv_sharded", engine, prompts, dev)
    return compare("sharded_vs_single", sharded, single, lm, tables,
                   prompts, range(len(prompts)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    from repro.launch.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    try:
        devs = check_device(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    log("compile_cache", dir=cache_dir)
    dev = devs[0]
    log("device", platform=dev.platform, kind=dev.device_kind,
        count=len(devs))

    import numpy as np

    from repro.launch.serve import deploy_model

    if args.chips == 1:
        kernel_vs_mirror()
    t0 = time.perf_counter()
    lm, tables = deploy_model(ARCH, reduced=False, max_seq=MAX_LEN)
    log("deploy", arch=ARCH, seconds=time.perf_counter() - t0,
        d_model=lm.cfg.d_model, n_layers=lm.cfg.n_layers,
        peak_bytes=peak_bytes(dev))
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, lm.cfg.vocab, size=(n,)).astype(np.int32)
        for n in PROMPT_LENS
    ]
    try:
        run = four_chips if args.chips == 4 else one_chip
        bad = run(lm, tables, prompts, dev)
    except SmokeFailure as e:
        bad = [str(e)]
    log("total", seconds=time.perf_counter() - t_start,
        peak_bytes=peak_bytes(dev))
    if bad:
        print("chip_smoke: " + "; ".join(bad), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
