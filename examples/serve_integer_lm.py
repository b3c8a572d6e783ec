"""Continuous-batching integer-only LM serving (the paper's deployment
target): calibrate -> deploy -> ServingEngine over int8/int32.

Ragged arrivals: requests with different prompt lengths and generation
budgets arrive staggered, share the slot arena, and complete at
different times — all greedy argmax on int32 logits, no floats.

  PYTHONPATH=src python examples/serve_integer_lm.py

Multi-device serving (DESIGN.md §Serving ¶Multi-device) — the same
engine, three `ServingConfig` knobs (`mesh=...`, `kv_shard=...`,
`dispatch_depth=...`), or on the CLI:

  PYTHONPATH=src python -m repro.launch.serve --arch granite_3_2b \
      --reduced --requests 8 --slots 4 --ragged \
      --mesh 2 --kv-shard --dispatch-depth 1

  --mesh N           ("data", "model") serving mesh, N devices on the
                     model axis; on a plain CPU host it forces N XLA
                     host devices before jax initializes
  --kv-shard         shard the KV arenas along kv heads over the mesh
                     model axis (GQA-aware; indivisible head counts
                     fall back to replication) — bit-exact with
                     single-device serving, token for token
  --dispatch-depth 1 async dispatch queue: overlap admission + chunk
                     packing with the in-flight fused decode, blocking
                     only at token harvest (0 = synchronous)

The second engine below runs that configuration in-process on two
devices.  On a CPU host this script asks XLA for two host devices
before jax starts; `make_serving_mesh` raises where fewer exist.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=2 "
    + os.environ.get("XLA_FLAGS", "")
)

import numpy as np  # noqa: E402

from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.launch.serve import deploy_model  # noqa: E402
from repro.serving import (  # noqa: E402
    SchedulerConfig,
    ServingConfig,
    ServingEngine,
)

lm, tables = deploy_model("granite_3_2b", reduced=True, max_seq=48)

streamed = {}
engine = ServingEngine(
    lm, tables,
    ServingConfig(
        n_slots=3, max_len=48,
        scheduler=SchedulerConfig(
            max_prefills_per_step=1, prefill_bucket=8)),
    on_token=lambda rid, tok: streamed.setdefault(rid, []).append(tok))

rng = np.random.default_rng(0)
workload = [(16, 8), (5, 12), (9, 6), (16, 4), (3, 10), (12, 7)]
for prompt_len, gen_len in workload:
    engine.submit(rng.integers(0, lm.cfg.vocab, size=(prompt_len,)),
                  max_new_tokens=gen_len)
    engine.step()  # arrivals interleave with in-flight decodes

completions = engine.run_until_drained()
print("generated (integer-only, ragged arrivals):")
for c in sorted(completions, key=lambda c: c.req_id):
    print(f"  req {c.req_id}: P={c.prompt_len:2d} -> {c.n_generated:2d} "
          f"toks [{c.finish_reason}] ttft={c.ttft * 1e3:6.1f}ms "
          f"{np.asarray(c.tokens)}")
    assert streamed[c.req_id] == c.tokens  # streaming == final record
s = engine.stats()
print(f"{s['throughput_tok_s']:.1f} tok/s, "
      f"mean occupancy {s['mean_occupancy']:.2f}")

# -- multi-device engine: sharded KV arena + async dispatch ----------
mesh = make_serving_mesh(2)
sharded = ServingEngine(lm, tables, ServingConfig(
    n_slots=3, max_len=48, paged=True, page_size=8,
    mesh=mesh, kv_shard=True, dispatch_depth=1,
    scheduler=SchedulerConfig(max_prefills_per_step=1, prefill_bucket=8)))
rng = np.random.default_rng(0)
for prompt_len, gen_len in workload:
    sharded.submit(rng.integers(0, lm.cfg.vocab, size=(prompt_len,)),
                   max_new_tokens=gen_len)
    sharded.step()
for c in sorted(sharded.run_until_drained(), key=lambda c: c.req_id):
    # same prompts (same rng seed) -> sharding, paging, and async
    # dispatch change no tokens: bit-exact with the first engine
    assert c.tokens == streamed[c.req_id]
s2 = sharded.stats()
print(f"mesh {dict(mesh.shape)}: kv_shard={s2['kv_shard']} "
      f"dispatch_depth={s2['dispatch_depth']} "
      f"{s2['throughput_tok_s']:.1f} tok/s")
