import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))
# ^ MUST precede any jax import/init (device count locks on first init).

"""Multi-pod dry-run driver (assignment deliverable e).

For one (arch x shape x mesh) cell:
  lower -> compile -> memory_analysis + cost_analysis + collective-byte
  parse of the optimized HLO -> roofline terms -> JSON record.

Run:
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite_3_2b \
      --shape train_4k --mesh pod --out results/
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multipod
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import numpy as np

from repro.configs.base import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import SHAPES, cell_supported, lower_cell

# --- TPU v5e hardware model (assignment constants) ---
PEAK_BF16 = 197e12        # FLOP/s per chip
PEAK_INT8 = 394e12        # OPS/s per chip (MXU int8 2x)
HBM_BW = 819e9            # B/s per chip
ICI_BW = 50e9             # B/s per link (~per direction); v5e: 4 links/chip

_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "pred": 1, "f64": 8, "s64": 8, "u64": 8,
}


RECORD_VERSION = 3  # v3: final landed framework (post-§Perf)


def _split_computations(hlo_text: str):
    """-> {comp_name: body_text} for every HLO computation."""
    comps = {}
    cur, buf = None, []
    hdr = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
    for line in hlo_text.splitlines():
        m = hdr.match(line)
        if m and not line.startswith(" "):
            if cur is not None:
                comps[cur] = "\n".join(buf)
            cur = m.group(1)
            buf = [line]
        elif cur is not None:
            buf.append(line)
    if cur is not None:
        comps[cur] = "\n".join(buf)
    return comps


def _loop_multipliers(comps: dict) -> dict:
    """Execution-count multiplier per computation.

    lax.scan lowers to `while(condition=%c, body=%b)`; ops inside %b (and
    computations it calls) execute trip-count times but appear once in
    the module text.  The trip count is recovered from the largest
    integer constant in the condition computation (the loop bound).
    Nested loops multiply.
    """
    # call edges: comp -> comps it references
    refs = {
        name: set(
            re.findall(
                r"(?:calls|body|condition|to_apply)=%?([\w\.\-]+)", text
            )
        )
        for name, text in comps.items()
    }
    # while ops: (body_comp, cond_comp)
    mult = dict.fromkeys(comps, 1)

    def trip(cond_name):
        text = comps.get(cond_name, "")
        consts = [int(c) for c in re.findall(r"constant\((\d+)\)", text)]
        return max(consts) if consts else 1

    # propagate: BFS from entry computations, multiplying at while edges
    entry = [
        n for n in comps if n.startswith("main") or "ENTRY" in comps[n][:40]
    ] or list(comps)[:1]
    seen = {}

    def visit(name, m):
        if seen.get(name, 0) >= m:
            return
        seen[name] = m
        text = comps.get(name, "")
        for w in re.finditer(
                r"while\([^\n]*?condition=%?([\w\.\-]+)"
                r"[^\n]*?body=%?([\w\.\-]+)"
                r"|while\([^\n]*?body=%?([\w\.\-]+)"
                r"[^\n]*?condition=%?([\w\.\-]+)",
                text):
            cond = w.group(1) or w.group(4)
            body = w.group(2) or w.group(3)
            t = max(trip(cond), 1)
            visit(body, m * t)
            visit(cond, m * t)
        for r in refs.get(name, ()):  # non-while calls inherit multiplier
            if r not in (None, name):
                visit(r, m)

    for e in entry:
        visit(e, 1)
    return seen or mult


def parse_collectives(hlo_text: str) -> dict:
    """Sum result bytes of every collective op in optimized HLO, bucketed
    by op kind and weighted by enclosing-loop trip counts (a collective
    inside the L-layer scan executes L times per step).  Wire-bytes per
    device are derived with ring-collective cost models in roofline()."""
    comps = _split_computations(hlo_text)
    mults = _loop_multipliers(comps)
    out = {
        "all-reduce": 0,
        "all-gather": 0,
        "reduce-scatter": 0,
        "all-to-all": 0,
        "collective-permute": 0,
    }
    counts = dict.fromkeys(out, 0)
    for name, text in comps.items():
        m_exec = mults.get(name, 1)
        for m in _COLL_RE.finditer(text):
            _, dtype, dims, kind = m.groups()
            nbytes = _DTYPE_BYTES.get(dtype)
            if nbytes is None:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            out[kind] += n * nbytes * m_exec
            counts[kind] += m_exec
    return {"bytes": out, "counts": counts}


def roofline(
    arch: str,
    shape: str,
    *,
    flops: float,
    hbm_bytes: float,
    coll: dict,
    n_chips: int,
    integer_path: bool,
) -> dict:
    """Three roofline terms in seconds-per-step.

    compiled.cost_analysis() / the optimized HLO describe the PER-DEVICE
    partitioned program, so flops / bytes / collective shard bytes are
    already per-chip; only the analytic global MODEL_FLOPS is divided by
    the chip count.  XLA undercounts integer-MXU MACs (and some fused
    float MACs), so the analytic per-chip share is the compute floor.
    """
    cfg = get_config(arch)
    s = SHAPES[shape]
    peak = PEAK_INT8 if integer_path else PEAK_BF16
    D_tokens = s["batch"] * (s["seq"] if s["kind"] != "decode" else 1)
    n_active = cfg.active_param_count()
    # MODEL_FLOPS: 6*N_active*D train / 2*N_active*D serve
    model_flops = (6 if s["kind"] == "train" else 2) * n_active * D_tokens
    t_compute = max(flops, model_flops / n_chips) / peak
    t_memory = hbm_bytes / HBM_BW
    # ring-model wire bytes (per device): all-reduce = 2x shard bytes
    wire = (coll["bytes"]["all-reduce"] * 2.0
            + coll["bytes"]["all-gather"]
            + coll["bytes"]["reduce-scatter"]
            + coll["bytes"]["all-to-all"]
            + coll["bytes"]["collective-permute"])
    t_coll = wire / ICI_BW
    dominant = max(
        ("compute", t_compute), ("memory", t_memory),
        ("collective", t_coll), key=lambda kv: kv[1])[0]
    return {
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": model_flops, "hlo_flops": flops,
        "useful_ratio": model_flops / max(flops, 1.0),
        "wire_bytes_per_dev_total": wire,
    }


# --variant keys: lower_cell's keywords, with their types
VARIANT_KEYS = {"microbatches": int, "zero2": bool, "weight_stationary": bool}


def parse_variant(spec: str) -> dict:
    """'microbatches=4,zero2=1' -> lower_cell keywords; unknown keys
    raise KeyError."""
    out = {}
    for item in filter(None, spec.split(",")):
        k, v = item.split("=")
        if VARIANT_KEYS[k] is bool:
            out[k] = v.lower() in ("1", "true", "yes")
        else:
            out[k] = int(v)
    return out


def run_cell(
    arch: str,
    shape: str,
    mesh_kind: str,
    out_dir: Path,
    variant: dict | None = None,
) -> dict:
    cfg = get_config(arch)
    reason = cell_supported(cfg, shape)
    rec = {
        "arch": arch,
        "shape": shape,
        "mesh": mesh_kind,
        "variant": variant or {},
        "time": time.strftime("%F %T"),
    }
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    n_chips = int(np.prod(mesh.devices.shape))
    t0 = time.time()
    lowered, lm = lower_cell(arch, shape, mesh, **(variant or {}))
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    flops = float(cost.get("flops", 0.0))
    hbm_bytes = float(cost.get("bytes accessed", 0.0))
    integer_path = SHAPES[shape]["kind"] != "train"
    rl = roofline(
        arch,
        shape,
        flops=flops,
        hbm_bytes=hbm_bytes,
        coll=coll,
        n_chips=n_chips,
        integer_path=integer_path,
    )
    rec.update({
        "status": "ok",
        "version": RECORD_VERSION,
        "n_chips": n_chips,
        "lower_s": round(t1 - t0, 1),
        "compile_s": round(t2 - t1, 1),
        "memory": {
            "argument_bytes_per_dev": mem.argument_size_in_bytes,
            "output_bytes_per_dev": mem.output_size_in_bytes,
            "temp_bytes_per_dev": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "cost": {"flops": flops, "bytes_accessed": hbm_bytes},
        "collectives": coll,
        "roofline": rl,
    })
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=[a for a in ARCH_IDS if a != "nemo_cnn"])
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", choices=["pod", "multipod"], default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--variant", default="",
                    help="k=v,k=v lower_cell overrides: "
                         + ", ".join(VARIANT_KEYS))
    ap.add_argument("--tag", default="", help="suffix for the output json")
    args = ap.parse_args()
    variant = parse_variant(args.variant)

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    cells = []
    if args.all:
        for a in ARCH_IDS:
            if a == "nemo_cnn":
                continue
            for sh in SHAPES:
                cells.append((a, sh))
    else:
        assert args.arch and args.shape
        cells = [(args.arch, args.shape)]

    for arch, shape in cells:
        tag = f"{arch}__{shape}__{args.mesh}" + (
            f"__{args.tag}" if args.tag else "")
        path = out_dir / f"{tag}.json"
        if path.exists():
            old = json.loads(path.read_text())
            fresh = old.get("status") == "skipped" or (
                old.get("status") == "ok"
                and old.get("version", 0) >= RECORD_VERSION
            )
            if fresh:
                print(f"[skip existing] {tag}")
                continue
        print(f"[run] {tag}", flush=True)
        try:
            rec = run_cell(arch, shape, args.mesh, out_dir, variant=variant)
        except Exception as e:  # record failures — they are bugs to fix
            rec = {
                "arch": arch,
                "shape": shape,
                "mesh": args.mesh,
                "status": "error",
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-2000:],
            }
        path.write_text(json.dumps(rec, indent=1))
        dom = (
            f" dominant={rec['roofline']['dominant']}"
            if rec.get("roofline")
            else ""
        )
        err = (
            f" err={rec.get('error', '')[:200]}"
            if rec["status"] == "error"
            else ""
        )
        print(f"  -> {rec['status']}" + dom + err, flush=True)


if __name__ == "__main__":
    main()
