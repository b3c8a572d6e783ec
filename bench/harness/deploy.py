"""Build the program's model and its integer tables for a configuration.

The first run in a checkout makes the float weights on the device (the
configuration's reference module, `program_params`), has the program
calibrate and deploy them (`DecoderLM.calibrate`, `DecoderLM.deploy`:
the tables are built on the host), and saves the tables under
`bench/.state/`, one file per key, written atomically.  Every later run
loads that file.  The key holds the configuration's name and sizes,
`max_len`, `kv_bits` and a digest of the program's source and of the
reference module, so a change to either deploys afresh.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import time
from pathlib import Path

import numpy as np

from harness.spec import BENCH, ROOT

STATE = BENCH / ".state"
CALIB_BATCH, CALIB_LEN = 4, 64  # the calibration sample deploy_model uses


def arch_config(config: dict, ref):
    """The program's ArchConfig for `config`, cut as the file says and
    registered under the configuration's own name, and the sizes of
    reference `ref`; raises where the two are not one model."""
    from repro.configs.base import get_config, register

    prog = config["program"]
    base = get_config(prog["arch"])
    cfg = dataclasses.replace(base, name=config["name"],
                              **prog.get("overrides", {}))
    register(cfg)
    dims = ref.Dims.from_config(config["model"])
    ref.check_program(cfg, dims)
    return cfg, dims


def _digest(config: dict, ref, max_len: int, kv_bits: int) -> str:
    h = hashlib.sha256()
    h.update(json.dumps([config["model"], config["program"],
                         config["weights_seed"], max_len, kv_bits],
                        sort_keys=True).encode())
    for f in sorted((ROOT / "src" / "repro").rglob("*.py")):
        h.update(str(f.relative_to(ROOT)).encode())
        h.update(f.read_bytes())
    f = Path(ref.__file__)
    h.update(f.name.encode())
    h.update(f.read_bytes())
    return h.hexdigest()[:16]


def _save(path: Path, tables):
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tables)
    paths, arrays = [], {}
    for i, (kp, leaf) in enumerate(flat):
        steps = []
        for k in kp:
            if isinstance(k, jax.tree_util.DictKey):
                steps.append(["d", k.key])
            elif isinstance(k, jax.tree_util.SequenceKey):
                steps.append(["i", k.idx])
            else:
                raise TypeError(f"unexpected tables node {k!r}")
        # a Python scalar goes back as one: jnp.asarray types it weakly
        kind = {float: "float", int: "int"}.get(type(leaf), "array")
        paths.append([steps, kind])
        arrays[f"l{i}"] = np.asarray(leaf)
    arrays["tree"] = np.frombuffer(json.dumps(paths).encode(), np.uint8)
    STATE.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f"{path.name}.{os.getpid()}.tmp")
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, path)


def _load(path: Path):
    with np.load(path, allow_pickle=False) as z:
        paths = json.loads(z["tree"].tobytes().decode())
        leaves = [z[f"l{i}"] for i in range(len(paths))]
    root: dict = {}
    for (steps, kind), leaf in zip(paths, leaves):
        if kind != "array":
            leaf = {"float": float, "int": int}[kind](leaf)
        node = root
        for j, (step, k) in enumerate(steps):
            last = j == len(steps) - 1
            nxt = steps[j + 1][0] if not last else None
            if step == "i":
                while len(node) <= k:
                    node.append(None)
            if last:
                node[k] = leaf
                break
            empty = [] if nxt == "i" else {}
            if step == "d":
                node = node.setdefault(k, empty)
            else:
                if node[k] is None:
                    node[k] = empty
                node = node[k]
    return root


def _deploy(config, ref, cfg, dims, max_len):
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import SyntheticConfig, SyntheticStream
    from repro.models.lm import DecoderLM

    lm = DecoderLM(cfg, max_seq=max_len)
    key = jax.random.PRNGKey(config["weights_seed"])
    p = jax.jit(ref.program_params, static_argnums=(1, 2))(
        key, dims, cfg.vocab_padded)
    want = jax.eval_shape(lm.init, key)
    if jax.tree.structure(want) != jax.tree.structure(p) or any(
            a.shape != b.shape
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(p))):
        raise ValueError("weights do not match DecoderLM.init's tree")
    stream = SyntheticStream(SyntheticConfig(
        vocab=cfg.vocab, seq_len=min(CALIB_LEN, max_len - 1),
        global_batch=CALIB_BATCH))
    calib = lm.calibrate(p, jnp.asarray(stream.batch(0))[:, :-1])
    p = jax.tree.map(np.asarray, p)  # the device copy goes
    tables = lm.deploy(p, calib)
    del p
    return tables


def tables(config: dict, ref, max_len: int, kv_bits: int, log):
    """-> (lm, device tables, seconds spent deploying or 0.0), for
    `config` with its reference module `ref`."""
    import jax
    import jax.numpy as jnp

    from repro.models.lm import DecoderLM

    cfg, dims = arch_config(config, ref)
    path = STATE / (f"{config['name']}-{max_len}-kv{kv_bits}-"
                    f"{_digest(config, ref, max_len, kv_bits)}.npz")
    deploy_s = 0.0
    if path.is_file():
        host = _load(path)
    else:
        t0 = time.perf_counter()
        host = _deploy(config, ref, cfg, dims, max_len)
        _save(path, host)
        deploy_s = time.perf_counter() - t0
        log(f"deploy_s {deploy_s} (tables saved to {path.name})")
    dev = jax.tree.map(jnp.asarray, host,
                       is_leaf=lambda x: isinstance(x, np.ndarray))
    return DecoderLM(cfg, max_seq=max_len), dev, deploy_s
