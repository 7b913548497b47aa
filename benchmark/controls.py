"""The controls of a reference's limits, through the harness's own comparison.

A reference's ``compared()`` states limits that were read between sound runs
and controls (``benchmark/reference/__init__.py``). This entry reads both
again, on an engine of its own outside any cell, through the SAME path a run
takes (``parity.check_engine``: the program's ``forward`` through the cache,
the reference the model's entry names, ``compared()``, ``ok``):

    chiprun --timeout 1500 -- python3 benchmark/controls.py \\
        --config trinity-mini-pp8-trio-bf16 --model trinity-mini --seeds 6

For each seed the model as the file states it (which must read ``ok``), then
each control of its reference's family (``CONTROLS``), each of which must
read ``ok`` false BY A LIMIT OF ``compared()`` (finite, and stored as the
control states): the program under a changed ``ModelConfig`` over the same
tree, the program one precision lower, or the program fed another token than
the reference. One JSON line a reading (``what``, ``seed``, ``ok``,
``compared`` as ``{name: [value, limit]}``, ``failed`` the names over their
limit), the same lines in ``chiprun_out/controls/<config>.<model>.jsonl``,
and a last line ``{"ok": ..}`` that says whether every sound reading was
``ok`` and every control's was not; the exit code is 0 only then. The lengths
are the model's own ``parity`` lengths in the file: the lengths its limits
were read at.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import os
import sys
import types

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# By reference module: what each control changes. ``cfg``: fields of the
# program's ModelConfig, over the same engine's tree; ``engine`` and ``env``:
# another engine (after the others, so that one engine is resident at a
# time), compared as stored in ``weights``; ``fault``: "token" hands the
# program another token than the reference at every decoded position.
CONTROLS = {
    "afmoe": {
        "no-window": {"cfg": {"sliding_window": 1 << 20}},
        "rotary-on-full-layers": {"cfg": {"rotary": True}},
        "another-token": {"fault": "token"},
        "one-precision-lower": {
            "engine": {"quant": "int8"}, "env": {"LLMC_W8A8": "1"},
            "weights": "int8"},
    },
}


def view(engine, **changes):
    """``engine`` as ``parity`` reads it, under a changed ModelConfig."""
    return types.SimpleNamespace(
        cfg=dataclasses.replace(engine.cfg, **changes),
        **{k: getattr(engine, k) for k in (
            "params", "attn_impl", "mesh", "_place", "_dtype", "kv_quant",
            "_shard_fn")})


class another_token:
    """While entered, the program's ``forward`` reads token + 1 wherever it
    is handed one position (every decoded position of the comparison)."""

    def __enter__(self):
        import llm_consensus_tpu.models as models

        self.models, self.forward = models, models.forward

        def shifted(params, cfg, tokens, *args, **kw):
            if tokens.shape[1] == 1:
                tokens = (tokens + 1) % cfg.vocab_size
            return self.forward(params, cfg, tokens, *args, **kw)

        models.forward = shifted

    def __exit__(self, *exc):
        self.models.forward = self.forward


def read(config: dict, model: str, seeds, *, dtype=None, max_seq=None,
         emit=lambda line: None) -> dict:
    """Sound and control readings of ``model`` of the configuration
    ``config`` (the file's whole document) on ``seeds``; ``dtype`` and the
    stored type default to the file's ``weights``. Returns the last line."""
    import jax.numpy as jnp

    from benchmark import parity, server
    from llm_consensus_tpu.engine.engine import Engine

    spec = config["models"][model]
    sizes = parity.lengths(config, model)
    reference = parity.reference_for(model, spec).__name__.rsplit(".", 1)[-1]
    controls = CONTROLS[reference]
    stored = jnp.dtype(dtype).name if dtype is not None else config["weights"]
    how = dict(max_seq=max_seq or sizes["cache_slots"], seed=0,
               dtype=dtype if dtype is not None else jnp.dtype(stored))
    counts = {"sound": [0, 0], **{name: [0, 0] for name in controls}}

    def one(what, engine, seed, weights, fault=None):
        with another_token() if fault == "token" else contextlib.nullcontext():
            out = parity.check_engine(engine, spec, weights, seed, sizes)
        failed = sorted(
            name for name, (value, limit) in out["compared"].items()
            if not value <= limit)
        line = {
            "what": what, "seed": seed, "ok": out["ok"], "failed": failed,
            "compared": out["compared"], "finite": out["finite"],
            "stored_as_stated": out["stored_as_stated"],
            "program_s": out["program_s"], "reference_s": out["reference_s"],
            **{k: out[k] for k in ("seq_len", "decoded", "cache_slots")}}
        emit(line)
        held = out["finite"] and out["stored_as_stated"]
        counts[what][1] += 1
        # sound: ok; a control: refused, and by a limit of compared()
        counts[what][0] += out["ok"] if what == "sound" else bool(held and failed)

    engine = Engine(server.model_config(model, spec), **how)
    for seed in seeds:
        one("sound", engine, seed, stored)
        for name, c in controls.items():
            if "engine" not in c:
                one(name, view(engine, **c.get("cfg", {})), seed, stored,
                    c.get("fault"))
    cfg = engine.cfg
    del engine
    gc.collect()
    for name, c in controls.items():
        if "engine" not in c:
            continue
        before = {k: os.environ.get(k) for k in c.get("env", {})}
        os.environ.update(c.get("env", {}))
        try:
            other = Engine(cfg, **{**how, **c["engine"]})
            for seed in seeds:
                one(name, other, seed, c.get("weights", stored))
            del other
            gc.collect()
        finally:
            for k, v in before.items():
                os.environ.pop(k) if v is None else os.environ.update({k: v})
    last = {
        "ok": all(good == n for good, n in counts.values()),
        "model": model, "reference": reference, **sizes,
        "sound_ok": counts.pop("sound"), "controls_refused": counts,
        "memory": parity.device_memory()}
    emit(last)
    return last


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--model", required=True)
    ap.add_argument("--seeds", type=int, default=6, help="how many, drawn from --seed")
    ap.add_argument("--seed", type=int, default=48)
    args = ap.parse_args(argv)
    import numpy as np

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", f"{args.config}.json")) as f:
        config = json.load(f)
    for key, value in (config.get("env") or {}).items():
        os.environ.setdefault(key, value)
    seeds = [int(s) for s in np.random.default_rng(args.seed).integers(
        0, 2**31 + 2**28, args.seeds)]
    out_dir = os.path.join(root, "chiprun_out", "controls")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{args.config}.{args.model}.jsonl"), "w") as log:
        def emit(line):
            print(json.dumps(line), flush=True)
            log.write(json.dumps(line) + "\n")
            log.flush()

        return 0 if read(config, args.model, seeds, emit=emit)["ok"] else 1


if __name__ == "__main__":
    raise SystemExit(main())
