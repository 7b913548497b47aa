"""Logits parity of the served engines against the plain reference.

Runs inside the serving process (only it holds the chips and the served
parameters), outside the measured window. For each model of the
configuration: one seeded sequence; the program's own ``forward`` with the
served engine's parameters — a prefill of the first positions, then the
last positions decoded one token at a time through the key/value cache —
against the plain reference the model's entry names (``benchmark/reference/``,
whose docstring is the contract) over the whole sequence at once. How long
the sequence is, how much of it is decoded and in how large a cache, the
configuration states (``lengths``).
"""

from __future__ import annotations

import importlib
import re
import time
from functools import partial

# A configuration's "parity" object states the three lengths; absent, these
# (what every run compared until PR 29, drawn in the same order).
DEFAULT_LENGTHS = {"seq_len": 128, "decoded": 32, "cache_slots": 256}
REFERENCE_NAME = re.compile(r"^[a-z][a-z0-9_]*$")


def lengths(cfg: dict) -> dict:
    """The configuration's parity lengths: ``seq_len`` positions compared,
    the last ``decoded`` of them each through the cache, in a cache of
    ``cache_slots``; held to 0 < decoded < seq_len <= cache_slots <=
    LLMC_MAX_SEQ (where the file's ``env`` states it)."""
    stated = cfg.get("parity") or {}
    extra = sorted(set(stated) - set(DEFAULT_LENGTHS))
    if extra:
        raise SystemExit(f"parity: no such length {extra}; have {sorted(DEFAULT_LENGTHS)}")
    out = {**DEFAULT_LENGTHS, **stated}
    if not all(type(v) is int for v in out.values()):
        raise SystemExit(f"parity: lengths are whole numbers, not {out}")
    max_seq = (cfg.get("env") or {}).get("LLMC_MAX_SEQ")
    if not 0 < out["decoded"] < out["seq_len"] <= out["cache_slots"] or (
            max_seq is not None and out["cache_slots"] > int(max_seq)):
        raise SystemExit(
            f"parity: need 0 < decoded < seq_len <= cache_slots <= LLMC_MAX_SEQ, "
            f"the file gives {out} under LLMC_MAX_SEQ {max_seq}")
    return out


def reference_for(name: str, spec: dict):
    """The plain reference a model's entry names (``benchmark/reference/``;
    its contract is that package's docstring)."""
    module = spec.get("reference", "decoder")
    if not isinstance(module, str) or not REFERENCE_NAME.match(module):
        raise SystemExit(f"{name}: reference {module!r} is not a module name")
    try:
        return importlib.import_module(f"benchmark.reference.{module}")
    except ModuleNotFoundError as err:
        if err.name != f"benchmark.reference.{module}":
            raise
        raise SystemExit(
            f"{name}: no benchmark/reference/{module}.py for the reference "
            "the file names") from None


def stored_as_stated(params, weights: str, leaves) -> bool:
    """The served tree is stored in the precision the file states, by the
    leaves the reference module names (``STORED_LEAVES``)."""
    found = []
    for path in leaves:
        leaf = params
        for key in path:
            leaf = leaf[key]
        found.append(leaf)
    if weights == "int8":
        return all(isinstance(w, dict) and "q8" in w for w in found)
    return all(
        not isinstance(w, dict) and str(w.dtype) == weights for w in found
    )


def draw_ids(seed: int, model: str, vocab_size: int, seq_len: int):
    """The sequence one model is compared on, from the run's seed."""
    import numpy as np

    rng = np.random.default_rng([seed, len(model)])
    return rng.integers(0, vocab_size, seq_len, dtype=np.int64)


def check_engine(engine, spec: dict, weights: str, seed: int,
                 sizes: dict = DEFAULT_LENGTHS) -> dict:
    """One model: returns the worst per-position relative error (overall
    and over the decoded positions alone), the logit scale and timings.
    ``spec`` is the model's whole entry in the configuration file."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_consensus_tpu.models import forward, init_kv_cache

    cfg = engine.cfg
    reference = reference_for(cfg.name, spec)
    seq_len = sizes["seq_len"]
    ids = draw_ids(seed, cfg.name, cfg.vocab_size, seq_len)
    n_pre = seq_len - sizes["decoded"]
    t0 = time.monotonic()

    cache = init_kv_cache(
        cfg, batch=1, max_seq=sizes["cache_slots"], dtype=engine._dtype,
        quant=engine.kv_quant,
    )
    if engine._shard_fn is not None:
        cache = engine._shard_fn(cache)
    place = engine._place

    @partial(jax.jit, donate_argnums=(2,))
    def prefill(params, tokens, cache):
        return forward(params, cfg, tokens, cache, 0,
                       attn_impl=engine.attn_impl, mesh=engine.mesh)

    @partial(jax.jit, donate_argnums=(2,))
    def step(params, token, cache, pos):
        logits, cache = forward(params, cfg, token, cache, pos,
                                attn_impl=engine.attn_impl, mesh=engine.mesh)
        return logits[0, 0], cache

    logits, cache = prefill(
        engine.params, place(np.asarray(ids[None, :n_pre], np.int32)), cache
    )
    rows = [logits[0]]
    for p in range(n_pre, seq_len):
        row, cache = step(
            engine.params, place(np.asarray(ids[None, p:p + 1], np.int32)),
            cache, place(np.asarray(p, np.int32)),
        )
        rows.append(row[None])
    got = jnp.concatenate(rows, axis=0).astype(jnp.float32)
    del cache
    t1 = time.monotonic()

    want = reference.forward(engine.params, spec, ids)
    if engine.mesh is not None:
        want = jax.device_put(want, got.sharding)
    err = jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want, axis=-1)
    err = np.asarray(jax.device_get(err), np.float64)
    finite = bool(np.isfinite(err).all())
    compared = reference.compared(err, n_pre)
    out = {
        "model": cfg.name,
        "reference": reference.__name__.rsplit(".", 1)[-1],
        **sizes,
        "rel_err_max": float(np.max(err)),
        "rel_err_decoded_max": float(np.max(err[n_pre:])),
        "rel_err_median": float(np.median(err)),
        "finite": finite,
        "stored_as_stated": stored_as_stated(
            engine.params, weights, reference.STORED_LEAVES),
        "tolerance": reference.TOLERANCE,
        "compared": compared,
        "program_s": round(t1 - t0, 3),
        "reference_s": round(time.monotonic() - t1, 3),
    }
    out["ok"] = bool(
        finite and out["stored_as_stated"]
        and all(value <= limit for value, limit in compared.values())
    )
    return out


def check_all(provider, cfg: dict, seed: int) -> dict:
    """Every model of the configuration ``cfg`` (the file's whole document),
    on the engines the provider serves."""
    sizes = lengths(cfg)
    results = [
        check_engine(
            provider._engine_for(f"tpu:{name}"), spec, cfg["weights"], seed, sizes)
        for name, spec in cfg["models"].items()
    ]
    return {"ok": all(r["ok"] for r in results), "models": results}
