"""Logits parity of the served engines against the plain reference.

Runs inside the serving process (only it holds the chips and the served
parameters), outside the measured window. For each model of the
configuration: one seeded sequence; the program's own ``forward`` with the
served engine's parameters — a prefill of the first positions, then the
last positions decoded one token at a time through the key/value cache —
against ``benchmark/reference`` over the whole sequence at once.
"""

from __future__ import annotations

import time
from functools import partial

SEQ_LEN = 128
DECODED = 32  # the last positions, each through the cache
CACHE_SLOTS = 256


def _stated_storage_ok(params, weights: str) -> bool:
    """The served tree is stored in the precision the file states."""
    leaves = [params["layers"][k] for k in ("wq", "w_up", "w_down")]
    if weights == "int8":
        return all(isinstance(w, dict) and "q8" in w for w in leaves)
    return all(
        not isinstance(w, dict) and str(w.dtype) == weights for w in leaves
    )


def check_engine(engine, shape: dict, weights: str, seed: int) -> dict:
    """One model: returns the worst per-position relative error (overall
    and over the decoded positions alone), the logit scale and timings."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference import decoder
    from llm_consensus_tpu.models import forward, init_kv_cache

    cfg = engine.cfg
    rng = np.random.default_rng([seed, len(cfg.name)])
    ids = rng.integers(0, cfg.vocab_size, SEQ_LEN, dtype=np.int64)
    n_pre = SEQ_LEN - DECODED
    t0 = time.monotonic()

    cache = init_kv_cache(
        cfg, batch=1, max_seq=CACHE_SLOTS, dtype=engine._dtype,
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
    for p in range(n_pre, SEQ_LEN):
        row, cache = step(
            engine.params, place(np.asarray(ids[None, p:p + 1], np.int32)),
            cache, place(np.asarray(p, np.int32)),
        )
        rows.append(row[None])
    got = jnp.concatenate(rows, axis=0).astype(jnp.float32)
    del cache
    t1 = time.monotonic()

    want = decoder.forward(engine.params, shape, ids)
    if engine.mesh is not None:
        want = jax.device_put(want, got.sharding)
    err = jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want, axis=-1)
    err = np.asarray(jax.device_get(err), np.float64)
    finite = bool(np.isfinite(err).all())
    out = {
        "model": cfg.name,
        "rel_err_max": float(np.max(err)),
        "rel_err_decoded_max": float(np.max(err[n_pre:])),
        "rel_err_median": float(np.median(err)),
        "finite": finite,
        "stored_as_stated": _stated_storage_ok(engine.params, weights),
        "tolerance": decoder.TOLERANCE,
        "program_s": round(t1 - t0, 3),
        "reference_s": round(time.monotonic() - t1, 3),
    }
    out["ok"] = bool(
        finite and out["stored_as_stated"]
        and out["rel_err_max"] <= decoder.TOLERANCE
    )
    return out


def check_all(provider, models: dict, weights: str, seed: int) -> dict:
    """Every model of the configuration, on the engines the provider
    serves."""
    results = [
        check_engine(provider._engine_for(f"tpu:{name}"), shape, weights, seed)
        for name, shape in models.items()
    ]
    return {"ok": all(r["ok"] for r in results), "models": results}
