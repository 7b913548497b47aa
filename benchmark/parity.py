"""Logits parity of the served engines against the plain reference.

Runs inside the serving process (only it holds the chips and the served
parameters), outside the measured window. For each model of the
configuration: one seeded sequence; the program's own ``forward`` with the
served engine's parameters — a prefill of the first positions, then the
last positions decoded one token at a time through the key/value cache —
against the plain reference the model's entry names (``benchmark/reference/``,
whose docstring is the contract). How long the sequence is, how much of it
is decoded and in how large a cache, the configuration states, for all its
models or for one (``lengths``). Up to ``WHOLE_UP_TO`` positions both sides
take the sequence whole; a longer one goes through the cache ``BLOCK``
positions at a time, as a chunked prefill does, and each block of logits is
compared with the reference's and dropped, so that neither side ever holds
more than ``[BLOCK, V]``: what lets a model be compared at the lengths where
its mechanism acts, beside resident engines. Which of the two is decided by
the length alone (``benchmark/reference/__init__.py`` has both numbers and
why): no file chooses.
"""

from __future__ import annotations

import importlib
import re
import time
from functools import partial

from benchmark.reference import BLOCK, WHOLE_UP_TO

# A "parity" object states the three lengths; absent, these (what every run
# compared until PR 29, drawn in the same order). It may also state a "why"
# in free text. A model's entry may carry a "parity" object of its own: what
# it leaves out falls back to the file's, then to these.
DEFAULT_LENGTHS = {"seq_len": 128, "decoded": 32, "cache_slots": 256}
REFERENCE_NAME = re.compile(r"^[a-z][a-z0-9_]*$")


def lengths(cfg: dict, model: str | None = None) -> dict:
    """The parity lengths of the configuration ``cfg`` (the file's whole
    document), or of one ``model`` of it: ``seq_len`` positions compared, the
    last ``decoded`` of them each through the cache, in a cache of
    ``cache_slots``; held to 0 < decoded < seq_len <= cache_slots <=
    LLMC_MAX_SEQ (where the file's ``env`` states it). What breaks a rule
    stops the process by its key."""
    who = "parity" if model is None else f"{model}: parity"
    stated = {}
    for where in (cfg,) if model is None else (cfg, cfg["models"][model]):
        given = where.get("parity") or {}
        extra = sorted(set(given) - {*DEFAULT_LENGTHS, "why"})
        if extra:
            raise SystemExit(f"{who}: no such length {extra}; have {sorted(DEFAULT_LENGTHS)}")
        stated.update({k: v for k, v in given.items() if k != "why"})
    out = {**DEFAULT_LENGTHS, **stated}
    if not all(type(v) is int for v in out.values()):
        raise SystemExit(f"{who}: lengths are whole numbers, not {out}")
    max_seq = (cfg.get("env") or {}).get("LLMC_MAX_SEQ")
    if not 0 < out["decoded"] < out["seq_len"] <= out["cache_slots"] or (
            max_seq is not None and out["cache_slots"] > int(max_seq)):
        raise SystemExit(
            f"{who}: need 0 < decoded < seq_len <= cache_slots <= LLMC_MAX_SEQ, "
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


def relative_error(got, want, mesh):
    """One relative error a position of ``got`` [n, V] against ``want``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    if mesh is not None:
        want = jax.device_put(want, got.sharding)
    err = jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want, axis=-1)
    return np.asarray(jax.device_get(err), np.float64)


def fresh_cache(engine, slots: int):
    """One row's empty cache of ``slots``, in the engine's type and placed
    as the engine's own."""
    from llm_consensus_tpu.models import init_kv_cache

    cache = init_kv_cache(
        engine.cfg, batch=1, max_seq=slots, dtype=engine._dtype,
        quant=engine.kv_quant,
    )
    if engine._shard_fn is not None:
        cache = engine._shard_fn(cache)
    return cache


def decode_step(engine):
    """The program's ``forward`` on one token through the cache, jitted:
    ``step(params, token [1, 1], cache, pos) -> (logits [V], cache)``."""
    import jax

    from llm_consensus_tpu.models import forward

    @partial(jax.jit, donate_argnums=(2,))
    def step(params, token, cache, pos):
        logits, cache = forward(params, engine.cfg, token, cache, pos,
                                attn_impl=engine.attn_impl, mesh=engine.mesh)
        return logits[0, 0], cache

    return step


def errors_whole(engine, reference, spec: dict, ids, sizes: dict):
    """The sequence whole on both sides: one prefill of the first positions,
    the rest decoded one by one, the reference's ``forward`` over all of it,
    three ``[seq_len, V]`` arrays alive at the comparison. Returns the
    relative error a position and the seconds of either side."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_consensus_tpu.models import forward

    cfg = engine.cfg
    seq_len = sizes["seq_len"]
    n_pre = seq_len - sizes["decoded"]
    t0 = time.monotonic()
    cache = fresh_cache(engine, sizes["cache_slots"])
    place = engine._place

    @partial(jax.jit, donate_argnums=(2,))
    def prefill(params, tokens, cache):
        return forward(params, cfg, tokens, cache, 0,
                       attn_impl=engine.attn_impl, mesh=engine.mesh)

    step = decode_step(engine)
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
    err = relative_error(got, want, engine.mesh)
    return err, t1 - t0, time.monotonic() - t1


def errors_blocked(engine, reference, spec: dict, ids, sizes: dict,
                   block: int):
    """The same comparison, never more than ``block`` positions of logits at
    a time (``BLOCK`` from ``check_engine``; the tests give a smaller one).
    The reference computes its hidden states once (``hidden``) and applies
    its head to a block of them (``logits``); the program feeds the prefill
    through the cache a block at a time at a traced start (one program for
    every full block, what the engine's chunked prefill does; a state-space
    model is told where the block's real tokens end), then the decoded
    positions one by one; each block of either is compared at once and
    dropped. The last block of the prefill may be shorter: a program of its
    own, where ``seq_len - decoded`` is no multiple of ``block``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_consensus_tpu.models import forward

    cfg = engine.cfg
    seq_len = sizes["seq_len"]
    n_pre = seq_len - sizes["decoded"]
    spent = {"program": 0.0, "reference": 0.0}
    errs = []

    def timed(side: str, fn, *args):
        t = time.monotonic()
        out = jax.block_until_ready(fn(*args))
        spent[side] += time.monotonic() - t
        return out

    def put(values):
        return engine._place(np.asarray(values, np.int32))

    def compare(got, lo: int) -> None:
        """``got`` [n, V]: the program's logits of positions ``lo ...``."""
        want = timed(
            "reference", reference.logits, engine.params, spec,
            hidden[lo:lo + got.shape[0]])
        errs.append(relative_error(got.astype(jnp.float32), want, engine.mesh))

    @partial(jax.jit, donate_argnums=(2,))
    def prefill(params, tokens, cache, start):
        end = start + tokens.shape[1]
        logits, cache = forward(
            params, cfg, tokens, cache, start, attn_impl=engine.attn_impl,
            mesh=engine.mesh, row_end=end[None] if cfg.has_ssm else None)
        return logits[0], cache

    # the reference's layers first: its peak is over before the cache is made
    hidden = timed("reference", reference.hidden, engine.params, spec, ids)
    cache = fresh_cache(engine, sizes["cache_slots"])
    step = decode_step(engine)
    for lo in range(0, n_pre, block):
        got, cache = timed(
            "program", prefill, engine.params,
            put(ids[None, lo:min(lo + block, n_pre)]), cache, put(lo))
        compare(got, lo)
        del got
    for lo in range(n_pre, seq_len, block):
        rows = []
        for p in range(lo, min(lo + block, seq_len)):
            row, cache = timed(
                "program", step, engine.params, put(ids[None, p:p + 1]),
                cache, put(p))
            rows.append(row[None])
        compare(jnp.concatenate(rows, axis=0), lo)
    return np.concatenate(errs), spent["program"], spent["reference"]


def routes_since(model: str, before: dict) -> dict:
    """``{phase: [route, ...]}``: the attention routes of the programs that
    ``forward`` traced for ``model`` since the snapshot ``before``: the
    check's own prefill and decode step, which are not the served ones (a
    blocked prefill starts at a traced position)."""
    from llm_consensus_tpu.models.transformer import attention_routes

    return {
        phase: sorted(
            route for route, programs in routes.items()
            if programs > before.get(phase, {}).get(route, 0))
        for phase, routes in attention_routes.snapshot(model).items()
    }


def check_engine(engine, spec: dict, weights: str, seed: int,
                 sizes: dict = DEFAULT_LENGTHS) -> dict:
    """One model: returns the worst per-position relative error (overall
    and over the decoded positions alone), the logit scale and timings.
    ``spec`` is the model's whole entry in the configuration file, ``sizes``
    its lengths (``lengths``). A sequence of at most ``WHOLE_UP_TO``
    positions is compared whole, a longer one in blocks of ``BLOCK`` (the
    record then says ``block``); ``attention`` is the route each of the
    check's own programs traced, by phase."""
    import numpy as np

    from llm_consensus_tpu.models.transformer import attention_routes

    cfg = engine.cfg
    reference = reference_for(cfg.name, spec)
    ids = draw_ids(seed, cfg.name, cfg.vocab_size, sizes["seq_len"])
    n_pre = sizes["seq_len"] - sizes["decoded"]
    blocked = sizes["seq_len"] > WHOLE_UP_TO
    before = attention_routes.snapshot(cfg.name)
    if blocked:
        err, program_s, reference_s = errors_blocked(
            engine, reference, spec, ids, sizes, BLOCK)
    else:
        err, program_s, reference_s = errors_whole(
            engine, reference, spec, ids, sizes)
    finite = bool(np.isfinite(err).all())
    compared = reference.compared(err, n_pre)
    out = {
        "model": cfg.name,
        "reference": reference.__name__.rsplit(".", 1)[-1],
        **sizes,
        **({"block": BLOCK} if blocked else {}),
        "attention": routes_since(cfg.name, before),
        "rel_err_max": float(np.max(err)),
        "rel_err_decoded_max": float(np.max(err[n_pre:])),
        "rel_err_median": float(np.median(err)),
        "finite": finite,
        "stored_as_stated": stored_as_stated(
            engine.params, weights, reference.STORED_LEAVES),
        "tolerance": reference.TOLERANCE,
        "compared": compared,
        "program_s": round(program_s, 3),
        "reference_s": round(reference_s, 3),
    }
    out["ok"] = bool(
        finite and out["stored_as_stated"]
        and all(value <= limit for value, limit in compared.values())
    )
    return out


def stated(cfg: dict) -> dict:
    """``{model: lengths}`` for every model of the configuration ``cfg`` (the
    file's whole document), with everything the file states about the
    comparison checked: the file's lengths, each model's own, each model's
    reference, and that the reference of a sequence too long to take whole
    can compare in blocks. ``server.py`` calls it before it serves."""
    lengths(cfg)
    out = {}
    for name, spec in cfg["models"].items():
        out[name] = lengths(cfg, name)
        module = reference_for(name, spec)
        if out[name]["seq_len"] > WHOLE_UP_TO and not (
                callable(getattr(module, "hidden", None))
                and callable(getattr(module, "logits", None))):
            raise SystemExit(
                f"{name}: parity states {out[name]['seq_len']} positions, over "
                f"the {WHOLE_UP_TO} a sequence is taken whole at, and "
                f"{module.__name__} has no hidden() and logits() to compare "
                "in blocks with")
    return out


def device_memory() -> dict:
    """Bytes in use now and at the process's peak so far, on the fullest
    device; empty where the backend reports none (the CPU)."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    return {
        key: max(s[key] for s in stats)
        for key in ("bytes_in_use", "peak_bytes_in_use")
        if all(key in s for s in stats)
    }


def check_all(provider, cfg: dict, seed: int) -> dict:
    """Every model of the configuration ``cfg`` (the file's whole document),
    on the engines the provider serves, each at its own lengths. ``memory``
    says what the engines and pools held when the check began and whether the
    check raised the process's peak (a peak never falls again: the run's
    ``memory_peak_bytes`` is read before the check is asked for)."""
    before = device_memory()
    results = [
        check_engine(
            provider._engine_for(f"tpu:{name}"), cfg["models"][name],
            cfg["weights"], seed, sizes)
        for name, sizes in stated(cfg).items()
    ]
    return {
        "ok": all(r["ok"] for r in results), "models": results,
        "memory": {"before": before, "after": device_memory()},
    }
