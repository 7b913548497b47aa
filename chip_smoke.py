#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the serving path end to end, through the entry points a user calls,
at the full published width of three supported models, and fails loudly
when anything ran somewhere other than where it claims:

  phase 1  ``python -m llm_consensus_tpu serve`` with the panel and judge
           below; waits for /healthz; POSTs a few /v1/consensus requests
           (JSON and SSE, two of them concurrent so the continuous batcher
           co-schedules rows); reads /statsz and /metricsz; SIGTERM, and
           waits for a clean drain.
  phase 2  the one-shot CLI on the same panel, a fresh process that must
           find compile-cache entries phase 1 left.

  --chips 4  runs instead ONLY the cross-chip path and what it is compared
           with, in one child that owns all four chips: the same requests
           on the planned placement (1B and 0.5B on a chip each, the 3B
           judge tensor-sharded over two) and with every model on one chip.

This process never imports JAX: a chip belongs to one process at a time,
so the phases are children, strictly one after another. Weights are the
provider's seeded random init — no checkpoint, no network. Every earlier
line of stdout is one JSON object of observations (cold and warm wall
times, attention path per model, compile-cache entries, peak device
memory) — observations, not metrics: nothing here gates on a time. The
LAST line is ``{"ok": true, "device": {"platform": "tpu", "kind": "...",
"count": 1}}`` with the device as the serving process itself reported it,
and the exit code is 0 only then.

Rehearse on the CPU with tiny presets (walks every phase, then fails
because the platform is not ``tpu``):

    JAX_PLATFORMS=cpu python chip_smoke.py \\
        --models tpu:tiny-llama,tpu:tiny-qwen2 --judge tpu:tiny-llama
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.abspath(__file__))
DEFAULT_MODELS = "tpu:llama-3.2-1b,tpu:qwen2.5-0.5b,tpu:llama-3.2-3b"
DEFAULT_JUDGE = "tpu:llama-3.2-3b"
# The presets' 32k-131k windows would ask for a KV cache larger than the
# chip; 2048 slots × 4 rows is under 2 GB next to ~9.5 GB of weights.
MAX_SEQ = 2048
MAX_BATCH = 4
MAX_TOKENS = 64  # four 16-step decode chunks per stream
# Sized for a cold compile of three models (observed: 75 s for the first
# request on a cold cache): a slow first compile must not turn into a
# best-effort "model timed out" warning.
REQUEST_TIMEOUT_S = 420
# A request in flight this long gets the server's own account of what it
# is doing (/statsz + a flight-recorder dump) saved next to the logs.
STALL_S = 150
START_TIMEOUT_S = 180
DRAIN_TIMEOUT_S = 180
# Attention path forward() must report per phase at the default widths:
# dh = 128 takes both fused kernels, dh = 64 the fused prefill and XLA
# decode (the decode kernel needs 128 lanes of head_dim).
EXPECTED_PATHS = {
    "llama-3.2-3b": {"prefill": "pallas", "decode": "pallas"},
    "llama-3.2-1b": {"prefill": "pallas", "decode": "xla"},
    "qwen2.5-0.5b": {"prefill": "pallas", "decode": "xla"},
}
PROMPTS = (
    "Compare tensor parallelism and pipeline parallelism for serving a "
    "large language model on a small accelerator pod.",
    "What limits the throughput of batched decoding on one accelerator?",
    "When does a key-value cache stop paying for itself?",
    "Explain why a judge model sees a longer prompt than its panel.",
)
_PACKAGE_WARNING = re.compile(r"llm_consensus_tpu[^\n:]*:\d+: RuntimeWarning")
_CACHE_HIT = re.compile(r"Persistent compilation cache hit for '[^']*' with key '([^']+)'")


def emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


# -- HTTP client (stdlib) ----------------------------------------------------


def _request(port: int, method: str, path: str, body=None,
             timeout: float = 30.0):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        payload = None if body is None else json.dumps(body).encode()
        headers = {"Content-Type": "application/json"} if payload else {}
        conn.request(method, path, body=payload, headers=headers)
        resp = conn.getresponse()
        return resp.status, resp.read().decode("utf-8", errors="replace")
    finally:
        conn.close()


def get_json(port: int, path: str) -> dict:
    status, text = _request(port, "GET", path)
    if status != 200:
        raise RuntimeError(f"GET {path} -> {status}: {text[:200]}")
    return json.loads(text)


def consensus(port: int, prompt: str, stream: bool) -> dict:
    """One POST /v1/consensus; returns the result envelope plus the wall
    time (and, for SSE, the number of chunk events seen)."""
    body = {
        "prompt": prompt, "max_tokens": MAX_TOKENS, "stream": stream,
        "timeout": REQUEST_TIMEOUT_S, "priority": "normal",
    }
    t0 = time.monotonic()
    status, text = _request(
        port, "POST", "/v1/consensus", body, timeout=REQUEST_TIMEOUT_S + 60
    )
    wall = time.monotonic() - t0
    if status != 200:
        raise RuntimeError(f"POST /v1/consensus -> {status}: {text[:300]}")
    if not stream:
        doc = json.loads(text)
        doc["_wall_s"] = wall
        return doc
    events = []
    for frame in text.split("\n\n"):
        name = data = None
        for line in frame.splitlines():
            if line.startswith("event: "):
                name = line[len("event: "):]
            elif line.startswith("data: "):
                data = line[len("data: "):]
        if name and data is not None:
            events.append((name, json.loads(data)))
    done = [d for n, d in events if n == "done"]
    if len(done) != 1:
        names = [n for n, _ in events]
        raise RuntimeError(f"SSE stream ended without one done event: {names[-5:]}")
    doc = done[0]
    doc["_wall_s"] = wall
    doc["_chunks"] = sum(1 for n, _ in events if n == "chunk")
    return doc


def check_result(doc: dict, models: list, where: str, failures: list) -> int:
    """The repo's own success contract for one consensus result: every
    panel model answered, nothing failed or warned, a consensus exists.
    Returns the panel tokens generated."""
    if doc.get("failed_models"):
        failures.append(f"{where}: failed_models {doc['failed_models']}")
    if doc.get("warnings"):
        failures.append(f"{where}: warnings {doc['warnings']}")
    answered = {r.get("model"): r for r in doc.get("responses", [])}
    for m in models:
        if not (answered.get(m) or {}).get("content"):
            failures.append(f"{where}: empty answer from {m}")
    if not doc.get("consensus"):
        failures.append(f"{where}: empty consensus")
    return sum(r.get("tokens") or 0 for r in doc.get("responses", []))


def save_stall_report(port: int, workdir: str, label: str) -> None:
    """What the server says it is doing while ``label`` has been in flight
    for STALL_S: /statsz, and a flight-recorder dump (its path is in the
    reply; the ring holds the scheduler's last spans)."""
    report: dict = {"label": label, "after_s": STALL_S}
    try:
        report["statsz"] = get_json(port, "/statsz")
        report["blackbox"] = _request(port, "POST", "/debugz/blackbox", {})[1]
    except Exception as err:  # noqa: BLE001 — diagnostics only
        report["error"] = f"{type(err).__name__}: {err}"
    with open(os.path.join(workdir, f"stall-{label}.json"), "w") as f:
        json.dump(report, f, indent=1)


def drive_requests(port: int, models: list, failures: list,
                   where: str = "serve", workdir: str = "") -> dict:
    """The request script shared by both modes: one cold JSON request, a
    JSON and an SSE request in flight together, one warm JSON request."""
    out: dict = {"tokens_out": 0, "results": []}

    def one(i: int, stream: bool, label: str) -> None:
        watchdog = threading.Timer(
            STALL_S, save_stall_report, (port, workdir, f"{where}-{label}")
        )
        watchdog.daemon = True
        if workdir:
            watchdog.start()
        try:
            doc = consensus(port, PROMPTS[i], stream)
        except Exception as err:  # noqa: BLE001 — booked, then reported
            failures.append(f"{where} {label}: {type(err).__name__}: {err}")
            return
        finally:
            watchdog.cancel()
        out["tokens_out"] += check_result(
            doc, models, f"{where} {label}", failures
        )
        if stream and not doc.get("_chunks"):
            failures.append(f"{where} {label}: SSE stream carried no chunk events")
        out[f"{label}_s"] = round(doc["_wall_s"], 3)
        out["results"].append((label, doc))

    one(0, False, "cold_json")
    pair = [
        threading.Thread(target=one, args=(1, False, "concurrent_json")),
        threading.Thread(target=one, args=(2, True, "concurrent_sse")),
    ]
    for t in pair:
        t.start()
    for t in pair:
        t.join()
    one(3, False, "warm_json")
    return out


def check_device_block(dev: dict, failures: list) -> None:
    """What /statsz says about where the engines ran."""
    if dev.get("platform") != "tpu":
        failures.append(f"platform is {dev.get('platform')!r}, not 'tpu'")
    elif not dev.get("peak_flops") or not dev.get("peak_hbm_bytes_per_s"):
        failures.append(f"no published peaks for device kind {dev.get('kind')!r}")
    for preset, eng in sorted((dev.get("engines") or {}).items()):
        if eng.get("impl") != eng.get("built") or eng.get("fallbacks"):
            failures.append(
                f"{preset}: built with attention {eng.get('built')!r}, ended "
                f"on {eng.get('impl')!r} after {eng.get('fallbacks')} guard "
                "fallback(s)"
            )
        want = EXPECTED_PATHS.get(preset)  # tiny rehearsal presets: none
        paths = eng.get("paths") or {}
        if want is None:
            continue
        if want["prefill"] not in paths.get("prefill", {}):
            failures.append(
                f"{preset}: no prefill program took the {want['prefill']} "
                f"path: {paths.get('prefill')}"
            )
        if set(paths.get("decode", {})) != {want["decode"]}:
            failures.append(
                f"{preset}: decode path is {paths.get('decode')}, expected "
                f"only {want['decode']!r}"
            )


def child_env() -> dict:
    env = dict(os.environ)
    env["LLMC_MAX_SEQ"] = str(MAX_SEQ)
    # The finite-logit sentinel rides every decode chunk's fetch: the
    # repo's own check that what the device produced is finite.
    env["LLMC_INTEGRITY"] = "1"
    env["PYTHONUNBUFFERED"] = "1"
    return env


def cache_entries(path) -> set:
    try:
        return set(os.listdir(path)) if path else set()
    except OSError:
        return set()


def package_warnings(log_path: str) -> list:
    with open(log_path, errors="replace") as f:
        return [ln.strip()[:300] for ln in f if _PACKAGE_WARNING.search(ln)]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


# -- phase 1: the server -----------------------------------------------------


def phase_serve(args, workdir: str, failures: list) -> tuple:
    """Returns the phase report and, once the server has answered, the
    compile cache it filled: ``(dir, entries)`` for phase 2 to hit."""
    models = args.models.split(",")
    port = free_port()
    log_path = os.path.join(workdir, "serve.log")
    cmd = [
        sys.executable, "-m", "llm_consensus_tpu", "serve",
        "--models", args.models, "--judge", args.judge,
        "--port", str(port), "--max-batch", str(MAX_BATCH),
        "--timeout", str(REQUEST_TIMEOUT_S),
        "--data-dir", os.path.join(workdir, "data"),
        "--blackbox-dir", os.path.join(workdir, "blackbox"),
    ]
    report: dict = {"phase": "serve", "models": models, "judge": args.judge}
    cache = None
    t_start = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            cmd, cwd=REPO, env=child_env(), stdout=log, stderr=log,
        )
    try:
        deadline = time.monotonic() + START_TIMEOUT_S
        while True:
            if proc.poll() is not None:
                failures.append(f"serve exited {proc.returncode} before /healthz")
                return report, cache
            try:
                if _request(port, "GET", "/healthz", timeout=2)[0] == 200:
                    break
            except OSError:
                pass
            if time.monotonic() > deadline:
                failures.append("serve: /healthz not up in time")
                return report, cache
            time.sleep(0.5)
        report["start_s"] = round(time.monotonic() - t_start, 3)
        dev = get_json(port, "/statsz").get("device") or {}
        report["device"] = {k: dev.get(k) for k in ("platform", "kind", "count")}
        cache_dir = (dev.get("compile_cache") or {}).get("dir")
        before = cache_entries(cache_dir)
        full_width = (args.models, args.judge) == (DEFAULT_MODELS, DEFAULT_JUDGE)
        if dev.get("platform") != "tpu" and full_width:
            # Nine gigabytes of weights on a CPU prove nothing about the
            # chip: fail now. (Tiny presets walk on, as a rehearsal.)
            failures.append(f"platform is {dev.get('platform')!r}, not 'tpu'")
            return report, cache
        report.update(drive_requests(port, models, failures, workdir=workdir))
        report.pop("results")
        stats = get_json(port, "/statsz")
        dev = stats.get("device") or {}
        check_device_block(dev, failures)
        report["attention"] = {
            p: {"built": e.get("built"), "impl": e.get("impl"),
                "fallbacks": e.get("fallbacks"), "paths": e.get("paths"),
                "devices": e.get("devices")}
            for p, e in sorted((dev.get("engines") or {}).items())
        }
        report["memory"] = dev.get("memory")
        report["utilization"] = stats.get("utilization")
        report["batchers"] = {
            preset: {k: snap.get(k) for k in (
                "decode_tokens", "decode_s", "admit_tokens", "admit_s",
                "establish_s", "preemptions",
            )}
            for preset, snap in sorted((stats.get("batchers") or {}).items())
        }
        integ = stats.get("integrity") or {}
        report["integrity"] = {
            "checks": integ.get("checks"), "failures": integ.get("failures"),
        }
        if integ.get("failures_total"):
            failures.append(f"integrity failures booked: {integ.get('failures')}")
        if not (integ.get("checks") or {}).get("logits"):
            failures.append("the finite-logit sentinel never ran")
        status, metrics = _request(port, "GET", "/metricsz")
        if status != 200 or "llmc_build_info" not in metrics:
            failures.append(f"/metricsz -> {status} without llmc_build_info")
        after = cache_entries(cache_dir)
        report["compile_cache"] = {
            "dir": cache_dir, "entries_before": len(before),
            "entries_after": len(after),
        }
        cache = (cache_dir, after)
    except Exception as err:  # noqa: BLE001 — booked; the drain still runs
        failures.append(f"serve phase: {type(err).__name__}: {err}")
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(timeout=DRAIN_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
                failures.append("serve did not drain after SIGTERM")
        if proc.returncode != 0 and "start_s" in report:
            failures.append(f"serve exited {proc.returncode}")
        for line in package_warnings(log_path):
            failures.append(f"serve printed a RuntimeWarning: {line}")
        report["wall_s"] = round(time.monotonic() - t_start, 3)
    return report, cache


# -- phase 2: the one-shot CLI ------------------------------------------------


def phase_cli(args, workdir: str, failures: list, cache: tuple) -> dict:
    models = args.models.split(",")
    cache_dir, from_serve = cache
    log_path = os.path.join(workdir, "cli.log")
    env = child_env()
    # JAX logs each persistent-cache hit, with its key, at DEBUG.
    env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
    cmd = [
        sys.executable, "-m", "llm_consensus_tpu",
        "--models", args.models, "--judge", args.judge,
        "--timeout", str(REQUEST_TIMEOUT_S), "--max-tokens", str(MAX_TOKENS),
        "--json", PROMPTS[0],
    ]
    report: dict = {"phase": "cli"}
    t0 = time.monotonic()
    with open(log_path, "w") as log:
        proc = subprocess.run(
            cmd, cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=log,
            text=True, timeout=REQUEST_TIMEOUT_S + 300,
        )
    report["wall_s"] = round(time.monotonic() - t0, 3)
    if proc.returncode != 0:
        failures.append(f"cli exited {proc.returncode}")
    else:
        try:
            report["tokens_out"] = check_result(
                json.loads(proc.stdout), models, "cli", failures
            )
        except ValueError as err:
            failures.append(f"cli printed no JSON result: {err}")
    for line in package_warnings(log_path):
        failures.append(f"cli printed a RuntimeWarning: {line}")
    with open(log_path, errors="replace") as f:
        hits = set(_CACHE_HIT.findall(f.read()))
    found = {h for h in hits if any(e.startswith(h) for e in from_serve)}
    report["compile_cache"] = {
        "dir": cache_dir, "hits": len(hits), "hits_written_by_serve": len(found),
        "entries_after": len(cache_entries(cache_dir)),
    }
    if not found:
        failures.append(
            "the second process hit no compile-cache entry the first one wrote"
        )
    return report


# -- --chips 4: the cross-chip path, in ONE child that owns the chips ---------


def multichip_child(args) -> int:
    """Runs inside the child (this is the only code here that imports
    JAX): the request script on the planned four-chip placement, then on
    one chip, and the comparison between them."""
    import gc

    import jax
    import numpy as np

    from llm_consensus_tpu import serve
    from llm_consensus_tpu.cli.main import init_registry
    from llm_consensus_tpu.engine.engine import _decode_chunk
    from llm_consensus_tpu.models import init_kv_cache
    from llm_consensus_tpu.providers.tpu import TPUProvider, parse_model_name

    failures: list = []
    models = args.models.split(",")
    presets = list(dict.fromkeys(
        parse_model_name(m) for m in models + [args.judge]
    ))
    judge_preset = parse_model_name(args.judge)
    devices = jax.devices()
    device = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if len(devices) != 4:
        failures.append(f"--chips 4 needs four devices, JAX reports {len(devices)}")
        emit({"phase": "multichip", "failures": failures})
        emit({"ok": False, "device": device})
        return 1
    judge_ids = None

    def stage(name: str, pool) -> dict:
        nonlocal judge_ids
        provider = TPUProvider(batch_streams=MAX_BATCH)
        registry = init_registry(models, args.judge, lambda m: provider)
        provider.prepare(models, args.judge, devices=pool)
        gateway = serve.build_gateway(
            registry, models, args.judge, timeout=REQUEST_TIMEOUT_S,
            max_concurrency=2, save=False, port=0,
        )
        _, port = gateway.start()
        info: dict = {"stage": name}
        try:
            t0 = time.monotonic()
            driven = drive_requests(port, models, failures, where=name)
            info["wall_s"] = round(time.monotonic() - t0, 3)
            info["texts"] = {
                label: {r["model"]: r["content"] for r in doc["responses"]}
                for label, doc in driven.pop("results")
            }
            info.update(driven)
            dev = get_json(port, "/statsz").get("device") or {}
            check_device_block(dev, failures)
            info["compile_cache_enabled"] = (
                dev.get("compile_cache") or {}
            ).get("enabled")
            placements: dict = {}
            for preset in presets:
                planned = {d.id for d in provider.placement(preset).devices.flat}
                eng = provider._engine_for(preset)
                trees = {"params": eng.params}
                entry = provider._batchers.get(preset)
                if entry is not None:
                    trees["kv"] = entry[1]._cache
                for what, tree in trees.items():
                    on = set()
                    for leaf in jax.tree.leaves(tree):
                        on |= {d.id for d in leaf.sharding.device_set}
                    if on != planned:
                        failures.append(
                            f"{name}: {preset} {what} live on {sorted(on)}, "
                            f"planned {sorted(planned)}"
                        )
                placements[preset] = {
                    "devices": sorted(planned),
                    "tp": dict(eng.mesh.shape).get("tp", 1),
                    "kv_checked": "kv" in trees,
                    # Booked per PROCESS at trace time: a model placed
                    # the same way in both stages reuses its compiled
                    # programs, so the second stage adds nothing to it.
                    "attention": eng.attention_stats()["paths"],
                }
            info["placements"] = placements
            info["bytes_in_use"] = {
                str(d.id): (d.memory_stats() or {}).get("bytes_in_use")
                for d in devices
            }
            jeng = provider._engine_for(judge_preset)
            if judge_ids is None:
                judge_ids = jeng.tokenizer.encode(PROMPTS[0])
            logits, _ = jeng._prefill_ids(judge_ids)
            info["_judge_logits"] = np.asarray(jax.device_get(logits), np.float32)
            if dict(jeng.mesh.shape).get("tp", 1) > 1:
                # The sharded judge's decode step, compiled as the engine
                # dispatches it: GSPMD's collectives for the row-parallel
                # matmuls, and the decode kernel under shard_map.
                rows = MAX_BATCH
                cache = jeng._shard_fn(init_kv_cache(
                    jeng.cfg, batch=rows, max_seq=jeng.max_seq,
                    dtype=jeng._dtype, quant=jeng.kv_quant,
                ))
                zeros = jeng._place(np.zeros((rows,), np.int32))
                text = _decode_chunk.lower(
                    jeng.params, jeng.cfg, zeros,
                    jeng._place(np.asarray(0, np.int32)), cache,
                    jeng._place(jax.random.PRNGKey(0)), n_steps=16,
                    temperature=0.0, top_k=None, top_p=None, row_start=zeros,
                    kv_width=jeng._decode_width(256), attn_impl=jeng.attn_impl,
                    mesh=jeng.mesh,
                ).compile().as_text()
                info["judge_decode_step"] = {
                    "all_reduce": len(re.findall(r"all-reduce(?:-start)?\(", text)),
                    "tpu_custom_call": text.count("tpu_custom_call"),
                }
                if not info["judge_decode_step"]["all_reduce"]:
                    failures.append(f"{name}: sharded judge decode step has no all-reduce")
                if not info["judge_decode_step"]["tpu_custom_call"]:
                    failures.append(f"{name}: sharded judge decode step lost the kernel")
                del cache, text
        except Exception as err:  # noqa: BLE001 — booked, then reported
            failures.append(f"{name}: {type(err).__name__}: {err}")
        finally:
            if not gateway.close(drain=True, timeout=DRAIN_TIMEOUT_S):
                failures.append(f"{name}: gateway did not drain")
            provider.release()
            gc.collect()
        return info

    planned = stage("planned", devices)
    single = stage("one_chip", devices[:1])

    slices = [set(p["devices"]) for p in planned.get("placements", {}).values()]
    if sum(len(s) for s in slices) != len(set().union(*slices) if slices else ()):
        failures.append(f"planned placements overlap: {planned.get('placements')}")
    used = set().union(*slices) if slices else set()
    if len(used) < 2:
        failures.append(f"the plan used {len(used)} chip(s): nothing crossed chips")
    for d in sorted(used):
        if (planned.get("bytes_in_use", {}).get(str(d)) or 0) < (64 << 20):
            failures.append(
                f"planned chip {d} holds almost nothing: "
                f"{planned.get('bytes_in_use', {}).get(str(d))} bytes in use"
            )
    # Greedy text of the models that are tp=1 in BOTH placements must not
    # move a byte: same program, same chip type. Gated on the requests
    # that ran alone; the concurrent pair's batch composition depends on
    # arrival order, so its equality is reported, not required.
    same_tp = [
        p for p, pl in planned.get("placements", {}).items()
        if pl["tp"] == single.get("placements", {}).get(p, {}).get("tp") == 1
    ]
    identical: dict = {}
    for label in ("cold_json", "concurrent_json", "concurrent_sse", "warm_json"):
        a = planned.get("texts", {}).get(label, {})
        b = single.get("texts", {}).get(label, {})
        for preset in same_tp:
            text = a.get(f"tpu:{preset}")
            same = text is not None and text == b.get(f"tpu:{preset}")
            identical[f"{label}:{preset}"] = same
            if not same and not label.startswith("concurrent"):
                failures.append(
                    f"{preset} ({label}): greedy text differs between the "
                    "planned placement and one chip"
                )
    la, lb = planned.pop("_judge_logits", None), single.pop("_judge_logits", None)
    logit_check = None
    if la is None or lb is None:
        failures.append("judge first-step logits missing from a stage")
    else:
        # bf16 matmuls under another reduction order: 16 ulps of bf16
        # (2^-8 relative) of the logit scale, fixed before the run.
        scale = float(np.max(np.abs(lb)))
        err = float(np.max(np.abs(la - lb)))
        tol = 16 * 2.0 ** -8 * scale
        logit_check = {
            "max_abs_diff": err, "tolerance": tol, "scale": scale,
            "finite": bool(np.isfinite(la).all() and np.isfinite(lb).all()),
            "argmax_equal": bool(la.argmax() == lb.argmax()),
        }
        if not logit_check["finite"] or err > tol:
            failures.append(f"tp=2 judge logits off the one-chip judge: {logit_check}")
    for info in (planned, single):
        info.pop("texts", None)
        emit({"phase": "multichip", **info})
    emit({
        "phase": "multichip", "stage": "compare", "same_tp_models": same_tp,
        "greedy_text_identical": identical, "judge_logits": logit_check,
        "failures": failures,
    })
    emit({"ok": not failures, "device": device})
    return 0 if not failures else 1


def run_multichip(args, workdir: str) -> int:
    log_path = os.path.join(workdir, "multichip.log")
    cmd = [
        sys.executable, os.path.abspath(__file__), "--chips", "4",
        "--in-child", "--models", args.models, "--judge", args.judge,
    ]
    with open(log_path, "w") as log:
        proc = subprocess.run(
            cmd, cwd=REPO, env=child_env(), stdout=subprocess.PIPE,
            stderr=log, text=True,
        )
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    last = None
    if lines:
        try:
            last = json.loads(lines[-1])
        except ValueError:
            pass
    failures = []
    if proc.returncode != 0:
        failures.append(f"multichip child exited {proc.returncode}")
    for line in package_warnings(log_path):
        failures.append(f"multichip child printed a RuntimeWarning: {line}")
    if not isinstance(last, dict) or "ok" not in last:
        failures.append("multichip child printed no result line")
        last = {"ok": False, "device": None}
    else:
        lines = lines[:-1]
    for ln in lines:
        print(ln, flush=True)
    if failures:
        emit({"phase": "multichip", "stage": "parent", "failures": failures})
    ok = bool(last.get("ok")) and not failures
    emit({"ok": ok, "device": last.get("device")})
    return 0 if ok else 1


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the cross-chip path and its one-chip "
                             "comparison (default 1: serve + CLI phases)")
    parser.add_argument("--models", default=DEFAULT_MODELS,
                        help="comma-separated panel (default: full width)")
    parser.add_argument("--judge", default=DEFAULT_JUDGE)
    parser.add_argument("--in-child", action="store_true",
                        help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.in_child:
        return multichip_child(args)

    workdir = os.path.join(REPO, "chiprun_out", "chip_smoke")
    os.makedirs(workdir, exist_ok=True)
    if args.chips == 4:
        return run_multichip(args, workdir)

    failures: list = []
    serve_report, cache = phase_serve(args, workdir, failures)
    emit(serve_report)
    if cache is not None:
        # Only after a server that came up and answered: phase 2 is the
        # fresh process that must find what phase 1 compiled.
        emit(phase_cli(args, workdir, failures, cache))
    if failures:
        emit({"failures": failures})
    emit({"ok": not failures, "device": serve_report.get("device")})
    return 0 if not failures else 1


if __name__ == "__main__":
    sys.exit(main())
