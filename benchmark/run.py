#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

This process never imports JAX: it generates the load, keeps the clock and
prints the result. Its one child (``benchmark/server.py``) is the program's
``serve`` with the cell's configuration and holds the chips; it is gone when
the run ends. Phases:

  set-up    start the child; wait for /healthz; send the mix's warm-up
            requests through the served path (they build the engines and
            compile this mix's shapes); read /statsz for the device
  window    --seconds of the cell's traffic, generated from --seed; /statsz
            and /metricsz read at both ends; with --trace 1 one bounded
            profiler window (POST /debugz/profile) inside it, and /statsz
            sampled twice a second through that window
  after     the logits parity check in the child (SIGUSR1), SIGTERM, wait;
            with --trace 1 the trace reduced by benchmark/trace_reduce.py

Everything that belongs to one configuration, one traffic mix, one cell or
one metric is a file found by the name ``BENCHMARK.json`` gives it:
``configs/<config>.json``, ``traffic/<mix>.json``, ``cells/<cell>.json``
(optional), ``end_to_end/<metric>.py``, ``layer_metrics/<metric>.py``.

Every earlier line of stdout is one JSON object of observations; the LAST
line is the result: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, when traced ``breakdown``, and last ``compared`` (each number the
verdict compared, beside its limit; the same as the last lines of stderr).
No result line is printed, and the exit code is not 0, when the program is
not there, when JAX found no TPU or fewer chips than the cell asks for, or
when the child died.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import socket  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmark import arith, load, peaks, traffic  # noqa: E402
from benchmark.layer_metrics import compiles_in_window, hbm_peak_gb  # noqa: E402

START_TIMEOUT_S = 300
PARITY_TIMEOUT_S = 420
DRAIN_TIMEOUT_S = 90
IN_FLIGHT_TIMEOUT_S = 90
NO_RESULT = 3  # exit code when no result line can be printed


def emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


def bail(reason: str, **more) -> int:
    """No result line: the reason goes to stderr, the exit code is not 0."""
    print(json.dumps({"no_result": reason, **more}), file=sys.stderr, flush=True)
    return NO_RESULT


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def load_spec(workload: str) -> tuple:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload in cells:
        cell = cells[workload]
    elif workload.startswith("rehearsal:"):
        # `rehearsal:<config>:<mix>`: any configuration under any mix, for
        # the CPU rehearsal and for sweeps; never one of the benchmark's
        # cells, and refused below unless the platform check is waived.
        # A fourth part overrides the rate (open loop) or the clients (closed).
        _, config, mix, *level = workload.split(":")
        cell = {"name": workload, "config": config, "traffic": mix, "chips": None}
        if level:
            cell["level"] = float(level[0])
    else:
        raise SystemExit(f"no workload {workload!r}; have {sorted(cells)}")
    with open(os.path.join(HERE, "configs", f"{cell['config']}.json")) as f:
        config = json.load(f)
    if cell["chips"] is None:
        cell["chips"] = config["chips"]
    return bench, cell, config


def metrics_for(bench: dict, kind: str, workload: str) -> list:
    return [
        m for m in bench[kind]
        if "workloads" not in m or workload in m["workloads"]
    ]


def read_metric(package: str, name: str, ctx: dict):
    """A metric's reader is a file of its own, found by the metric's name. A
    reader that finds nothing to read returns None, and the metric is left
    out of the line."""
    try:
        module = importlib.import_module(f"benchmark.{package}.{name}")
    except ModuleNotFoundError:
        return None
    value = module.read(ctx)
    return None if value is None else float(value)


class Child:
    def __init__(self, config_path: str, port: int, workdir: str, seed: int):
        env = dict(os.environ)
        env["PYTHONUNBUFFERED"] = "1"
        # Cache every program, however quick its compile: a run is a new
        # process, and what is not in the cache is compiled again each time.
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
        self.log_path = os.path.join(workdir, "server.log")
        with open(self.log_path, "w") as log:
            self.proc = subprocess.Popen(
                [sys.executable, os.path.join(HERE, "server.py"),
                 "--config", config_path, "--port", str(port),
                 "--workdir", workdir, "--seed", str(seed)],
                cwd=REPO, env=env, stdout=log, stderr=log,
            )

    def alive(self) -> bool:
        return self.proc.poll() is None

    def stop(self) -> int:
        """SIGTERM (the program drains), then SIGKILL; waits either way."""
        if self.alive():
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(timeout=DRAIN_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        return self.proc.returncode

    def log_tail(self, n: int = 2000) -> str:
        try:
            with open(self.log_path, errors="replace") as f:
                return f.read()[-n:]
        except OSError:
            return ""


def wait_healthy(child: Child, port: int) -> bool:
    deadline = time.monotonic() + START_TIMEOUT_S
    while time.monotonic() < deadline and child.alive():
        try:
            if load.http_json(port, "GET", "/healthz", timeout=2)[0] == 200:
                return True
        except OSError:
            pass
        time.sleep(0.25)
    return False


def check_device(stats: dict, config: dict) -> list:
    """What /statsz says about where and how the engines run, against the
    configuration's file. Returns the guarantees broken."""
    broken = []
    dev = stats.get("device") or {}
    engines = dev.get("engines") or {}
    for name, spec in config["models"].items():
        eng = engines.get(name)
        if eng is None:
            broken.append(f"{name}: no engine in /statsz")
            continue
        if eng.get("impl") != eng.get("built") or eng.get("fallbacks"):
            broken.append(
                f"{name}: attention built {eng.get('built')!r}, running "
                f"{eng.get('impl')!r} after {eng.get('fallbacks')} fallback(s)")
        want = spec.get("expect_attention") or {}
        paths = eng.get("paths") or {}
        for phase in ("prefill", "decode"):
            seen = set(paths.get(phase) or {})
            if want and (not seen or not seen <= set(want[phase])):
                broken.append(
                    f"{name}: {phase} programs on {sorted(seen)}, the file "
                    f"allows {want[phase]}")
    return broken


def warm_up(plan, port: int, panel: list, chips: int):
    """The mix's warm-up requests, one at a time and then in groups. Returns
    None, or what went wrong (a run that failed; fewer chips than asked)."""
    for i, req in enumerate(plan.warmup_sequential):
        reason = arith.why_failed(load.consensus(port, req, time.monotonic()), panel)
        if reason:
            return f"sequential {i}: {reason}"
        if i == 0:
            dev = load.get_json(port, "/statsz").get("device") or {}
            if dev.get("platform") == "tpu" and dev.get("count") != chips:
                return f"the cell asks for {chips} chip(s), JAX reports {dev.get('count')}"
    for g, (gap_s, group) in enumerate(plan.warmup_concurrent):
        for rec in load.run_group(port, group, gap_s):
            reason = arith.why_failed(rec, panel)
            if reason:
                return f"concurrent {g}: {reason}"
    return None


def ask_parity(child: Child, workdir: str) -> dict:
    """SIGUSR1 to the child, then wait for the parity.json it writes."""
    if not child.alive():
        return {"ok": False, "error": "the child was gone"}
    child.proc.send_signal(signal.SIGUSR1)
    path = os.path.join(workdir, "parity.json")
    deadline = time.monotonic() + PARITY_TIMEOUT_S
    while time.monotonic() < deadline and child.alive():
        if os.path.exists(path):
            with open(path) as f:
                return json.load(f)
        time.sleep(0.2)
    return {"ok": False, "error": "no parity result in time"}


def reduce_trace(workdir: str, profile: dict):
    """The window's .xplane.pb reduced by benchmark/trace_reduce.py, in a
    process of its own that is held to the CPU. None where there is none."""
    traces = glob.glob(
        os.path.join(workdir, "profiles", "**", "*.xplane.pb"), recursive=True)
    if not traces:
        return None
    out = os.path.join(workdir, "trace_reduced.json")
    with open(os.path.join(workdir, "trace_reduce.log"), "w") as log:
        rc = subprocess.run(
            [sys.executable, os.path.join(HERE, "trace_reduce.py"), traces[0], out],
            env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
            stdout=subprocess.DEVNULL, stderr=log,
        ).returncode
    profile["xplane_bytes"] = os.path.getsize(traces[0])
    shutil.rmtree(os.path.join(workdir, "profiles"), ignore_errors=True)
    if rc != 0:
        return None
    with open(out) as f:
        return json.load(f)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(REPO, "llm_consensus_tpu")):
        return bail("the program (llm_consensus_tpu/) is not in this checkout")
    bench, cell, config = load_spec(args.workload)
    mix = traffic.load_mix(cell["traffic"])
    numbers = traffic.load_cell(cell["name"])
    if "level" in cell:
        numbers = {"rate_per_s": cell["level"], "clients": int(cell["level"])}
    plan = traffic.generate(mix, numbers, args.seed, args.seconds)

    workdir = os.path.join(
        REPO, "benchmark_out", cell["name"].replace(":", "_"),
        f"seed{args.seed}-trace{args.trace}")
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    port = free_port()
    child = Child(
        os.path.join(HERE, "configs", f"{cell['config']}.json"), port, workdir,
        args.seed)
    try:
        return measure(args, bench, cell, config, plan, child, port, workdir)
    finally:
        child.stop()


def measure(args, bench, cell, config, plan, child, port, workdir) -> int:
    panel = config["panel"]
    if not wait_healthy(child, port):
        return bail("the server did not come up", log=child.log_tail())

    # -- set-up: warm-up through the served path ------------------------------
    t_warm = time.monotonic()
    problem = warm_up(plan, port, panel, cell["chips"])
    if problem or not child.alive():
        return bail("warm-up failed", failure=problem, log=child.log_tail())
    emit({"phase": "set-up", "warm_up_s": round(time.monotonic() - t_warm, 3),
          "warm_up_runs": len(plan.warmup_sequential)
          + sum(len(g) for _, g in plan.warmup_concurrent)})

    # -- the window -----------------------------------------------------------
    stats_before = load.get_json(port, "/statsz")
    metrics_before = load.http_json(port, "GET", "/metricsz")[1]
    t0 = time.monotonic()
    setup_s = t0 - T_START
    window = load.Window(port, t0, args.seconds)
    sampler = load.Sampler(port)
    profile: dict = {}

    def traced_window() -> None:
        dur = min(plan.trace_window_s, args.seconds / 2)
        time.sleep(max(0.0, t0 + (args.seconds - dur) * 0.5 - time.monotonic()))
        status, text = load.http_json(
            port, "POST", "/debugz/profile",
            {"duration_s": dur, "tag": "bench"})
        profile.update(status=status, reply=text[:300], duration_s=dur)
        sampler.start()
        time.sleep(dur)
        sampler.stop()

    tracer = threading.Thread(target=traced_window, daemon=True)
    if args.trace:
        tracer.start()
    if plan.kind.startswith("open-"):
        window.run_open(plan.arrivals)
    else:
        window.run_closed(plan.clients)
    t1 = window.t1
    stats_after = load.get_json(port, "/statsz")
    metrics_after = load.http_json(port, "GET", "/metricsz")[1]
    records, in_flight, sent = window.snapshot()
    if args.trace:
        tracer.join(timeout=30)

    # -- after: parity, then the child goes -----------------------------------
    # The runs still in flight finish first (their callers are daemon
    # threads): the parity check then has the chip and its memory to itself.
    deadline = time.monotonic() + IN_FLIGHT_TIMEOUT_S
    while window.in_flight and time.monotonic() < deadline and child.alive():
        time.sleep(0.2)
    parity = ask_parity(child, workdir)
    child_rc = child.stop()
    trace = reduce_trace(workdir, profile) if args.trace else None

    # -- numbers --------------------------------------------------------------
    ok, failed = arith.split(records, t0, t1, panel)
    dev = stats_after.get("device") or {}
    try:
        chip_peaks = peaks.peaks_of(dev.get("kind")) if dev.get("platform") == "tpu" else None
    except peaks.UnknownDevice as err:
        return bail(str(err))
    ctx = {
        "ok": ok, "failed": failed, "records": records, "t0": t0, "t1": t1,
        "setup_s": setup_s, "config": config,
        "stats_before": stats_before, "stats_after": stats_after,
        "metrics_before": metrics_before, "metrics_after": metrics_after,
        "samples": sampler.samples, "trace": trace, "peaks": chip_peaks,
    }
    values: dict = {}
    for kind, package in (("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")):
        for m in metrics_for(bench, kind, cell["name"]):
            v = read_metric(package, m["name"], ctx)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"], "kind": kind}

    integ = stats_after.get("integrity") or {}
    compiled = compiles_in_window.read(ctx)
    broken = check_device(stats_after, config)
    if compiled:
        broken.append(f"{compiled:.0f} program(s) compiled inside the window")
    if integ.get("failures_total"):
        broken.append(f"integrity failures: {integ.get('failures')}")
    if not (integ.get("checks") or {}).get("logits"):
        broken.append("the finite-logit sentinel never ran")
    if not parity.get("ok"):
        broken.append(f"logits parity failed: {json.dumps(parity)[:600]}")
    if not ok:
        broken.append("no run completed inside the window")
    if failed:
        broken.append(f"{len(failed)} run(s) failed, first: {failed[0]['reason'][:200]}")
    if child_rc != 0:
        broken.append(f"the server exited {child_rc}")
    if args.trace and (trace is None or not trace.get("busy_s")):
        broken.append(f"no device operation in the trace: {profile}")

    emit({
        "phase": "window", "workload": cell["name"], "seed": args.seed,
        "seconds": args.seconds, "kind": plan.kind, "rate_per_s": plan.rate_per_s,
        "clients": len(plan.clients) or None, "sent": sent,
        "completed_ok": len(ok), "failed": len(failed), "in_flight_at_end": in_flight,
        "failed_reasons": [f["reason"][:200] for f in failed[:5]],
        "all_metrics": {k: v["value"] for k, v in values.items()},
        "batchers_delta": {
            model: {k: arith.delta(stats_after, stats_before, "batchers", model, k)
                    for k in ("admit_s", "admit_tokens", "decode_s", "decode_tokens",
                              "impure_s", "impure_tokens", "tail_s", "absorb_s",
                              "establish_s", "preemptions")}
            for model in config["models"]
        },
        "compiles_delta": {
            fam: arith.delta(stats_after, stats_before, "attrib", "compiles", fam)
            for fam in (stats_after.get("attrib") or {}).get("compiles") or {}
        },
        "device_ops_top": [[k[:100], v] for k, v in (trace or {}).get("device_ops", [])],
        "parity": parity, "profile": profile, "broken": broken,
        "compile_cache": dev.get("compile_cache"),
        "engines": {m: (e.get("devices"), e.get("paths"))
                    for m, e in (dev.get("engines") or {}).items()},
    })

    if dev.get("platform") != "tpu":
        return bail(f"platform is {dev.get('platform')!r}, not 'tpu': a rehearsal, "
                    "no number of it is a device metric")
    if dev.get("count") != cell["chips"]:
        return bail(f"the cell asks for {cell['chips']} chip(s), JAX reports {dev.get('count')}")
    if args.workload.startswith("rehearsal:") and not os.environ.get("BENCH_ALLOW_REHEARSAL"):
        return bail("a rehearsal workload is not a cell of the benchmark")

    kind = "per_layer" if args.trace else "end_to_end"
    device = {
        "platform": dev.get("platform"), "kind": dev.get("kind"),
        "count": dev.get("count"),
        "memory_peak_bytes": hbm_peak_gb.peak_bytes(stats_after),
    }
    result = {
        "correct": not broken,
        "attempted": len(ok) + len(failed),
        "failed": len(failed),
        "metrics": {
            k: {"value": v["value"], "unit": v["unit"]}
            for k, v in values.items() if v["kind"] == kind
        },
        "device": device,
    }
    if args.trace and trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {
            "device_ops": trace["device_programs"], "idle_gaps": trace["idle_gaps"],
        }
    # Every number the verdict compared, beside its limit: the last lines of
    # stderr and the last key of the result line.
    result["compared"] = compared = {
        "runs_failed": [len(failed), 0],
        "compiles_in_window": [compiled or 0, 0],
        **{f"{m['model']}.{name}": pair
           for m in parity.get("models") or []
           for name, pair in (m.get("compared") or {}).items()},
    }
    for name, (value, limit) in compared.items():
        print(f"compared {name} {value} limit {limit}", file=sys.stderr, flush=True)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
