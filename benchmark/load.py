"""Load generation: a stdlib HTTP/SSE client that keeps the clock.

One process, few threads: an open loop has one scheduler thread and one
short-lived thread per request in flight; a closed loop has one thread per
client. Every time is ``time.monotonic()`` of this process. A request is
timed from when it was DUE (the schedule's instant in an open loop; the
moment the caller became free in a closed one), not from when it was sent,
and how late it was sent is kept beside it (``gen_lag``).
"""

from __future__ import annotations

import http.client
import json
import threading
import time
from typing import Callable, Optional


def http_json(port: int, method: str, path: str, body=None,
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
    status, text = http_json(port, "GET", path)
    if status != 200:
        raise RuntimeError(f"GET {path} -> {status}: {text[:200]}")
    return json.loads(text)


def new_record(request, due: float) -> dict:
    return {
        "index": request.index, "due": due, "prompt_tokens": request.prompt_tokens,
        "max_tokens": request.max_tokens, "sent": None, "done": None,
        "judge_events": [], "token_events": [], "streamed": {}, "doc": None,
        "error": None,
    }


def consensus(port: int, request, due: float, rec: Optional[dict] = None) -> dict:
    """One POST /v1/consensus; fills and returns the run's record (events
    are appended as they arrive, so a record of a run still in flight can
    be read). Never raises: a failure is a record with ``error`` set."""
    if rec is None:
        rec = new_record(request, due)
    conn = http.client.HTTPConnection(
        "127.0.0.1", port, timeout=request.timeout_s + 30
    )
    try:
        payload = json.dumps(request.body()).encode()
        rec["sent"] = time.monotonic()
        conn.request("POST", "/v1/consensus", body=payload,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}: {resp.read(300)!r}"
        elif not request.stream:
            rec["doc"] = json.loads(resp.read().decode("utf-8"))
        else:
            name = None
            while True:
                line = resp.readline()
                if not line:
                    break
                now = time.monotonic()
                line = line.rstrip(b"\r\n")
                if line.startswith(b"event: "):
                    name = line[7:].decode()
                elif line.startswith(b"data: "):
                    data = json.loads(line[6:].decode("utf-8"))
                    if name == "chunk":
                        # Characters are all a client can count: a chunk
                        # carries text and no token count. One character
                        # is one token under the byte fold, which
                        # arith.why_failed holds against the counts the
                        # program does report (responses[].tokens).
                        n = len(data.get("text") or "")
                        rec["token_events"].append((now, n))
                        if data.get("kind") == "judge_chunk":
                            rec["judge_events"].append((now, n))
                        else:
                            model = data.get("model")
                            rec["streamed"][model] = rec["streamed"].get(model, 0) + n
                    elif name == "done":
                        rec["doc"] = data
                    elif name == "error":
                        rec["error"] = f"SSE error: {str(data)[:300]}"
            if rec["doc"] is None and rec["error"] is None:
                rec["error"] = "SSE stream ended without a done event"
    except Exception as err:  # noqa: BLE001 — booked as a failed run
        rec["error"] = f"{type(err).__name__}: {err}"
    finally:
        rec["done"] = time.monotonic()
        conn.close()
    return rec


def follow_up_of(request, rec: dict):
    """The refine round of a session: the same prompt with the synthesis
    under revision appended (the program's refine-prompt wording)."""
    from dataclasses import replace

    draft = (rec.get("doc") or {}).get("consensus") or ""
    return replace(
        request, follow_up=False, index=request.index + 100000,
        prompt=f"{request.prompt}\n\n[Previous draft answer under revision]\n{draft}",
    )


class Window:
    """Sends one plan's requests between ``t0`` and ``t0 + seconds`` and
    collects the records of the runs that ended inside it."""

    def __init__(self, port: int, t0: float, seconds: float,
                 send: Callable = consensus):
        self.port, self.t0, self.t1 = port, t0, t0 + seconds
        self._send = send
        self._lock = threading.Lock()
        self.records: list = []
        self.in_flight = 0
        self.sent = 0

    def _one(self, request, due: float) -> dict:
        rec = new_record(request, due)
        with self._lock:
            self.in_flight += 1
            self.sent += 1
            self.records.append(rec)
        self._send(self.port, request, due, rec)
        with self._lock:
            self.in_flight -= 1
        return rec

    def _session(self, request, due: float) -> None:
        rec = self._one(request, due)
        if request.follow_up and rec["error"] is None:
            nxt = follow_up_of(request, rec)
            if time.monotonic() < self.t1:
                self._one(nxt, time.monotonic())

    def run_open(self, arrivals: list) -> None:
        for req in arrivals:
            due = self.t0 + req.due_s
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            threading.Thread(
                target=self._session, args=(req, due), daemon=True).start()
        remaining = self.t1 - time.monotonic()
        if remaining > 0:
            time.sleep(remaining)

    def run_closed(self, clients: list) -> None:
        def caller(requests: list) -> None:
            for req in requests:
                due = max(time.monotonic(), self.t0)
                if due >= self.t1:
                    return
                self._session(req, due)

        threads = [
            threading.Thread(target=caller, args=(reqs,), daemon=True)
            for reqs in clients
        ]
        for t in threads:
            t.start()
        remaining = self.t1 - time.monotonic()
        if remaining > 0:
            time.sleep(remaining)

    def snapshot(self) -> tuple:
        """Copies of the records of every run sent so far (``done`` is None
        for one still in flight), the count in flight (their callers are
        daemon threads; the server finishes or drops those runs when it
        drains) and the count sent."""
        with self._lock:
            records = [
                dict(r, judge_events=list(r["judge_events"]),
                     token_events=list(r["token_events"]))
                for r in self.records
            ]
            return records, self.in_flight, self.sent


def run_group(port: int, requests: list, gap_s: float = 0.0,
              send: Callable = consensus) -> list:
    """Warm-up: a group of requests sent together, or ``gap_s`` apart;
    waits for all."""
    out: list = [None] * len(requests)

    def one(i: int, req) -> None:
        out[i] = send(port, req, time.monotonic())

    threads = [
        threading.Thread(target=one, args=(i, r)) for i, r in enumerate(requests)
    ]
    for t in threads:
        t.start()
        if gap_s:
            time.sleep(gap_s)
    for t in threads:
        t.join()
    return out


class Sampler:
    """Polls a JSON endpoint about twice a second while running."""

    def __init__(self, port: int, path: str = "/statsz", period_s: float = 0.5):
        self.port, self.path, self.period_s = port, path, period_s
        self.samples: list = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def _loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.samples.append((time.monotonic(), get_json(self.port, self.path)))
            except Exception:  # noqa: BLE001 — a missed sample is no sample
                pass
            self._stop.wait(self.period_s)

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self) -> list:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)
        return self.samples
