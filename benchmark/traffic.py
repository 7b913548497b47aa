"""The one traffic generator: a mix's parameters in, seeded requests out.

A traffic mix is a data file, ``benchmark/traffic/<name>.json``; a cell may
carry its own numbers in ``benchmark/cells/<cell>.json`` (the fixed rate of
an open-loop cell, the client count of a closed one — they follow from a
sweep on one configuration, so they belong to the cell, not to the mix).
Adding a mix or a cell is adding files; this module does not change.

Parameters a mix file may set (all read here, whether or not a first cell
uses them):

  kind                  ``open-poisson`` or ``open-paced`` (arrivals on a
                        schedule, whether or not earlier requests have
                        finished: exponential gaps, or even gaps with
                        ``pace_jitter`` as a +-share of each) or ``closed``
                        (``clients`` callers, each sending its next request
                        when the last has finished)
  rate_per_s            open loop: mean requests per second
  clients               closed loop: number of callers
  burst {min, max}      open loop: requests per arrival instant, drawn
                        uniformly; the mean rate stays ``rate_per_s``
  prompt_tokens         {dist: log-uniform | uniform | fixed, min, max,
                        strata}: prompt length in engine tokens, BOS
                        included (one token per byte under the byte
                        tokenizer). With ``strata`` = k, every k consecutive
                        requests carry the k mid-quantile lengths of the
                        distribution in a seeded order: the same work in
                        every run, which a handful of free draws is not
  max_tokens            answer length, panel and judge alike
  stream                SSE or one JSON reply
  repeat_share          share of requests that repeat a prompt sent in the
                        last ``repeat_window_s`` seconds
  shared_system_tokens  length of one system prompt sent with every request
  second_round          every run is followed by a refine round over its
                        own synthesis (a session of two requests)
  priority              {class: weight}: per-request priority class
  request_timeout_s     per-request deadline given to the server
  warmup                {sequential: [len...], concurrent: [group...]}: the
                        prompt lengths sent before the window, one at a
                        time and then in groups, so that this mix's prompt
                        buckets and row counts are compiled. A group is a
                        list of lengths sent together, or {lengths, gap_s}
                        sent ``gap_s`` apart (arrivals at a busy pool)
  trace_window_s        length of the profiler window in a traced run

Everything is drawn from ``numpy.random.default_rng([seed, ...])``: the same
seed gives the same requests, byte for byte; the program sees only them.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import asdict, dataclass
from typing import Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("open-poisson", "open-paced", "closed")

_WORDS = (
    "consensus panel judge answer prompt token cache batch prefill decode "
    "latency tail queue admit stream chunk kernel roofline bandwidth chip "
    "memory shard mesh replica router window prefix reuse merge compare "
    "explain summarize review diff function error trace request response "
    "deploy config schedule budget limit steady burst median spread bound"
).split()


@dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # open loop: offset from window start; closed: 0
    prompt: str
    system: str
    max_tokens: int
    stream: bool
    priority: str
    timeout_s: float
    follow_up: bool       # a refine round follows this run

    def body(self) -> dict:
        doc = {
            "prompt": self.prompt, "max_tokens": self.max_tokens,
            "stream": self.stream, "timeout": self.timeout_s,
            "priority": self.priority,
        }
        if self.system:
            doc["system"] = self.system
        return doc

    @property
    def prompt_tokens(self) -> int:
        return len(self.prompt.encode("utf-8")) + 1  # + BOS


@dataclass
class Plan:
    kind: str
    warmup_sequential: list
    warmup_concurrent: list  # [(gap_s, [Request...]), ...]
    arrivals: list        # open loop: Requests ordered by due_s
    clients: list         # closed loop: one list of Requests per client
    trace_window_s: float
    rate_per_s: Optional[float]

    def to_doc(self) -> dict:
        as_docs = lambda rs: [asdict(r) for r in rs]  # noqa: E731
        return {
            "kind": self.kind,
            "warmup_sequential": as_docs(self.warmup_sequential),
            "warmup_concurrent": [
                [gap, as_docs(g)] for gap, g in self.warmup_concurrent],
            "arrivals": as_docs(self.arrivals),
            "clients": [as_docs(c) for c in self.clients],
        }


def _load(kind_dir: str, name: str, required: bool) -> dict:
    path = os.path.join(HERE, kind_dir, f"{name}.json")
    if not os.path.exists(path):
        if required:
            have = sorted(
                f[:-5] for f in os.listdir(os.path.join(HERE, kind_dir))
                if f.endswith(".json")
            )
            raise FileNotFoundError(f"no {kind_dir}/{name}.json; have {have}")
        return {}
    with open(path) as f:
        return json.load(f)


def load_mix(name: str) -> dict:
    mix = _load("traffic", name, required=True)
    if mix.get("kind") not in KINDS:
        raise ValueError(f"traffic/{name}.json: kind must be one of {KINDS}")
    return mix


def load_cell(name: str) -> dict:
    """A cell's own numbers, if it has a file (``rate_per_s``, ``clients``)."""
    return _load("cells", name, required=False)


def text_of(rng: np.random.Generator, n_bytes: int, tag: str) -> str:
    """Seeded ASCII text of exactly ``n_bytes`` bytes, starting with a tag
    that makes it unique (no result-cache hit, no coalescing)."""
    parts = [tag]
    size = len(tag)
    while size < n_bytes:
        w = _WORDS[int(rng.integers(len(_WORDS)))]
        parts.append(" " + w)
        size += len(w) + 1
    return "".join(parts)[:n_bytes].ljust(n_bytes, ".")


def _length_at(spec: dict, u: float) -> int:
    """The length at quantile ``u`` of the mix's length distribution."""
    lo, hi = int(spec["min"]), int(spec["max"])
    dist = spec.get("dist", "log-uniform")
    if dist == "fixed" or lo == hi:
        return lo
    if dist == "uniform":
        return min(hi, lo + int(u * (hi - lo + 1)))
    if dist == "log-uniform":
        return int(round(math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))))
    raise ValueError(f"unknown prompt_tokens dist {dist!r}")


class _Maker:
    """Builds Requests for one mix from one seeded stream."""

    def __init__(self, mix: dict, seed: int, stream_id: int):
        self.mix = mix
        self.rng = np.random.default_rng([seed, stream_id])
        self.tag = f"s{seed}.{stream_id}"
        n_sys = int(mix.get("shared_system_tokens") or 0)
        # One system prompt for the whole mix, whatever the stream.
        self.system = (
            text_of(np.random.default_rng([seed, 7]), n_sys, f"[sys {seed}]")
            if n_sys else ""
        )
        pri = mix.get("priority") or {"normal": 1.0}
        self.classes = sorted(pri)
        w = np.asarray([float(pri[c]) for c in self.classes])
        self.weights = w / w.sum()
        self.recent: list = []  # (due_s, prompt) for repeats
        self.count = 0
        self.strata: list = []  # lengths left of the current block

    def _draw_length(self) -> int:
        spec = self.mix["prompt_tokens"]
        k = int(spec.get("strata") or 0)
        if k < 1:
            return _length_at(spec, float(self.rng.random()))
        if not self.strata:
            self.strata = [
                _length_at(spec, (int(i) + 0.5) / k)
                for i in self.rng.permutation(k)
            ]
        return self.strata.pop()

    def make(self, due_s: float, length: Optional[int] = None) -> Request:
        mix, rng = self.mix, self.rng
        i = self.count
        self.count += 1
        n = length if length is not None else self._draw_length()
        window = float(mix.get("repeat_window_s") or 0.0)
        self.recent = [(t, p) for t, p in self.recent if due_s - t <= window]
        share = float(mix.get("repeat_share") or 0.0)
        if length is None and self.recent and rng.random() < share:
            prompt = self.recent[int(rng.integers(len(self.recent)))][1]
        else:
            prompt = text_of(rng, max(n - 1, 8), f"[{self.tag}.{i}]")
        self.recent.append((due_s, prompt))
        cls = self.classes[int(rng.choice(len(self.classes), p=self.weights))]
        return Request(
            index=i, due_s=float(due_s), prompt=prompt, system=self.system,
            max_tokens=int(mix["max_tokens"]), stream=bool(mix["stream"]),
            priority=cls, timeout_s=float(mix.get("request_timeout_s", 240)),
            follow_up=bool(mix.get("second_round")),
        )


def generate(mix: dict, cell: dict, seed: int, seconds: float,
             closed_depth: int = 64) -> Plan:
    """The whole plan of one run: warm-up requests, then the window's."""
    kind = mix["kind"]
    warm = _Maker(mix, seed, 1)
    wu = mix.get("warmup") or {}
    warm_seq = [warm.make(0.0, n) for n in wu.get("sequential", [])]
    warm_con = []
    for group in wu.get("concurrent", []):
        gap, lengths = (
            (float(group.get("gap_s", 0.0)), group["lengths"])
            if isinstance(group, dict) else (0.0, group)
        )
        warm_con.append((gap, [warm.make(0.0, n) for n in lengths]))
    arrivals: list = []
    clients: list = []
    rate = None
    if kind.startswith("open-"):
        rate = cell.get("rate_per_s", mix.get("rate_per_s"))
        if not rate or rate <= 0:
            raise ValueError(
                f"open-loop mix {mix['name']!r} needs rate_per_s, in the mix "
                "or in the cell's file"
            )
        maker = _Maker(mix, seed, 2)
        rng = np.random.default_rng([seed, 3])
        burst = mix.get("burst") or {"min": 1, "max": 1}
        b_lo, b_hi = int(burst["min"]), int(burst["max"])
        # Arrival instants come at rate / mean burst, so that the mean
        # request rate is `rate`.
        mean_gap = (b_lo + b_hi) / 2.0 / float(rate)
        jitter = float(mix.get("pace_jitter", 0.1))
        t = 0.0 if kind == "open-poisson" else -mean_gap * float(rng.random())
        while True:
            if kind == "open-poisson":
                t += float(rng.exponential(mean_gap))
            else:
                t += mean_gap * (1.0 + jitter * float(rng.uniform(-1.0, 1.0)))
            if t >= seconds:
                break
            for _ in range(int(rng.integers(b_lo, b_hi + 1))):
                arrivals.append(maker.make(t))
    else:
        n_clients = int(cell.get("clients") or mix.get("clients") or 0)
        if n_clients < 1:
            raise ValueError(
                f"closed-loop mix {mix['name']!r} needs clients, in the mix "
                "or in the cell's file"
            )
        for c in range(n_clients):
            maker = _Maker(mix, seed, 100 + c)
            clients.append([maker.make(0.0) for _ in range(closed_depth)])
    return Plan(
        kind=kind, warmup_sequential=warm_seq, warmup_concurrent=warm_con,
        arrivals=arrivals, clients=clients,
        trace_window_s=float(mix.get("trace_window_s", 4.0)), rate_per_s=rate,
    )
