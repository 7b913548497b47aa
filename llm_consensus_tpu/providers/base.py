"""Provider abstraction — the seam between orchestration and compute.

Parity: /root/reference/internal/provider/provider.go:10-55. The reference's
Provider interface {Query, QueryStream} maps to the abstract base below; its
ProviderFunc adapter (provider.go:39-55) — the seam every reference test is
built on — maps to :class:`ProviderFunc`.

One deliberate deviation: the reference marshals ``Response.Latency`` (a Go
``time.Duration``, i.e. nanoseconds) under the JSON key ``latency_ms``
(provider.go:34) — so the JSON value is in nanoseconds despite the name.
Here ``latency_ms`` genuinely holds milliseconds.
"""

from __future__ import annotations

import abc
from dataclasses import dataclass, field
from typing import Callable, Optional

from llm_consensus_tpu.utils.context import Context

# Called once per streamed chunk of incremental text (provider.go:10).
StreamCallback = Callable[[str], None]


@dataclass(frozen=True)
class Request:
    """All inputs for one LLM query (provider.go:24-27).

    ``max_tokens`` / ``temperature`` are TPU-build extensions consumed by the
    on-device engine; HTTP providers and fakes may ignore them.
    """

    model: str
    prompt: str
    max_tokens: Optional[int] = None
    temperature: Optional[float] = None
    system: Optional[str] = None  # system prompt (TPU-build extension)
    # Priority class (pressure/priority.py: HIGH=0/NORMAL=1/LOW=2) —
    # orders continuous-batcher admission and selects preemption
    # victims. None = NORMAL; HTTP providers and fakes may ignore it.
    priority: Optional[int] = None
    # Cross-hop request trace id (obs/live.py): minted at the fleet
    # router or the gateway and threaded through runner workers into
    # engine-level spans, so one id recovers the full path of a request.
    # None outside the serving path; providers treat it as opaque.
    trace_id: Optional[str] = None
    # Live-migration resume payload (serve/elastic.py): the sealed
    # journal snapshot for THIS model's stream — {"prompt_ids": [...],
    # "sampling": {...}, "tokens": [...]} — or an emitted-text prefix
    # {"text": "..."}. Engine providers replay it through the journal
    # path (recovery/journal.py) so the resumed stream re-emits the
    # prefix and continues; providers without replay ignore it (safe:
    # deterministic decode re-derives the prefix and the router's
    # stream ledger burns the duplicate bytes).
    resume: Optional[dict] = None


@dataclass
class Response:
    """Result of one LLM query (provider.go:30-35).

    ``truncated`` is a TPU-build extension: the on-device engine sets it
    when the prompt had to be middle-out truncated to fit the model's
    context window (engine/engine.py). ``tokens`` / ``tokens_per_sec`` /
    ``mfu`` / ``mbu`` are on-device throughput measurements (utils/flops.py) — real
    generated-token counts and decode MFU, versus the reference's chars/4
    display estimate (ui.go:142). All extensions serialize only when set,
    so the reference JSON shape is unchanged in the common case.
    """

    model: str
    content: str
    provider: str
    latency_ms: float = 0.0
    truncated: bool = False
    tokens: Optional[int] = None
    tokens_per_sec: Optional[float] = None
    mfu: Optional[float] = None
    mbu: Optional[float] = None  # memory-bandwidth utilization (decode)
    # Speculative-decode telemetry for this query (rounds, accepted,
    # acceptance EMA, governor state — engine/speculative.py); None on
    # plain paths, so the reference JSON shape is unchanged without it.
    spec: Optional[dict] = None
    # KV-reuse degradation for this query: {"truncated": True} when the
    # paged pool's arena exhausted while publishing this context's
    # prefix — reuse of it is silently degraded, and operators should
    # see that per response, not only in lifetime counters.
    kv: Optional[dict] = None
    # This stream was preempted (and byte-identically resumed) at least
    # once by the pressure scheduler (engine/batcher.preempt) — the
    # live-metrics plane labels the request's latency outcome with it.
    preempted: bool = False
    # Clock reads of this query's way through its engine pool
    # (``time.monotonic_ns``: admit_ns, first_token_ns, first_chunk_ns —
    # engine/batcher.py); never serialized. The serving tier turns the
    # judge's into the result's ``timings``.
    marks: Optional[dict] = None

    def to_dict(self) -> dict:
        """JSON shape parity with the reference's Response tags."""
        d = {
            "model": self.model,
            "content": self.content,
            "provider": self.provider,
            "latency_ms": self.latency_ms,
        }
        if self.truncated:
            d["truncated"] = True
        if self.tokens is not None:
            d["tokens"] = self.tokens
        if self.tokens_per_sec is not None:
            d["tokens_per_sec"] = round(self.tokens_per_sec, 2)
        if self.mfu is not None:
            d["mfu"] = round(self.mfu, 4)
        if self.mbu is not None:
            d["mbu"] = round(self.mbu, 4)
        if self.spec is not None:
            d["spec"] = dict(self.spec)
        if self.kv is not None:
            d["kv"] = dict(self.kv)
        if self.preempted:
            d["preempted"] = True
        return d


class Provider(abc.ABC):
    """Abstracts LLM interactions — remote HTTP or on-device TPU engine."""

    def prepare(self, models: list[str], judge: Optional[str]) -> None:
        """Announce the full run composition before any query (TPU-build seam).

        The reference never needs this — each HTTP provider is stateless —
        but the on-device provider must place N panel models plus the judge
        on disjoint device-mesh slices, and slicing decisions require the
        whole panel at once (parallel/mesh.py). The CLI and bench call this
        once, after registry init and before the fan-out. Default: no-op.
        """

    @abc.abstractmethod
    def query(self, ctx: Context, req: Request) -> Response:
        """Send a prompt and return the complete response."""

    @abc.abstractmethod
    def query_stream(
        self, ctx: Context, req: Request, callback: Optional[StreamCallback]
    ) -> Response:
        """Send a prompt, invoking ``callback`` per chunk; returns the full response."""


class ProviderFunc(Provider):
    """Function adapter implementing Provider (provider.go:39-55).

    ``query_stream`` calls the function once and fires the callback with the
    full content — exactly the reference adapter's behavior, which tests and
    simple providers rely on.
    """

    def __init__(self, fn: Callable[[Context, Request], Response]):
        self._fn = fn

    def query(self, ctx: Context, req: Request) -> Response:
        return self._fn(ctx, req)

    def query_stream(
        self, ctx: Context, req: Request, callback: Optional[StreamCallback]
    ) -> Response:
        resp = self.query(ctx, req)
        if callback is not None:
            callback(resp.content)
        return resp
