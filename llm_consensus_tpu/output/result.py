"""The stable JSON output contract of a consensus run.

Parity: /root/reference/internal/output/output.go:8-15 — field order and
names match the reference's JSON tags, with ``warnings`` and
``failed_models`` omitted when empty (omitempty).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field

from llm_consensus_tpu.providers import Response


@dataclass
class Result:
    prompt: str
    responses: list[Response]
    consensus: str
    judge: str
    warnings: list[str] = field(default_factory=list)
    failed_models: list[str] = field(default_factory=list)
    # Conversation history for --continue (TPU-build extension, reference
    # roadmap §3.1): earlier {prompt, consensus} exchanges, oldest first.
    # Omitted when empty so the reference JSON shape is unchanged.
    history: list[dict] = field(default_factory=list)
    # Panel agreement analysis (roadmap §2.4): {score, level, divergence}.
    agreement: "dict | None" = None
    # LLM-graded confidence in the consensus (roadmap §2.4, --confidence):
    # {score: 0-100 | null, controversy: [str]}.
    confidence: "dict | None" = None
    # Where a SERVED run's time went (serve/scheduler.py run_timings:
    # queue, panel, judge queue / prefill / first chunk / decode, total,
    # judge prompt and answer tokens). Last, and omitted on paths that do
    # not have it, so the reference shape and field order are unchanged.
    timings: "dict | None" = None

    def to_dict(self) -> dict:
        out = {
            "prompt": self.prompt,
            "responses": [r.to_dict() for r in self.responses],
            "consensus": self.consensus,
            "judge": self.judge,
        }
        if self.warnings:
            out["warnings"] = self.warnings
        if self.failed_models:
            out["failed_models"] = self.failed_models
        if self.history:
            out["history"] = self.history
        if self.agreement is not None:
            out["agreement"] = self.agreement
        if self.confidence is not None:
            out["confidence"] = self.confidence
        if self.timings is not None:
            out["timings"] = self.timings
        return out

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent, ensure_ascii=False) + "\n"
