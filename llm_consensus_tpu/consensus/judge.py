"""LLM-as-Judge consensus synthesis.

Parity: /root/reference/internal/consensus/judge.go:12-105. Behavioral
contract preserved:

  * The judge prompt embeds the user's original prompt plus every panel
    response, each introduced by the separator line
    ``--- Model: <model> | Provider: <provider> ---`` (judge.go:21-25);
    the separator format is load-bearing (asserted by reference tests).
  * Empty response list → error (judge.go:69-71).
  * Exactly one response → returned verbatim with no judge call, still
    invoking the stream callback once (judge.go:74-79).
  * Otherwise a single streamed query against the judge's provider
    (judge.go:96-99). The judge never touches the registry or runner.

The instruction text itself is this framework's own wording — the contract
is the structure, not the prose.
"""

from __future__ import annotations

from typing import Optional

from llm_consensus_tpu.providers import Provider, Request, Response, StreamCallback
from llm_consensus_tpu.utils.context import Context

JUDGE_PROMPT_HEADER = """\
Role
You are a synthesis judge. Several AI models independently answered the same
user prompt; your job is to merge their answers into the single best response.

Inputs
User's original prompt:
{prompt}

Model responses:
"""

JUDGE_PROMPT_FOOTER = """\

Task
Write ONE final answer to the user's original prompt, synthesized from the
model responses above.

Guidelines
1) Honor the intent, scope, tone, and formatting implied by the original
   prompt.
2) Keep the claims that multiple responses agree on or that are best
   justified; when responses conflict, prefer the more specific, more
   logically sound, and safer position, qualifying briefly if real
   uncertainty remains.
3) Add connective material only where needed for completeness — never invent
   facts or pad the answer.

Output requirements
- Output ONLY the synthesized answer: no preamble, no meta-commentary, and no
  mention of the models, their disagreements, or the word "consensus".
- Do not quote or attribute individual model responses.
- Keep it coherent and non-redundant; use structure (headings, bullets, code
  blocks) when it serves the task.
"""


CRITIQUE_PROMPT = """\
{prompt}

A draft answer to the prompt above is shown below. Critique it — identify
errors, omissions, and concrete improvements — then provide your own
corrected and improved answer.

--- Draft answer ---
{draft}
"""


def render_critique_prompt(prompt: str, draft: str) -> str:
    """Panel prompt for refinement rounds (multi-round consensus,
    reference roadmap §2.2: panel critiques the previous synthesis)."""
    return CRITIQUE_PROMPT.format(prompt=prompt, draft=draft)


def render_refine_prompt(prompt: str, draft: str) -> str:
    """The 'user prompt' a refinement round's judge sees: the original
    prompt plus the draft under revision (the critiques arrive as the
    panel responses through the normal judge template)."""
    return (
        f"{prompt}\n\n[Previous draft answer under revision]\n{draft}"
    )


def render_response_block(resp: Response) -> str:
    """One panel answer's block in the judge prompt — separator line +
    content. The separator format is load-bearing (judge.go:21-25,
    asserted by reference tests); this helper is the single owner, shared
    by the one-shot render below and the incremental judge-overlap path
    (consensus/overlap.py), so the two can never diverge."""
    return (
        f"\n--- Model: {resp.model} | Provider: {resp.provider} ---\n"
        f"{resp.content}\n"
    )


def render_judge_prompt(prompt: str, responses: list[Response]) -> str:
    """Render the judge prompt (template semantics of judge.go:12-44)."""
    parts = [JUDGE_PROMPT_HEADER.format(prompt=prompt)]
    for resp in responses:
        parts.append(render_response_block(resp))
    parts.append(JUDGE_PROMPT_FOOTER)
    return "".join(parts)


class NoResponsesError(ValueError):
    """No responses to synthesize (judge.go:69-71)."""

    def __str__(self) -> str:
        return "no responses to synthesize"


class Judge:
    """Synthesizes consensus from multiple model responses (judge.go:48-60)."""

    def __init__(self, provider: Provider, model: str,
                 max_tokens: "int | None" = None,
                 priority: "int | None" = None,
                 trace_id: "str | None" = None):
        self._provider = provider
        self._model = model
        self._max_tokens = max_tokens
        # Cross-hop trace id (obs/live.py): stamps the judge's own
        # engine hop with the serving request's id.
        self._trace = trace_id
        # Judge work outranks panel work by default (pressure/priority):
        # the judge is the run's serialization point — every consumer of
        # the run waits on it — so on a contended engine its stream must
        # not sit behind other runs' panel streams. Explicit callers
        # (the serve scheduler derives judge priority from the request's
        # own class) override.
        self._priority = 0 if priority is None else priority
        # Set by synthesize_stream when the engine had to truncate the judge
        # prompt (long panel concatenation vs the judge's context window);
        # the CLI surfaces it as a run warning.
        self.last_truncated = False
        # Speculative-decode telemetry of the last judge query (rounds,
        # accepted, acceptance EMA, governor state — the judge is the
        # latency tail a drafted/prompt-lookup decode mode exists for,
        # and the judge prompt QUOTES every panel answer, which is
        # exactly the workload prompt lookup wins on). None when the
        # judge's provider ran plain.
        self.last_spec: Optional[dict] = None
        # The last judge query's clock reads through its engine pool and
        # its sizes (Response.marks, prompt tokens as the engine counted
        # them): what serve/scheduler.py builds the result's ``timings``
        # from. None when the provider reports none.
        self.last_marks: Optional[dict] = None

    @property
    def model(self) -> str:
        return self._model

    def synthesize(self, ctx: Context, prompt: str, responses: list[Response]) -> str:
        return self.synthesize_stream(ctx, prompt, responses, None)

    def synthesize_stream(
        self,
        ctx: Context,
        prompt: str,
        responses: list[Response],
        callback: Optional[StreamCallback],
    ) -> str:
        if not responses:
            raise NoResponsesError()
        self.last_truncated = False

        # Single response: no consensus needed, pass it through (judge.go:74-79).
        if len(responses) == 1:
            if callback is not None:
                callback(responses[0].content)
            return responses[0].content

        judge_prompt = render_judge_prompt(prompt, responses)
        try:
            resp = self._provider.query_stream(
                ctx,
                Request(model=self._model, prompt=judge_prompt,
                        max_tokens=self._max_tokens,
                        priority=self._priority,
                        trace_id=self._trace),
                callback,
            )
        except Exception as err:
            raise RuntimeError(f"judge query failed: {err}") from err
        self.last_truncated = resp.truncated
        self.last_spec = getattr(resp, "spec", None)
        self.last_marks = getattr(resp, "marks", None)
        return resp.content
