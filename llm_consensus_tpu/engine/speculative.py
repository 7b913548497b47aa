"""Speculative decoding: a drafter proposes, the target verifies.

Single-stream decode is HBM-bound — each target step streams the full
weight set to produce ONE token. Verifying ``k`` draft tokens in one
forward streams those same weights once for up to ``k+1`` tokens of
progress, so wall-clock speedup ≈ (mean accepted run length) × (cost
ratio amortization) − draft overhead.

Three draft sources behind one :class:`Drafter` interface:

  * :class:`ModelDrafter` — the classic second-model drafter (a warm 1B
    drafting for the 8B judge): the draft runs the same engine machinery
    on a smaller preset, chained on device via ``_spec_draft``.
  * :class:`PromptLookupDrafter` — n-gram prompt lookup: proposals are
    the continuation of the most recent earlier occurrence of the last
    ``g`` known tokens, matched ON DEVICE against a token ring buffer
    holding prompt + accepted output. ZERO draft-model cost, and the
    judge — which quotes panel answers heavily — is exactly the
    copy-heavy workload it wins on. Because the buffer is device data,
    proposing never round-trips to the host, so rounds pipeline.
  * :class:`OracleDrafter` — replays a known continuation (the target's
    own greedy output), optionally truncated to a forced acceptance
    level. Bench/tests only: it measures the MACHINERY's ceiling (every
    round accepts k+1 ⇒ verify dispatch cost ≈ 1 plain step) and sweeps
    the break-even acceptance curve independent of any real drafter.

TPU-first structure — single-forward programs per round, chained on
device:

  * A spec ROUND is one draft proposal (a ``_spec_draft`` scan for the
    model drafter; one tiny vector program for buffer drafters) then
    ONE target forward over ``k+1`` positions + on-device acceptance.
    All shapes are static; the variable acceptance count is data, not
    shape. The host chains round dispatches with the carry (tokens,
    position, caches, token buffer) fully device-resident and fetches
    accepted tokens in batches, so the transfer round trip amortizes.
  * **No cache rollback** (single stream): rejected positions hold junk
    KV beyond the accepted frontier, and every later round re-writes a
    position before any read reaches it. The BATCHED form (see
    ``_spec_verify_batch``) cannot re-write — rows share one frontier —
    so rejected slots become per-row HOLES masked by a written-slot
    bitmap instead (the ``kv_mask`` path in models/transformer.py).
  * **Greedy acceptance** (temperature 0): accept the longest prefix
    where the target's argmax equals the draft token, then take the
    target's argmax at the first mismatch — the output is TOKEN-EXACT
    against plain greedy decoding for ANY draft; the draft only changes
    speed, never text.
  * **Rejection-sampling acceptance** (temperature > 0, no top-k/top-p,
    model drafter only): the standard speculative-sampling scheme whose
    OUTPUT DISTRIBUTION is exactly the target's for any draft.

Control plane (host-side, both tiers):

  * :class:`AdaptiveK` — per-stream acceptance EMA drives the draft
    length along a pow2 ladder {1, 2, …, k_max} (static ``k`` is program
    identity, so the ladder bounds compiles at log2(k_max)): shrink
    toward 1 when acceptance collapses (wasted draft + verify width),
    regrow on sustained wins.
  * :class:`SpecGovernor` — an online drafted-vs-plain A/B: measure a
    window of spec-mode tokens/s, then a window of PLAIN decode on the
    same carry (both modes produce identical greedy tokens, so switching
    is free), lock the faster mode. A stream whose drafter is losing
    therefore converges to plain throughput — drafted-enabled serving is
    never slower than plain at steady state, which the adversarial
    (acceptance→1) bench point pins.

Speedup arithmetic (per token): plain decode costs 1 target step;
speculation costs (draft + v) / a where v ≈ 1 is the k+1-token verify
(HBM-bound, same weight stream as one step) and a = mean accepted tokens
per round ∈ [1, k+1]. The prompt-lookup drafter's draft term is ~0, so
it pays whenever a > v — i.e. whenever the output quotes its context.
The bench's random-init models have uncorrelated argmaxes (a → 1) for
REAL drafters, so the oracle phase is what measures the machinery.

The reference has no analog (its compute is remote HTTP APIs —
SURVEY.md §2); this is the serving-latency extension of the roadmap.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from llm_consensus_tpu.obs.attrib import tag as _attrib_tag
from llm_consensus_tpu.engine.engine import (
    Engine, GenerateResult, SamplingParams, _decode_chunk)
from llm_consensus_tpu.engine.tokenizer import StreamDecoder
from llm_consensus_tpu.models import forward
from llm_consensus_tpu.models.config import ModelConfig
from llm_consensus_tpu.ops.quant import w8a8_scope
from llm_consensus_tpu.utils.context import Context
from llm_consensus_tpu.utils import knobs


# -- host-side control plane -------------------------------------------------


def k_ladder(k_max: int) -> list[int]:
    """The pow2 draft-length ladder {1, 2, 4, …} ∪ {k_max}: every distinct
    ``k`` is a compiled program pair (propose + verify), so adaptive k
    walks a log-bounded set instead of discovering arbitrary values."""
    ladder = []
    v = 1
    while v < k_max:
        ladder.append(v)
        v *= 2
    ladder.append(k_max)
    return ladder


class AdaptiveK:
    """Per-stream draft-length controller on an acceptance EMA.

    ``observe(accepted, k_used)`` feeds one round's accepted count (in
    [1, k_used+1]); ``k`` is the ladder rung the next round should use.
    Policy: regrow one rung when the EMA sits near the current ceiling
    (the drafter is being truncated), shrink one rung when the EMA says
    rounds mostly deliver only the correction token (draft cost + verify
    width bought nothing). The EMA resets toward the new regime on its
    own — no explicit phase detection."""

    def __init__(self, k_max: int, alpha: float = 0.25,
                 adaptive: bool = True):
        self.ladder = k_ladder(max(1, k_max))
        self._i = len(self.ladder) - 1  # start at k_max: optimistic
        self.alpha = alpha
        self.adaptive = adaptive
        self.ema = 1.0 + self.ladder[self._i] / 2.0  # neutral prior

    @property
    def k(self) -> int:
        return self.ladder[self._i]

    def observe(self, accepted: float, k_used: int) -> None:
        self.ema += self.alpha * (accepted - self.ema)
        if not self.adaptive:
            return
        if self.ema >= 0.8 * (k_used + 1) and self._i < len(self.ladder) - 1:
            self._i += 1
        elif self.ema <= 1.35 and self._i > 0:
            self._i -= 1


class SpecGovernor:
    """Online drafted-vs-plain A/B for one stream (or one pool).

    State machine: ``spec_probe`` → ``plain_probe`` → ``spec_locked`` |
    ``plain_locked``. Each probe measures ``probe_tokens`` emitted tokens
    of wall time in its mode; the decision locks the faster mode for the
    rest of the stream. Greedy modes emit identical tokens, so switching
    costs nothing but the measurement itself — the total exposure to a
    losing drafter is ONE spec probe window, which is what makes the
    "never slower than plain at steady state" guarantee hold: steady
    state IS the locked mode. ``feed`` is called at drain/fetch
    boundaries (the only points where wall time attributes cleanly)."""

    def __init__(self, probe_tokens: int = 64, enabled: bool = True):
        self.enabled = enabled
        self.probe_tokens = max(1, probe_tokens)
        self.state = "spec_probe" if enabled else "spec_locked"
        self._tokens = 0
        self._wall = 0.0
        self._spec_rate: Optional[float] = None
        self.disabled_spec = False  # plain won the A/B

    @property
    def mode(self) -> str:
        """"spec" or "plain" — what the next dispatch should run."""
        return "plain" if self.state in ("plain_probe", "plain_locked") \
            else "spec"

    def feed(self, tokens: int, wall: float) -> bool:
        """Account one drained window in the CURRENT mode. Returns True
        when the mode just changed (the caller must drain + switch
        carries before the next dispatch)."""
        if self.state in ("spec_locked", "plain_locked"):
            return False
        self._tokens += tokens
        self._wall += wall
        if self._tokens < self.probe_tokens:
            return False
        rate = self._tokens / max(self._wall, 1e-9)
        if self.state == "spec_probe":
            self._spec_rate = rate
            self.state = "plain_probe"
            self._tokens, self._wall = 0, 0.0
            return True
        # plain_probe decided
        if self._spec_rate is not None and self._spec_rate >= rate:
            self.state = "spec_locked"
            return True
        self.state = "plain_locked"
        self.disabled_spec = True
        return False  # already in plain mode; no carry switch needed


@dataclass(frozen=True)
class SpecConfig:
    """Speculation plan for a continuous-batching pool (and the provider
    seam): which drafter, the k ceiling, and the control-plane knobs.
    ``oracle`` maps prompt ids → a known continuation (bench/tests)."""

    kind: str                 # "lookup" | "oracle"
    k: int = 4
    ngram: int = 3
    adaptive: bool = True
    governor: bool = True
    probe_tokens: int = 64
    oracle: Optional[Callable] = None  # (prompt_ids: list) -> list[int]
    oracle_accept: Optional[int] = None  # force per-round acceptance


def spec_config_from_env(kind: str = "lookup", k: Optional[int] = None,
                         ngram: Optional[int] = None,
                         oracle: Optional[Callable] = None,
                         oracle_accept: Optional[int] = None) -> SpecConfig:
    """SpecConfig from the LLMC_SPEC* knobs (the provider/serving seam).

    The ONE owner of the env defaults: :class:`SpeculativeEngine` reads
    its control-plane defaults through here too, so the single-stream
    and batched tiers obey one set of knobs."""
    return SpecConfig(
        kind=kind,
        k=k if k is not None else max(1, knobs.get_int("LLMC_SPEC_K")),
        ngram=ngram if ngram is not None else max(
            1, knobs.get_int("LLMC_SPEC_NGRAM")
        ),
        adaptive=knobs.get_bool("LLMC_SPEC_ADAPT"),
        governor=knobs.get_bool("LLMC_SPEC_GOVERNOR"),
        probe_tokens=knobs.get_int("LLMC_SPEC_PROBE"),
        oracle=oracle,
        oracle_accept=oracle_accept,
    )


# -- drafter interface -------------------------------------------------------


class Drafter:
    """One draft source. ``kind`` routes tier-specific dispatch:

    * ``needs_buffer`` drafters propose from the device token buffer
      (prompt + accepted output) — they compose with round pipelining
      (no host round trip) and with the batched shared-frontier pool.
    * The model drafter carries its own KV cache; it serves the
      single-stream latency tier only (a per-slot draft cache under the
      shared frontier is future work).
    """

    kind = "base"
    needs_buffer = False
    batch_ok = False


class ModelDrafter(Drafter):
    """A second (smaller) engine proposes autoregressively."""

    kind = "model"

    def __init__(self, engine: Engine):
        self.engine = engine


class PromptLookupDrafter(Drafter):
    """n-gram prompt lookup: propose the continuation of the most recent
    earlier occurrence of the last ``ngram`` known tokens. Device-side
    (see ``_lookup_propose``), zero model cost."""

    kind = "lookup"
    needs_buffer = True
    batch_ok = True

    def __init__(self, ngram: int = 3):
        if ngram < 1:
            raise ValueError("ngram must be >= 1")
        self.ngram = ngram


class OracleDrafter(Drafter):
    """Replays a known continuation of the prompt (bench/tests).

    ``accept`` forces per-round acceptance: the first ``accept − 1``
    proposals are the oracle's (the target will agree), the rest are
    deliberately perturbed (``(tok + 1) % vocab`` — never equal to the
    target's argmax, so rejected deterministically). ``accept=None``
    replays everything ⇒ every round accepts k+1."""

    kind = "oracle"
    needs_buffer = True
    batch_ok = True

    def __init__(self, continuation_ids: list, accept: Optional[int] = None):
        self.continuation_ids = list(continuation_ids)
        self.accept = accept


# -- single-stream device programs (model drafter) ---------------------------

# The round is split into TWO single-forward programs instead of one
# scan-of-rounds: a scan body containing several forwards (draft opener,
# draft steps, verify) defeats XLA's in-place aliasing — profiling the
# fused form showed full weight and cache stacks copied every round. With
# one forward per program, each program is the same carry shape the
# decode chunk uses (proven to alias), donation carries the caches
# across dispatches, and the host chains dispatches with device-resident
# (prev, cur, pos) so nothing round-trips until tokens are fetched.


@partial(
    jax.jit,
    static_argnames=("dcfg", "k", "kv_width"),
    donate_argnames=("dcache",),
)
def _spec_draft(dparams, dcfg: ModelConfig, prev_tok, cur_tok, pos, dcache,
                k: int, kv_width=None):
    """Draft ``k`` proposals as ONE uniform scan of 1-token steps.

    Steps 0 and 1 ingest ``prev`` (at pos-1, an idempotent re-write that
    covers the bonus-token case where the draft never saw the previous
    round's last accepted token) and ``cur``; steps 1..k emit proposals.
    """
    def body(carry, i):
        tok, dcache = carry
        tok_in = jnp.where(i == 0, prev_tok, tok)
        lg, dcache = forward(
            dparams, dcfg, tok_in[:, None], dcache,
            start_pos=pos - 1 + i, kv_width=kv_width,
        )
        nxt = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)
        # Step 0's output is discarded; step 1 must input ``cur``.
        return (jnp.where(i == 0, cur_tok, nxt), dcache), nxt

    (_, dcache), outs = jax.lax.scan(
        body, (prev_tok, dcache), jnp.arange(k + 1)
    )
    return outs[1:, 0], dcache  # [k] proposals


@partial(
    jax.jit, static_argnames=("dcfg", "n", "kv_width"),
    donate_argnames=("dcache",),
)
def _draft_ingest(dparams, dcfg: ModelConfig, toks, pos, dcache,
                  n: int, kv_width=None):
    """Catch the draft cache up over ``n`` tokens the target decoded in a
    PLAIN governor window (the draft never saw them): one forward over
    the window, logits discarded. Without this, re-entering spec after a
    plain probe would condition the draft on junk KV — still token-exact
    (exactness never depends on the draft) but acceptance would collapse
    for no reason."""
    _, dcache = forward(
        dparams, dcfg, toks, dcache, start_pos=pos, kv_width=kv_width,
    )
    return dcache


@partial(
    jax.jit,
    static_argnames=("tcfg", "kv_width"),
    donate_argnames=("tcache",),
)
def _spec_verify(tparams, tcfg: ModelConfig, cur_tok, drafts, pos, tcache,
                 kv_width=None):
    """One target forward over [cur, d_1..d_k]; greedy acceptance.

    greedy[i-1] is the target's token after seeing d_1..d_{i-1}; accept
    the longest matching draft prefix plus greedy[leading] (the
    correction, or the bonus when every draft matched): a ∈ [1, k+1].
    Returns (out [k+1], a, prev', cur', pos', tcache).
    """
    k = drafts.shape[0]
    vin = jnp.concatenate([cur_tok, drafts])[None, :]  # [1, k+1]
    tlogits, tcache = forward(
        tparams, tcfg, vin, tcache, start_pos=pos, kv_width=kv_width,
    )
    greedy = jnp.argmax(tlogits[0], axis=-1).astype(jnp.int32)  # [k+1]
    matches = drafts == greedy[:-1]
    leading = jnp.argmin(
        jnp.concatenate([matches, jnp.zeros((1,), bool)])
    ).astype(jnp.int32)
    a = leading + 1
    idx = jnp.arange(k + 1, dtype=jnp.int32)
    out = jnp.where(
        idx < leading,
        jnp.concatenate([drafts, jnp.zeros((1,), jnp.int32)]),
        jnp.where(idx == leading, greedy[leading], 0),
    )
    new_pos = pos + a
    new_cur = out[leading]
    new_prev = jnp.where(leading > 0, out[leading - 1], cur_tok[0])
    return out, a, new_prev[None], new_cur[None], new_pos, tcache


@partial(
    jax.jit,
    static_argnames=("dcfg", "k", "temperature", "kv_width"),
    donate_argnames=("dcache",),
)
def _spec_draft_sampled(dparams, dcfg: ModelConfig, prev_tok, cur_tok, pos,
                        dcache, key, k: int, temperature: float,
                        kv_width=None):
    """Sampled drafting: k proposals drawn from the draft's temperature
    distribution, returned WITH each step's full probability vector —
    rejection sampling needs q(·), not just the sampled token."""
    def body(carry, i):
        tok, dcache = carry
        tok_in = jnp.where(i == 0, prev_tok, tok)
        lg, dcache = forward(
            dparams, dcfg, tok_in[:, None], dcache,
            start_pos=pos - 1 + i, kv_width=kv_width,
        )
        scaled = lg[0, -1].astype(jnp.float32) / temperature
        q = jax.nn.softmax(scaled)
        nxt = jax.random.categorical(
            jax.random.fold_in(key, i), scaled
        ).astype(jnp.int32)[None]
        return (jnp.where(i == 0, cur_tok, nxt), dcache), (nxt, q)

    (_, dcache), (outs, qs) = jax.lax.scan(
        body, (prev_tok, dcache), jnp.arange(k + 1)
    )
    return outs[1:, 0], qs[1:], dcache  # [k] proposals, [k, V] draft probs


@partial(
    jax.jit,
    static_argnames=("tcfg", "temperature", "kv_width"),
    donate_argnames=("tcache",),
)
def _spec_verify_sampled(tparams, tcfg: ModelConfig, cur_tok, drafts, qs,
                         pos, tcache, key, temperature: float, kv_width=None):
    """One target forward + rejection sampling (Leviathan et al. 2023).

    Draft token d_i is accepted with prob min(1, p_i(d_i)/q_i(d_i)); the
    first rejection resamples from the residual max(p_i − q_i, 0)
    normalized, and a fully-accepted round draws the bonus token from
    p_k — together this makes the OUTPUT DISTRIBUTION exactly the
    target's temperature distribution for any draft (the draft only
    changes speed), the sampled-decoding analog of greedy exactness.
    """
    k = drafts.shape[0]
    vin = jnp.concatenate([cur_tok, drafts])[None, :]  # [1, k+1]
    tlogits, tcache = forward(
        tparams, tcfg, vin, tcache, start_pos=pos, kv_width=kv_width,
    )
    ps = jax.nn.softmax(
        tlogits[0].astype(jnp.float32) / temperature, axis=-1
    )  # [k+1, V]
    rows = jnp.arange(k)
    p_of_d = ps[rows, drafts]
    q_of_d = qs[rows, drafts]
    us = jax.random.uniform(jax.random.fold_in(key, 0), (k,))
    accept = us < jnp.minimum(1.0, p_of_d / jnp.maximum(q_of_d, 1e-30))
    leading = jnp.argmin(
        jnp.concatenate([accept, jnp.zeros((1,), bool)])
    ).astype(jnp.int32)
    a = leading + 1
    # Correction token: residual distribution at the first rejection
    # (max(p − q, 0), renormalized by categorical's implicit softmax
    # normalization), the raw target distribution if the residual is
    # numerically empty, or the bonus draw from p_k when every draft
    # was accepted.
    q_at = qs[jnp.minimum(leading, k - 1)]
    p_at = ps[leading]
    resid = jnp.maximum(p_at - q_at, 0.0)
    use_resid = jnp.logical_and(leading < k, jnp.sum(resid) > 1e-12)
    corr_probs = jnp.where(use_resid, resid, p_at)
    corr = jax.random.categorical(
        jax.random.fold_in(key, 1),
        jnp.log(jnp.maximum(corr_probs, 1e-38)),
    ).astype(jnp.int32)
    idx = jnp.arange(k + 1, dtype=jnp.int32)
    out = jnp.where(
        idx < leading,
        jnp.concatenate([drafts, jnp.zeros((1,), jnp.int32)]),
        jnp.where(idx == leading, corr, 0),
    )
    new_pos = pos + a
    new_cur = out[leading]
    new_prev = jnp.where(leading > 0, out[leading - 1], cur_tok[0])
    return out, a, new_prev[None], new_cur[None], new_pos, tcache


# -- buffer-drafter programs (any batch size) --------------------------------


@partial(jax.jit, static_argnames=("k", "g"))
def _lookup_propose(buf, blen, k: int, g: int):
    """Prompt-lookup proposals for every row: [B, k].

    ``buf`` [B, S] holds each row's known tokens (prompt + accepted
    output, ``blen`` of them — the last one is the stream's current
    token). The gram is the last ``g`` known tokens; the proposal is the
    continuation after the MOST RECENT earlier occurrence of that gram
    (max source position p < blen − g), or the current token repeated
    when nothing matches (repetition is the cheapest correlated guess,
    and a wrong guess only costs the round's unaccepted tail). Pure
    vector ops — O(B · S · g) compares, trivial next to any forward —
    so proposing is one tiny dispatch and rounds keep pipelining.
    """
    b, s = buf.shape
    rows = jnp.arange(b)[:, None]
    gram = jnp.take_along_axis(
        buf, jnp.maximum(blen[:, None] - g + jnp.arange(g)[None, :], 0), 1
    )  # [B, g]
    n_src = s - g  # candidate source positions p ∈ [0, n_src)
    match = jnp.ones((b, n_src), bool)
    for j in range(g):
        match = jnp.logical_and(match, buf[:, j:j + n_src] == gram[:, j:j + 1])
    # p + g ≤ blen − 1: the gram's own trailing occurrence is excluded
    # and the continuation starts at a known token.
    match = jnp.logical_and(
        match, jnp.arange(n_src)[None, :] < (blen - g)[:, None]
    )
    p_best = jnp.max(
        jnp.where(match, jnp.arange(n_src, dtype=jnp.int32)[None, :], -1),
        axis=1,
    )  # [B], -1 = no match
    src = jnp.clip(p_best[:, None] + g + jnp.arange(k)[None, :], 0, s - 1)
    props = jnp.take_along_axis(buf, src, 1)
    last = jnp.take_along_axis(buf, jnp.maximum(blen - 1, 0)[:, None], 1)
    return jnp.where(p_best[:, None] >= 0, props, last)  # [B, k]


@partial(jax.jit, static_argnames=("k", "vocab", "accept"))
def _oracle_propose(obuf, blen, k: int, vocab: int, accept=None):
    """Oracle proposals: the known continuation ``obuf[blen : blen+k]``
    (token p of the stream lives at ``obuf[p]``; the current token is
    position blen−1). ``accept`` perturbs proposals past the first
    ``accept − 1`` to ``(tok+1) % vocab`` — guaranteed ≠ the oracle
    token the target's argmax will produce, so each round accepts
    EXACTLY ``accept`` (the bench's acceptance-sweep knob)."""
    s = obuf.shape[1]
    src = jnp.clip(blen[:, None] + jnp.arange(k)[None, :], 0, s - 1)
    props = jnp.take_along_axis(obuf, src, 1)
    if accept is not None:
        junk = (props + 1) % vocab
        props = jnp.where(jnp.arange(k)[None, :] < accept - 1, props, junk)
    return props


@partial(jax.jit, static_argnames=("k", "vocab"))
def _junk_propose(buf, blen, k: int, vocab: int):
    """Deterministic garbage proposals (the ``acceptance_collapse``
    fault): last-token-derived, never the obvious continuation.
    Exactness is untouchable by construction — acceptance only keeps
    proposals the target's argmax equals — so this is purely a SPEED
    fault: acceptance pins to ~1 and the adaptive-k / governor machinery
    must absorb it."""
    last = jnp.take_along_axis(buf, jnp.maximum(blen - 1, 0)[:, None], 1)
    return (last + 1 + jnp.arange(k)[None, :]) % vocab


@partial(
    jax.jit,
    static_argnames=("tcfg", "kv_width", "w8a8"),
    donate_argnames=("tcache", "buf"),
)
def _spec_verify_buf(tparams, tcfg: ModelConfig, cur_tok, drafts, pos,
                     blen, tcache, buf, kv_width=None, w8a8: bool = False):
    """Single-stream verify that also maintains the token buffer.

    Same acceptance math as ``_spec_verify`` (per-stream frontier, no
    holes — later rounds re-write rejected positions) plus: accepted
    tokens scatter into ``buf`` at ``blen`` so buffer drafters can
    propose from them next round without any host round trip. Returns
    (out [k+1], a, cur', pos', blen', tcache, buf).
    """
    k = drafts.shape[0]
    vin = jnp.concatenate([cur_tok, drafts])[None, :]  # [1, k+1]
    with w8a8_scope(w8a8):
        tlogits, tcache = forward(
            tparams, tcfg, vin, tcache, start_pos=pos, kv_width=kv_width,
        )
    greedy = jnp.argmax(tlogits[0], axis=-1).astype(jnp.int32)  # [k+1]
    matches = drafts == greedy[:-1]
    leading = jnp.argmin(
        jnp.concatenate([matches, jnp.zeros((1,), bool)])
    ).astype(jnp.int32)
    a = leading + 1
    idx = jnp.arange(k + 1, dtype=jnp.int32)
    out = jnp.where(
        idx < leading,
        jnp.concatenate([drafts, jnp.zeros((1,), jnp.int32)]),
        jnp.where(idx == leading, greedy[leading], 0),
    )
    bidx = jnp.minimum(blen + idx, buf.shape[1] - 1)[None, :]
    old = jnp.take_along_axis(buf, bidx, 1)
    buf = buf.at[jnp.zeros((1, k + 1), jnp.int32), bidx].set(
        jnp.where((idx < a)[None, :], out[None, :], old)
    )
    return out, a, out[leading][None], pos + a, blen + a, tcache, buf


@partial(jax.jit, static_argnames=("n",), donate_argnames=("buf",))
def _append_buf(buf, blen, toks, n: int):
    """Append a plain decode chunk's ``n`` tokens ([n, 1]) to the buffer
    (governor plain windows keep the buffer current so a later return to
    spec proposes from the full history)."""
    idx = jnp.minimum(blen + jnp.arange(n), buf.shape[1] - 1)[None, :]
    buf = buf.at[jnp.zeros((1, n), jnp.int32), idx].set(toks[None, :, 0])
    return buf, blen + n


# -- batched (shared-frontier) programs --------------------------------------


@partial(
    jax.jit,
    static_argnames=("cfg", "k", "kv_width", "w8a8"),
    donate_argnames=("cache", "valid", "buf"),
)
def _spec_verify_batch(params, cfg: ModelConfig, cur, drafts, pos, row_start,
                       blen, cache, valid, buf, k: int, kv_width=None,
                       w8a8: bool = False):
    """One target dispatch verifies ``k+1`` positions for EVERY resident
    row — B×(k+1) tokens per weight stream, the batch-1 MFU fix.

    Shared-frontier-with-holes carry (the design that keeps the pool's
    one-scalar write position): every round writes slots [pos, pos+k]
    for all rows and the frontier advances k+1 — HOST-KNOWN, so
    admission splicing, capacity checks, and compaction keep their
    shared-frontier arithmetic. Per-row acceptance a_i is DATA:

      * slots [pos+a_i, pos+k] become per-row HOLES — junk KV that is
        never rewritten (rows share the frontier, so no row can re-use
        another's slots). The ``valid`` bitmap [B, S] masks them at
        attention time (the ``kv_mask`` path in the transformer); this
        round's own window is pre-marked fully valid so the in-window
        causal triangle comes from positions, then trimmed to a_i for
        every later round.
      * ``row_start`` absorbs the holes: the invariant is
        row_start_i = pos − blen_i + 1 (slot s of a NEW write holds
        logical position s − row_start_i), so each round adds
        (k+1 − a_i). Old valid slots' positions computed from the
        current row_start underestimate their write-time positions —
        harmless for full attention (they are all strictly past), which
        is why kv_mask gates sliding_window off.
      * ``blen``/``buf`` track each row's LOGICAL sequence (no holes):
        accepted tokens scatter at blen_i, feeding the lookup drafter.

    Returns (out [B, k+1], a [B], cur', row_start', blen', cache, valid,
    buf).
    """
    b = cur.shape[0]
    idx = jnp.arange(k + 1, dtype=jnp.int32)[None, :]  # [1, k+1]
    # Pre-mark the write window valid for every row: queries must see
    # the window's earlier tokens (causality via positions), and stale
    # bitmap content at these slots (pre-compaction wrap) must not leak.
    valid = jax.lax.dynamic_update_slice(
        valid, jnp.ones((b, k + 1), bool), (0, pos)
    )
    vin = jnp.concatenate([cur[:, None], drafts], axis=1)  # [B, k+1]
    with w8a8_scope(w8a8):
        logits, cache = forward(
            params, cfg, vin, cache, start_pos=pos, row_start=row_start,
            kv_width=kv_width, kv_mask=valid,
        )
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B, k+1]
    matches = drafts == greedy[:, :-1]
    leading = jnp.argmin(
        jnp.concatenate([matches, jnp.zeros((b, 1), bool)], axis=1), axis=1
    ).astype(jnp.int32)  # [B]
    a = leading + 1
    dpad = jnp.concatenate([drafts, jnp.zeros((b, 1), jnp.int32)], axis=1)
    corr = jnp.take_along_axis(greedy, leading[:, None], 1)
    out = jnp.where(
        idx < leading[:, None], dpad,
        jnp.where(idx == leading[:, None], corr, 0),
    )
    new_cur = jnp.take_along_axis(out, leading[:, None], 1)[:, 0]
    # Trim the window to the accepted prefix for all later rounds.
    valid = jax.lax.dynamic_update_slice(
        valid, idx < a[:, None], (0, pos)
    )
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, k + 1))
    bidx = jnp.minimum(blen[:, None] + idx, buf.shape[1] - 1)
    old = jnp.take_along_axis(buf, bidx, 1)
    buf = buf.at[rows, bidx].set(jnp.where(idx < a[:, None], out, old))
    return (out, a, new_cur, row_start + (k + 1) - a, blen + a,
            cache, valid, buf)


@partial(
    jax.jit,
    static_argnames=("cfg", "n_steps", "kv_width", "w8a8"),
    donate_argnames=("cache", "valid", "buf"),
)
def _plain_chunk_masked(params, cfg: ModelConfig, token, pos, row_start,
                        blen, cache, valid, buf, n_steps: int,
                        kv_width=None, w8a8: bool = False):
    """``n_steps`` greedy decode steps over a HOLEY pool cache (the
    governor's plain mode, and the cache tail, of a spec-enabled pool):
    the engine's ``_decode_chunk`` shape plus the written-slot bitmap
    (each step marks its slot before the forward) and the token-buffer
    append, so a later return to spec mode has current state. Greedy
    only — spec pools are greedy-gated at creation."""
    b = token.shape[0]

    def body(carry, _):
        token, pos, blen, cache, valid, buf = carry
        valid = jax.lax.dynamic_update_slice(
            valid, jnp.ones((b, 1), bool), (0, pos)
        )
        logits, cache = forward(
            params, cfg, token[:, None], cache, start_pos=pos,
            row_start=row_start, kv_width=kv_width, kv_mask=valid,
        )
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        bidx = jnp.minimum(blen, buf.shape[1] - 1)[:, None]
        buf = buf.at[jnp.arange(b)[:, None], bidx].set(nxt[:, None])
        return (nxt, pos + 1, blen + 1, cache, valid, buf), nxt

    with w8a8_scope(w8a8):
        (token, _, blen, cache, valid, buf), toks = jax.lax.scan(
            body,
            (token, jnp.asarray(pos, jnp.int32), blen, cache, valid, buf),
            None, length=n_steps,
        )
    return token, toks, blen, cache, valid, buf


@partial(jax.jit, static_argnames=("k",), donate_argnames=("valid", "buf"))
def _install_spec_rows(valid, buf, blen, slots, dsts, pos, prompts, nlens,
                       samples, k: int):
    """Install ``k`` admitted rows' speculative state in ONE program:
    bitmap row = the spliced prompt window [dst, pos), buffer row =
    prompt ids + the prefill-sampled first token, blen = n + 1 (the
    sampled token is the stream's current token — its KV is written by
    the row's first round, at the then-current frontier). Padding rows
    repeat row 0 (idempotent scatter), mirroring ``_admit_finish``."""
    s = valid.shape[1]
    ar = jnp.arange(s, dtype=jnp.int32)[None, :]
    valid = valid.at[slots].set(
        jnp.logical_and(ar >= dsts[:, None], ar < pos)
    )
    w = prompts.shape[1]
    rows = jnp.zeros((k, s), jnp.int32)
    rows = rows.at[:, :w].set(prompts) if w <= s else rows
    rows = rows.at[jnp.arange(k), jnp.minimum(nlens, s - 1)].set(samples)
    buf = buf.at[slots].set(rows)
    blen = blen.at[slots].set(nlens + 1)
    return valid, buf, blen


@partial(jax.jit, donate_argnames=("valid",))
def _roll_valid(valid, shift):
    """Compaction twin of the batcher's cache roll: slide every row's
    bitmap left with the KV it describes."""
    return jnp.roll(valid, -shift, axis=1)


# -- engine ------------------------------------------------------------------


class SpeculativeEngine:
    """Drives a target Engine with speculative decode from any Drafter.

    ``generate`` matches ``Engine.generate``'s contract and is token-exact
    against ``target.generate`` for greedy sampling; non-greedy sampling
    params delegate to the plain target engine (pure-temperature sampling
    rides a MODEL drafter via rejection sampling; buffer drafters and
    truncated distributions go plain), as does any generation whose
    prompt + requested tokens would outgrow a model draft's (possibly
    smaller) context window — the target's limits alone decide output
    length. Two edge deviations: near cache capacity the loop stops a
    round's worth of slots early rather than switching to 1-token tail
    steps, and when ``max_new_tokens`` lands exactly on a round boundary
    the loop may report "length" where the plain engine's chunk
    overshoot would have peeked at an EOS just past the cap (both
    engines only report "eos" for past-the-cap EOS when their dispatch
    granularity happens to produce that token; token_ids are unaffected
    either way).

    Control plane: per-stream :class:`AdaptiveK` (acceptance EMA →
    draft-length ladder) and :class:`SpecGovernor` (drafted-vs-plain
    online A/B; the losing mode is abandoned, so a bad drafter costs one
    probe window and then the stream runs at plain speed). The finished
    target cache is retained through ``Engine._retain_prefix`` — under
    ``LLMC_KV_POOL`` that is a pool PUBLISH, so spec streams share KV
    with every other stream instead of owning a private cache, and their
    prefill rides pool hits the same way.
    """

    def __init__(self, target: Engine, draft, k: int = 4,
                 rounds_per_chunk: Optional[int] = None,
                 adaptive: Optional[bool] = None,
                 governor: Optional[bool] = None,
                 probe_tokens: Optional[int] = None):
        if k < 1:
            raise ValueError("k must be >= 1")
        from llm_consensus_tpu.engine.engine import refuse_ssm

        # A rejected draft cannot be taken back out of a recurrent state.
        refuse_ssm(target.cfg, "speculative decoding")
        if isinstance(draft, Engine):
            refuse_ssm(draft.cfg, "speculative drafting")
            draft = ModelDrafter(draft)
        if not isinstance(draft, Drafter):
            raise TypeError("draft must be an Engine or a Drafter")
        if isinstance(draft, ModelDrafter):
            def single_device(mesh):
                return None if mesh is None else tuple(mesh.devices.flat)

            t_dev = single_device(target.mesh)
            d_dev = single_device(draft.engine.mesh)
            ok = (t_dev is None and d_dev is None) or (
                t_dev is not None and len(t_dev) == 1 and (
                    d_dev is None or d_dev == t_dev
                )
            )
            if not ok:
                # Multi-device meshes would need the two caches
                # co-located across the slice; unsharded or
                # same-single-device (what the panel planner pins on one
                # chip) are the supported shapes. Buffer drafters carry
                # no second cache, so they skip this check entirely —
                # a tp-sharded judge can ride prompt lookup (the verify
                # forward is plain XLA that GSPMD partitions).
                raise ValueError(
                    "speculative decoding supports unsharded engines or "
                    "a target/draft pair on the same single-device mesh"
                )
        self.target = target
        self.drafter = draft
        self.draft = draft.engine if isinstance(draft, ModelDrafter) else None
        self.k = k
        # Rounds per dispatch: enough that the fetch round trip amortizes
        # (a round advances >= 1 token, so rounds ~ stream_interval keeps
        # chunk latency comparable to the plain decode chunk).
        self.rounds = rounds_per_chunk or max(1, target.stream_interval // 2)
        self.tokenizer = target.tokenizer
        # Control-plane knobs: explicit constructor overrides (bench's
        # pinned-k ceiling/sweep points, tests) beat the env defaults,
        # which come from the same spec_config_from_env the batched tier
        # reads — one set of knobs, one parser.
        env_cfg = spec_config_from_env(kind=draft.kind)
        self.adaptive = adaptive if adaptive is not None else env_cfg.adaptive
        self.governor_enabled = (
            governor if governor is not None else env_cfg.governor
        )
        self.probe_tokens = (
            probe_tokens if probe_tokens is not None
            else env_cfg.probe_tokens
        )
        self.stats = {
            "rounds": 0, "accepted": 0, "plain_tokens": 0,
            "governor_disables": 0, "collapse_faults": 0,
        }
        self.last_accept_ema = 0.0
        from llm_consensus_tpu import faults as _faults
        from llm_consensus_tpu import obs as _obs

        self._faults = _faults.plan()
        self._obs = _obs.recorder()
        # Chip-time attribution (obs/attrib): rejected verify positions
        # feed the goodput ledger; draft/verify dispatches are tagged so
        # the retrace sentinel attributes their compiles.
        self._attrib = _obs.attrib.ledger()

    @property
    def mean_accepted(self) -> float:
        """Mean tokens per round so far (1.0 = no speculation win)."""
        r = self.stats["rounds"]
        return self.stats["accepted"] / r if r else 0.0

    def _fire_spec_fault(self, sampled: bool = False) -> Optional[str]:
        """Consult the ``spec`` fault site once per round dispatch.
        ``acceptance_collapse`` makes this round's proposals junk (speed
        only — greedy output is exact for ANY proposals);
        ``draft_stall`` sleeps the host dispatcher (@s= seconds).
        ``sampled`` marks the rejection-sampling path, where collapse is
        structurally a no-op (proposals must keep their true q(·) or the
        output distribution would bend) — the firing still lands in the
        fault trace, but the collapse counter only counts rounds the
        fault actually junked."""
        if self._faults is None:
            return None
        fs = self._faults.fire("spec", model=self.target.cfg.name)
        if fs is None:
            return None
        if fs.kind == "draft_stall":
            time.sleep(float(fs.param("s", 0.05)))
            return "draft_stall"
        if fs.kind == "acceptance_collapse" and not sampled:
            self.stats["collapse_faults"] += 1
            return "acceptance_collapse"
        return None

    def generate(
        self,
        prompt: str,
        sampling: SamplingParams = SamplingParams(),
        ctx: Optional[Context] = None,
        on_text: Optional[Callable[[str], None]] = None,
    ) -> GenerateResult:
        if sampling.temperature != 0.0 and (
            self.draft is None
            or sampling.top_k is not None or sampling.top_p is not None
        ):
            # Rejection sampling composes cleanly with pure temperature
            # scaling AND a model drafter (it needs the draft's q(·));
            # truncated distributions (top-k/top-p) would need the same
            # filtering applied consistently to both p and q, and buffer
            # drafters propose point masses the sampled path does not
            # model — fall back to the plain engine rather than
            # approximate.
            return self.target.generate(prompt, sampling, ctx, on_text)
        ctx = ctx or Context.background()
        start_time = time.monotonic()
        tgt = self.target
        prompt_ids, truncated = tgt._budget_prompt(
            self.tokenizer.encode(prompt), sampling.max_new_tokens
        )
        if not prompt_ids:
            raise ValueError("empty prompt")
        n = len(prompt_ids)
        max_new = min(sampling.max_new_tokens, tgt.max_seq - n)
        if self.draft is not None and (
            n + max_new + self.k + 2 > self.draft.max_seq
        ):
            # The draft's (smaller) window would bind before the requested
            # tokens are done. The token-exact contract means the TARGET's
            # limits alone decide output length, so delegate the whole
            # generation to the plain target engine rather than silently
            # returning fewer tokens (a mid-stream draft→plain switch at
            # the draft-window tail is future work).
            return self.target.generate(prompt, sampling, ctx, on_text)
        if max_new <= 0:
            return GenerateResult(
                token_ids=[], text="", finish_reason="length",
                prompt_tokens=n,
                latency_ms=(time.monotonic() - start_time) * 1000,
                truncated_prompt=truncated,
            )
        if sampling.temperature != 0.0:
            return self._generate_sampled(
                prompt_ids, n, max_new, truncated, sampling, ctx, on_text,
                start_time,
            )
        return self._generate_greedy(
            prompt_ids, n, max_new, truncated, sampling, ctx, on_text,
            start_time,
        )

    # -- greedy (any drafter; adaptive k + governor) -------------------------

    def _generate_greedy(self, prompt_ids, n, max_new, truncated, sampling,
                         ctx, on_text, start_time):
        tgt, drf = self.target, self.draft
        drafter = self.drafter
        stats0 = dict(self.stats)  # per-call telemetry = cumulative delta
        decoder = StreamDecoder(self.tokenizer)
        parts: list[str] = []
        out_ids: list[int] = []
        finish = "length"
        eos = -1 if sampling.ignore_eos else self.tokenizer.eos_id

        def emit(tok: int) -> bool:
            nonlocal finish
            if tok == eos:
                finish = "eos"
                return True
            if len(out_ids) >= max_new:
                return True
            out_ids.append(tok)
            text = decoder.push(tok)
            if text:
                parts.append(text)
                if on_text is not None:
                    on_text(text)
            return False

        # Prefill the target (and a model draft); the prefill-sampled
        # target token is the first output and the spec loop's first
        # ``cur``. It stays on device and rides down with the first
        # drain — no dedicated sync (the plain engine makes the same
        # trade).
        tlogits, tcache = tgt._prefill_ids(prompt_ids)
        cur = jnp.argmax(tlogits, axis=-1).astype(jnp.int32)  # [1]
        dcache = None
        prev = None
        if drf is not None:
            _, dcache = drf._prefill_ids(prompt_ids)
            prev = tgt._place(jnp.asarray([prompt_ids[-1]], jnp.int32))
        buf = None
        blen = None
        if drafter.needs_buffer:
            sbuf = tgt.max_seq
            host_buf = prompt_ids[:sbuf]
            if isinstance(drafter, OracleDrafter):
                # The oracle buffer holds the FUTURE too: token p of the
                # stream at obuf[p].
                host_buf = (prompt_ids + drafter.continuation_ids)[:sbuf]
            host_buf = host_buf + [0] * (sbuf - len(host_buf))
            buf = tgt._place(jnp.asarray(host_buf, jnp.int32)[None, :])
            if not isinstance(drafter, OracleDrafter):
                buf = buf.at[0, min(n, sbuf - 1)].set(cur[0])
            blen = tgt._place(jnp.asarray(n + 1, jnp.int32))

        pos_dev = tgt._place(jnp.asarray(n, jnp.int32))
        first_dev: Optional[jax.Array] = cur
        stopped = False
        cap = min(tgt.max_seq, drf.max_seq if drf is not None else tgt.max_seq)
        vocab = tgt.cfg.vocab_size
        key0 = tgt._place(jax.random.PRNGKey(0))  # greedy: content unused
        chunk_sz = tgt.stream_interval

        controller = AdaptiveK(self.k, adaptive=self.adaptive)
        governor = SpecGovernor(
            probe_tokens=self.probe_tokens, enabled=self.governor_enabled,
        )
        decode_t0: Optional[float] = None
        decode_n0 = 0
        # Host frontier UPPER BOUND (acceptance is data): gates the
        # cache-tail stop conservatively, tightened at each drain.
        pos_ub = n
        # Window accounting for the governor (tokens + wall per mode,
        # measured at drain boundaries).
        win_t0 = time.monotonic()
        win_tokens0 = 0
        plain_backlog: list = []  # (toks, n_steps, start_pos) for ingest
        pending: list[tuple] = []

        def drain() -> None:
            nonlocal stopped, decode_t0, decode_n0, pos_ub, first_dev
            if not pending and first_dev is None:
                return
            spec_entries = [p for p in pending if p[0] == "spec"]
            last_pos = spec_entries[-1][3] if spec_entries else None
            first_h, fetched, last_pos_h = jax.device_get((
                first_dev,
                [p[1:3] if p[0] == "spec" else (p[1], None) for p in pending],
                last_pos,
            ))
            if first_dev is not None:
                first_dev = None
                stopped = emit(int(first_h[0]))
            plain_seen = 0
            for (kind, *rest), (v1, v2) in zip(pending, fetched):
                if stopped:
                    break
                if kind == "spec":
                    a = int(v2)
                    self.stats["rounds"] += 1
                    self.stats["accepted"] += a
                    if self._attrib is not None:
                        self._attrib.token_event(
                            "spec_rejected", rest[3] + 1 - a
                        )
                    controller.observe(a, rest[3])
                    for i in range(a):
                        if emit(int(v1[i])):
                            stopped = True
                            break
                else:  # plain chunk
                    plain_seen += 1
                    for t in v1[:, 0]:
                        if emit(int(t)):
                            stopped = True
                            break
                    if not stopped:
                        self.stats["plain_tokens"] += v1.shape[0]
            if last_pos_h is not None:
                pos_ub = int(last_pos_h)
            elif pending and pending[-1][0] == "plain":
                pos_ub = pending[-1][2]
            pending.clear()
            if decode_t0 is None:
                decode_t0 = time.monotonic()
                decode_n0 = len(out_ids)

        def governor_feed() -> None:
            """Feed the drained window to the governor; on a mode switch,
            reset the window clock (carries are device-resident and
            always current, so switching is free)."""
            nonlocal win_t0, win_tokens0, dcache, plain_backlog
            now = time.monotonic()
            switched = governor.feed(
                len(out_ids) - win_tokens0, now - win_t0
            )
            win_t0, win_tokens0 = now, len(out_ids)
            if governor.disabled_spec and self.stats["governor_disables"] == 0:
                self.stats["governor_disables"] = 1
                if self._obs is not None:
                    self._obs.instant(
                        "spec_governor_disable", tid="engine",
                        model=tgt.cfg.name,
                        ema=round(controller.ema, 3),
                    )
            if switched and governor.mode == "spec" and plain_backlog:
                # Returning to spec after a plain window: catch the model
                # draft's cache up over the tokens it never saw (buffer
                # drafters stayed current via _append_buf).
                if drf is not None and dcache is not None:
                    for toks, nst, sp in plain_backlog:
                        width = drf._decode_width(min(sp + nst, cap))
                        dcache = _draft_ingest(
                            drf.params, drf.cfg,
                            jnp.transpose(toks, (1, 0)), sp, dcache,
                            n=nst, kv_width=width,
                        )
                plain_backlog = []

        while True:
            k = controller.k
            can_dispatch = (
                not stopped
                and not ctx.done()
                and pos_ub + (k + 1) + 1 <= cap
                and len(out_ids) + sum(
                    1 if p[0] == "spec" else p[3] for p in pending
                ) + (1 if first_dev is not None else 0) < max_new
            )
            if not can_dispatch:
                drain()
                governor_feed()
                if stopped or len(out_ids) >= max_new:
                    break
                if ctx.done():
                    finish = (
                        "deadline" if ctx.remaining() == 0.0 else "cancelled"
                    )
                    break
                if pos_ub + (k + 1) + 1 > cap:
                    break  # cache tail: documented early stop
                continue  # drain tightened pos_ub; re-evaluate
            if governor.mode == "plain":
                n_steps = chunk_sz if pos_ub + chunk_sz + 1 <= cap else 1
                width = tgt._decode_width(min(pos_ub + n_steps + 1, cap))
                # The engine's own attention impl + mesh, so the plain
                # probe measures (and the locked plain mode runs) the
                # program the plain engine would — the A/B must compare
                # against true plain speed, not a degraded twin.
                cur_prev = cur  # the token at pos_dev (KV written by the
                # chunk's first step — the ingest window starts with it)
                cur, toks, tcache = tgt._flash_guard(
                    lambda impl: _decode_chunk(
                        tgt.params, tgt.cfg, cur, pos_dev, tcache, key0,
                        n_steps, 0.0, None, None, kv_width=width,
                        attn_impl=impl, mesh=tgt.mesh, w8a8=tgt.w8a8,
                    )
                )
                if buf is not None and not isinstance(drafter, OracleDrafter):
                    buf, blen = _append_buf(buf, blen, toks, n=n_steps)
                if drf is not None and governor.state == "plain_probe":
                    # Position alignment: toks[j] sits at pos_dev+1+j and
                    # its KV is unwritten for the LAST one — the window
                    # whose KV the target wrote at [pos_dev, pos_dev+n)
                    # is [cur_prev, toks[:-1]], which is exactly what a
                    # later _draft_ingest must replay at pos_dev.
                    win = jnp.concatenate([cur_prev[:, None], toks[:-1]])
                    plain_backlog.append((win, n_steps, pos_dev))
                if prev is not None:
                    # The draft opener re-ingests the token at pos-1: after
                    # this window the next round's pos is pos_dev+n, so
                    # that token is toks[-2] (or cur_prev for a 1-step
                    # tail chunk) — NOT toks[-1], which is the new cur.
                    prev = toks[-2] if n_steps >= 2 else cur_prev
                pos_dev = pos_dev + n_steps
                pos_ub += n_steps
                pending.append(("plain", toks, pos_ub, n_steps))
                if len(pending) >= max(1, self.rounds // 2):
                    drain()
                    governor_feed()
                continue
            # -- spec round --
            fault = self._fire_spec_fault()
            width = tgt._decode_width(min(pos_ub + k + 2, cap))
            if drf is not None:
                with _attrib_tag("draft"):
                    if fault == "acceptance_collapse":
                        # Junk proposals via the draft too: cheapest is
                        # to draft normally then perturb — but the draft
                        # scan is the cost we want to keep, so perturb
                        # its output.
                        drafts, dcache = _spec_draft(
                            drf.params, drf.cfg, prev, cur, pos_dev,
                            dcache, k, kv_width=width,
                        )
                        drafts = (drafts + 1) % vocab
                    else:
                        drafts, dcache = _spec_draft(
                            drf.params, drf.cfg, prev, cur, pos_dev,
                            dcache, k, kv_width=width,
                        )
                with _attrib_tag("spec_verify"):
                    out, a, prev, cur, pos_dev, tcache = _spec_verify(
                        tgt.params, tgt.cfg, cur, drafts, pos_dev, tcache,
                        kv_width=width,
                    )
                pending.append(("spec", out, a, pos_dev, k))
            else:
                with _attrib_tag("draft"):
                    if fault == "acceptance_collapse":
                        drafts = _junk_propose(buf, blen[None], k, vocab)[0]
                    elif isinstance(drafter, OracleDrafter):
                        drafts = _oracle_propose(
                            buf, blen[None], k, vocab,
                            accept=drafter.accept,
                        )[0]
                    else:
                        drafts = _lookup_propose(
                            buf, blen[None], k, drafter.ngram
                        )[0]
                with _attrib_tag("spec_verify"):
                    if isinstance(drafter, OracleDrafter):
                        # The oracle buffer already holds the future;
                        # verify must not overwrite it (out == obuf
                        # content anyway, but forced-accept junk rounds
                        # would corrupt it).
                        out, a, cur, pos_dev, blen2, tcache, _scratch = \
                            _spec_verify_buf(
                                tgt.params, tgt.cfg, cur, drafts, pos_dev,
                                blen, tcache, jnp.zeros_like(buf),
                                kv_width=width, w8a8=tgt.w8a8,
                            )
                        blen = blen2
                    else:
                        out, a, cur, pos_dev, blen, tcache, buf = \
                            _spec_verify_buf(
                                tgt.params, tgt.cfg, cur, drafts, pos_dev,
                                blen, tcache, buf, kv_width=width,
                                w8a8=tgt.w8a8,
                            )
                pending.append(("spec", out, a, pos_dev, k))
            pos_ub += k + 1
            if len(pending) >= self.rounds:
                drain()
                governor_feed()

        self.last_accept_ema = controller.ema
        d_rounds = self.stats["rounds"] - stats0["rounds"]
        d_accepted = self.stats["accepted"] - stats0["accepted"]
        if self._obs is not None:
            self._obs.count("spec.rounds", d_rounds)
            self._obs.count("spec.accepted", d_accepted)
        spec_info = {
            "kind": drafter.kind,
            "k": self.k,
            "rounds": d_rounds,
            "accepted": d_accepted,
            "mean_accepted": (
                round(d_accepted / d_rounds, 3) if d_rounds else None
            ),
            "accept_ema": round(controller.ema, 3),
            "governor": governor.state,
            "plain_tokens": (
                self.stats["plain_tokens"] - stats0["plain_tokens"]
            ),
        }
        # Retain the finished cache for prefix reuse (under LLMC_KV_POOL
        # this is a pool publish — spec streams share KV like any other
        # stream): every position < the accepted frontier holds exact
        # greedy KV (each was written by its round's verify), and the
        # ids cap excludes the junk beyond.
        kv_truncated = False
        if not stopped or finish in ("eos", "length"):
            kv_truncated = tgt._retain_prefix(prompt_ids + out_ids, tcache)

        decode_tokens = 0
        decode_s = 0.0
        if decode_t0 is not None:
            decode_tokens = len(out_ids) - decode_n0
            decode_s = time.monotonic() - decode_t0
        tail = decoder.flush()
        if tail:
            parts.append(tail)
            if on_text is not None:
                on_text(tail)
        return GenerateResult(
            token_ids=out_ids,
            text="".join(parts),
            finish_reason=finish,
            prompt_tokens=n,
            latency_ms=(time.monotonic() - start_time) * 1000,
            truncated_prompt=truncated,
            decode_tokens=decode_tokens,
            decode_s=decode_s,
            spec=spec_info,
            kv_truncated=bool(kv_truncated),
        )

    # -- sampled (model drafter; rejection sampling) -------------------------

    def _generate_sampled(self, prompt_ids, n, max_new, truncated, sampling,
                          ctx, on_text, start_time):
        tgt, drf = self.target, self.draft
        stats0 = dict(self.stats)  # per-call telemetry = cumulative delta
        base_key = jax.random.PRNGKey(sampling.seed)
        decoder = StreamDecoder(self.tokenizer)
        parts: list[str] = []
        out_ids: list[int] = []
        finish = "length"
        eos = -1 if sampling.ignore_eos else self.tokenizer.eos_id

        def emit(tok: int) -> bool:
            nonlocal finish
            if tok == eos:
                finish = "eos"
                return True
            if len(out_ids) >= max_new:
                return True
            out_ids.append(tok)
            text = decoder.push(tok)
            if text:
                parts.append(text)
                if on_text is not None:
                    on_text(text)
            return False

        from llm_consensus_tpu.ops.sampling import sample_token

        tlogits, tcache = tgt._prefill_ids(prompt_ids)
        _, dcache = drf._prefill_ids(prompt_ids)
        cur = sample_token(
            tlogits, jax.random.fold_in(base_key, n - 1),
            temperature=sampling.temperature,
        )
        prev = jnp.asarray([prompt_ids[-1]], jnp.int32)
        first_dev: Optional[jax.Array] = cur
        stopped = False
        controller = AdaptiveK(self.k, adaptive=self.adaptive)
        cap = min(tgt.max_seq, drf.max_seq)
        decode_t0: Optional[float] = None
        decode_n0 = 0
        # The host chains per-round (draft → verify) dispatches with the
        # carry — prev/cur/pos and both caches — entirely device-resident,
        # fetching accumulated (out, a, pos) triples only every
        # ``self.rounds`` rounds. Dispatches pipeline ahead of execution,
        # so the fetch round trip amortizes over a whole batch of rounds.
        pos_ub = n
        pos_dev = n
        round_no = 0  # monotone round counter: the sampled path's key
        # schedule MUST be collision-free across rounds (deriving keys
        # from len(out_ids)+pos_ub repeats values across fetch batches,
        # which would reuse randomness and bend the output distribution).
        pending: list[tuple] = []  # (out [k+1], a, pos_dev, k) per round

        def drain() -> None:
            nonlocal stopped, decode_t0, decode_n0, pos_ub, first_dev
            if not pending and first_dev is None:
                return
            first_h, fetched, last_pos = jax.device_get((
                first_dev,
                [p[:2] for p in pending],
                pending[-1][2] if pending else pos_dev,
            ))
            if first_dev is not None:
                first_dev = None
                stopped = emit(int(first_h[0]))
            for (out, a), (_o, _a, _p, k_used) in zip(fetched, pending):
                if stopped:
                    break
                a = int(a)
                self.stats["rounds"] += 1
                self.stats["accepted"] += a
                controller.observe(a, k_used)
                for i in range(a):
                    if emit(int(out[i])):
                        stopped = True
                        break
            pending.clear()
            pos_ub = int(last_pos) if not isinstance(last_pos, int) else last_pos
            if decode_t0 is None:
                decode_t0 = time.monotonic()
                decode_n0 = len(out_ids)

        while True:
            k = controller.k
            can_dispatch = (
                not stopped
                and not ctx.done()
                and pos_ub + (k + 1) + 1 <= cap
                and len(out_ids) + len(pending)
                + (1 if first_dev is not None else 0) < max_new
            )
            if not can_dispatch:
                drain()
                if stopped or len(out_ids) >= max_new:
                    break
                if ctx.done():
                    finish = (
                        "deadline" if ctx.remaining() == 0.0 else "cancelled"
                    )
                    break
                if pos_ub + (k + 1) + 1 > cap:
                    break  # cache tail: documented early stop
                continue
            self._fire_spec_fault(sampled=True)  # only draft_stall
            # applies here; see the method's ``sampled`` contract.
            width = tgt._decode_width(min(pos_ub + k + 2, cap))
            round_no += 1
            rkey = jax.random.fold_in(base_key, round_no)
            drafts, qs, dcache = _spec_draft_sampled(
                drf.params, drf.cfg, prev, cur, pos_dev, dcache,
                jax.random.fold_in(rkey, 7), k,
                temperature=sampling.temperature, kv_width=width,
            )
            out, a, prev, cur, pos_dev, tcache = _spec_verify_sampled(
                tgt.params, tgt.cfg, cur, drafts, qs, pos_dev, tcache,
                jax.random.fold_in(rkey, 13),
                temperature=sampling.temperature, kv_width=width,
            )
            pending.append((out, a, pos_dev, k))
            pos_ub += k + 1
            if len(pending) >= self.rounds:
                drain()

        self.last_accept_ema = controller.ema
        d_rounds = self.stats["rounds"] - stats0["rounds"]
        d_accepted = self.stats["accepted"] - stats0["accepted"]
        if self._obs is not None:
            self._obs.count("spec.rounds", d_rounds)
            self._obs.count("spec.accepted", d_accepted)
        decode_tokens = 0
        decode_s = 0.0
        if decode_t0 is not None:
            decode_tokens = len(out_ids) - decode_n0
            decode_s = time.monotonic() - decode_t0
        tail = decoder.flush()
        if tail:
            parts.append(tail)
            if on_text is not None:
                on_text(tail)
        return GenerateResult(
            token_ids=out_ids,
            text="".join(parts),
            finish_reason=finish,
            prompt_tokens=n,
            latency_ms=(time.monotonic() - start_time) * 1000,
            truncated_prompt=truncated,
            decode_tokens=decode_tokens,
            decode_s=decode_s,
            spec={
                "kind": "model",
                "k": self.k,
                "rounds": d_rounds,
                "accepted": d_accepted,
                "mean_accepted": (
                    round(d_accepted / d_rounds, 3) if d_rounds else None
                ),
                "accept_ema": round(controller.ema, 3),
                "governor": "sampled",  # rejection path has no A/B
                "plain_tokens": 0,
            },
        )
