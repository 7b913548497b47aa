"""Speculative decoding (engine/speculative.py + the batched pool mode).

TPU-build extension — no reference analog (SURVEY.md §2: remote HTTP
compute). The load-bearing property: greedy speculative output is
TOKEN-EXACT against the plain target engine for ANY draft — the draft
changes only speed. Acceptance-rate machinery is validated at both
extremes: a self-draft (target drafts for itself → every draft accepted)
and an unrelated random draft (≈ nothing accepted). The BATCHED form
(ContinuousBatcher spec mode: shared frontier + per-row holes behind
the written-slot bitmap) is validated against the single-stream engine
across batch sizes, mid-round exit/admission, and compaction.
"""

import time

import jax
import jax.numpy as jnp
import pytest

from llm_consensus_tpu.engine import (
    ContinuousBatcher, Engine, OracleDrafter, PromptLookupDrafter,
    SamplingParams, SpecConfig, SpeculativeEngine)
from llm_consensus_tpu.models import get_config, init_params
from llm_consensus_tpu.utils import Context


def _engine(preset, seed, **kw):
    cfg = get_config(preset)
    params = init_params(cfg, jax.random.PRNGKey(seed), dtype=jnp.float32)
    kw.setdefault("max_seq", 512)
    kw.setdefault("stream_interval", 8)
    return Engine(cfg, params=params, dtype=jnp.float32, **kw)


@pytest.fixture(scope="module")
def target():
    return _engine("tiny-llama", 0)


@pytest.fixture(scope="module")
def unrelated_draft():
    return _engine("tiny-llama", 7)  # same family, different weights


def test_exact_vs_plain_with_unrelated_draft(target, unrelated_draft):
    """Near-zero acceptance: output still byte-identical to the target."""
    spec = SpeculativeEngine(target, unrelated_draft, k=3)
    s = SamplingParams(max_new_tokens=48, ignore_eos=True)
    prompt = "speculative decoding exactness probe"
    got = spec.generate(prompt, s)
    ref = target.generate(prompt, s)
    assert got.token_ids == ref.token_ids
    assert got.text == ref.text
    assert got.finish_reason == ref.finish_reason
    # Random unrelated draft: acceptance stays near the 1-token floor.
    assert 1.0 <= spec.mean_accepted < 2.0


def test_self_draft_accepts_everything(target):
    """Target drafting for itself: every draft token matches, so each
    round advances k+1 tokens and output stays exact."""
    spec = SpeculativeEngine(target, target, k=3)
    s = SamplingParams(max_new_tokens=40, ignore_eos=True)
    prompt = "self speculation accepts all drafts"
    got = spec.generate(prompt, s)
    ref = target.generate(prompt, s)
    assert got.token_ids == ref.token_ids
    assert spec.mean_accepted == pytest.approx(4.0)  # k+1


def test_self_draft_shares_engine_safely(target):
    """Using one Engine object as both target and draft must not corrupt
    state across generates (separate caches per call)."""
    spec = SpeculativeEngine(target, target, k=2)
    s = SamplingParams(max_new_tokens=16, ignore_eos=True)
    a = spec.generate("first call", s).token_ids
    b = spec.generate("first call", s).token_ids
    assert a == b


def test_eos_respected(target, unrelated_draft):
    spec = SpeculativeEngine(target, unrelated_draft, k=3)
    s = SamplingParams(max_new_tokens=64)  # honors EOS
    got = spec.generate("eos handling probe", s)
    ref = target.generate("eos handling probe", s)
    assert got.finish_reason == ref.finish_reason
    assert got.token_ids == ref.token_ids


def test_streaming_callbacks(target, unrelated_draft):
    spec = SpeculativeEngine(target, unrelated_draft, k=2)
    s = SamplingParams(max_new_tokens=20, ignore_eos=True)
    chunks: list[str] = []
    got = spec.generate("stream me", s, on_text=chunks.append)
    assert "".join(chunks) == got.text


def test_topk_topp_delegate_to_plain_engine(target, unrelated_draft):
    """Truncated-distribution sampling stays on the plain engine (the
    documented rejection-sampling scope is pure temperature)."""
    spec = SpeculativeEngine(target, unrelated_draft, k=2)
    s = SamplingParams(max_new_tokens=12, temperature=0.8, top_k=20, seed=3,
                       ignore_eos=True)
    got = spec.generate("sampled fallback", s)
    ref = target.generate("sampled fallback", s)
    assert got.token_ids == ref.token_ids  # same engine, same seed path


def test_sampled_rejection_speculation_runs(target, unrelated_draft):
    """Pure-temperature sampling rides the draft via rejection sampling:
    requested token count, valid vocabulary ids, sane stats."""
    spec = SpeculativeEngine(target, unrelated_draft, k=3)
    s = SamplingParams(max_new_tokens=24, temperature=0.8, seed=5,
                       ignore_eos=True)
    got = spec.generate("rejection sampling probe", s)
    assert len(got.token_ids) == 24
    assert all(0 <= t < target.cfg.vocab_size for t in got.token_ids)
    assert got.finish_reason == "length"
    assert spec.stats["rounds"] > 0
    assert spec.mean_accepted >= 1.0


def test_sampled_self_draft_mean_acceptance_above_one(target):
    """Correlated draft (the target drafting for itself: p == q, so the
    acceptance probability is exactly 1): mean accepted run length must
    approach k+1 — the >1 acceptance pin for the sampled path (round-2
    VERDICT #4)."""
    spec = SpeculativeEngine(target, target, k=3)
    s = SamplingParams(max_new_tokens=32, temperature=0.7, seed=11,
                       ignore_eos=True)
    got = spec.generate("self drafted sampled speculation", s)
    assert len(got.token_ids) == 32
    assert spec.mean_accepted > 3.0, spec.mean_accepted  # k+1 = 4 ideal


def test_cancellation(target, unrelated_draft):
    spec = SpeculativeEngine(target, unrelated_draft, k=2)
    ctx = Context.background().with_timeout(0.0)
    got = spec.generate(
        "deadline immediately",
        SamplingParams(max_new_tokens=400, ignore_eos=True), ctx=ctx,
    )
    assert got.finish_reason == "deadline"
    assert len(got.token_ids) < 400


def test_partial_acceptance_regime_stays_exact(target):
    """A quantized copy of the target's own weights drafts for it:
    mostly-agreeing but imperfect proposals land acceptance strictly
    between the floor (1) and the ceiling (k+1), exercising the
    mid-round correction path (out[leading-1] re-ingestion) — and the
    output must STILL be token-exact."""
    cfg = get_config("tiny-llama")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    draft = Engine(cfg, params=params, dtype=jnp.float32, max_seq=512,
                   stream_interval=8, quant="int8")
    spec = SpeculativeEngine(target, draft, k=4)
    s = SamplingParams(max_new_tokens=64, ignore_eos=True)
    prompt = "partial acceptance statistics probe"
    got = spec.generate(prompt, s)
    ref = target.generate(prompt, s)
    assert got.token_ids == ref.token_ids
    assert 1.0 < spec.mean_accepted < 5.0  # neither floor nor ceiling


def test_draft_window_too_small_delegates(target):
    small_draft = _engine("tiny-llama", 3, max_seq=16)
    spec = SpeculativeEngine(target, small_draft, k=4)
    s = SamplingParams(max_new_tokens=12, ignore_eos=True)
    prompt = "a prompt comfortably longer than the draft's tiny window"
    got = spec.generate(prompt, s)
    ref = target.generate(prompt, s)
    assert got.token_ids == ref.token_ids
    assert len(got.token_ids) == 12


def test_multi_device_engines_rejected(target):
    import numpy as np
    from jax.sharding import Mesh

    sharded = _engine("tiny-llama", 1)
    sharded.mesh = Mesh(np.array(jax.devices()[:2]).reshape(2), ("tp",))
    with pytest.raises(ValueError, match="unsharded"):
        SpeculativeEngine(target, sharded)


def test_same_single_device_mesh_accepted():
    """The panel planner pins one-chip models to single-device meshes —
    speculation must attach there (pure placement, no sharding)."""
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("tp",))
    tgt = _engine("tiny-llama", 0, mesh=mesh)
    drf = _engine("tiny-llama", 7, mesh=mesh)
    spec = SpeculativeEngine(tgt, drf, k=2)
    s = SamplingParams(max_new_tokens=10, ignore_eos=True)
    prompt = "single device mesh speculation"
    assert spec.generate(prompt, s).token_ids == tgt.generate(prompt, s).token_ids


def test_requested_tokens_beyond_draft_window_delegate(target):
    """A draft whose window is smaller than prompt + requested max_new
    must not silently cap the output (the round-1 bug returned 31 of a
    requested 120 tokens): the target's limits alone decide length."""
    small_draft = _engine("tiny-llama", 3, max_seq=64)
    spec = SpeculativeEngine(target, small_draft, k=4)
    s = SamplingParams(max_new_tokens=120, ignore_eos=True)
    prompt = "short prompt"  # fits the draft; prompt + 120 does not
    got = spec.generate(prompt, s)
    ref = target.generate(prompt, s)
    assert got.token_ids == ref.token_ids
    assert len(got.token_ids) == 120
    assert got.finish_reason == ref.finish_reason == "length"


def test_provider_draft_flag_exactness():
    """LLMC_DRAFT through the provider seam: greedy output with a draft
    attached is identical to the plain provider path, and the spec
    engine is actually engaged."""
    from llm_consensus_tpu.providers.base import Request
    from llm_consensus_tpu.providers.tpu import TPUProvider

    plain = TPUProvider(ignore_eos=True, stream_interval=4)
    drafted = TPUProvider(ignore_eos=True, stream_interval=4,
                          draft="tiny-llama")
    req = Request(model="tpu:tiny-mistral", prompt="drafted consensus check",
                  max_tokens=16)
    want = plain.query(Context.background(), req)
    got = drafted.query(Context.background(), req)
    assert got.content == want.content
    entry = drafted._specs.get("tiny-mistral")
    assert entry is not None and entry[1] is not None
    assert entry[1].stats["rounds"] > 0


def test_provider_draft_self_pair_disabled():
    """target == draft configures nothing (a model can't draft itself
    through the map; the self-draft case is a test-only construction)."""
    from llm_consensus_tpu.providers.tpu import TPUProvider

    provider = TPUProvider(ignore_eos=True, stream_interval=4,
                           draft="tiny-llama")
    assert provider._draft_preset_for("tiny-llama") is None
    assert provider._draft_preset_for("tiny-mistral") == "tiny-llama"


def test_provider_draft_pair_spec_parsing():
    from llm_consensus_tpu.providers.tpu import _parse_draft_spec

    assert _parse_draft_spec("") == {}
    assert _parse_draft_spec("tiny-llama") == {"*": "tiny-llama"}
    assert _parse_draft_spec("a=b, c=d") == {"a": "b", "c": "d"}
    assert _parse_draft_spec("a=b,fallback") == {"a": "b", "*": "fallback"}


def _ids(eng, prompt, max_new):
    return eng._budget_prompt(eng.tokenizer.encode(prompt), max_new)[0]


def _pool_run(eng, prompts, max_new, spec, stagger_s=0.0):
    b = ContinuousBatcher(eng, max_batch=4, spec=spec)
    try:
        futs = []
        for p, m in zip(prompts, max_new):
            futs.append(b.submit(
                p, SamplingParams(max_new_tokens=m, ignore_eos=True)
            ))
            if stagger_s:
                time.sleep(stagger_s)
        results = [f.result(timeout=600) for f in futs]
        snap = b.spec_snapshot()
    finally:
        b.close()
    return results, snap


class TestBatchedSpec:
    """ContinuousBatcher spec mode: batched verification over the shared
    frontier with per-row acceptance as data (holes + bitmap)."""

    def test_token_exact_across_batch_sizes(self, target):
        prompts = [
            "batched speculative exactness probe",
            "a second stream with a rather longer prompt body to vary",
            "third",
            "the fourth resident stream",
        ]
        max_new = [24, 17, 31, 9]  # staggered mid-round exits
        refs = [
            target.generate(
                p, SamplingParams(max_new_tokens=m, ignore_eos=True)
            )
            for p, m in zip(prompts, max_new)
        ]
        for n in (1, 4):
            results, snap = _pool_run(
                target, prompts[:n], max_new[:n],
                SpecConfig(kind="lookup", k=3, governor=False),
            )
            assert [r.token_ids for r in results] == \
                [r.token_ids for r in refs[:n]]
            assert snap["rounds"] > 0

    def test_mid_stream_admission(self, target):
        """A stream admitted while the pool is mid-spec-rounds (splice at
        the advanced frontier, bitmap row installed over the spliced
        window) must still be token-exact."""
        p1, p2 = "the long-running resident stream", "late admission"
        r1 = target.generate(
            p1, SamplingParams(max_new_tokens=48, ignore_eos=True)
        )
        r2 = target.generate(
            p2, SamplingParams(max_new_tokens=16, ignore_eos=True)
        )
        results, _snap = _pool_run(
            target, [p1, p2], [48, 16],
            SpecConfig(kind="lookup", k=3, governor=False),
            stagger_s=0.5,
        )
        assert results[0].token_ids == r1.token_ids
        assert results[1].token_ids == r2.token_ids

    def test_oracle_full_acceptance(self, target):
        """An oracle replaying the target's own greedy output forces
        a=k+1 every round — the machinery's ceiling — and the output is
        still token-exact."""
        prompts = ["oracle pool stream a", "oracle pool stream b longer"]
        max_new = [20, 26]
        refs = {
            p: target.generate(
                p, SamplingParams(max_new_tokens=m, ignore_eos=True)
            )
            for p, m in zip(prompts, max_new)
        }
        by_ids = {
            tuple(_ids(target, p, m)): refs[p].token_ids
            for p, m in zip(prompts, max_new)
        }
        results, snap = _pool_run(
            target, prompts, max_new,
            SpecConfig(
                kind="oracle", k=3, adaptive=False, governor=False,
                oracle=lambda ids: by_ids.get(tuple(ids), []),
            ),
        )
        for r, p in zip(results, prompts):
            assert r.token_ids == refs[p].token_ids
        assert snap["mean_accepted"] > 3.0, snap  # k+1 = 4 ceiling

    @pytest.mark.xfail(strict=True, reason=(
        "Program fault, larger than an off-by-one: a spec round takes "
        "k+1 slots of EVERY row's window for one guaranteed token, so "
        "the rejected slots (holes) of a row near its cache's end eat "
        "its runway and compaction retires it short of max_new — the "
        "117-token prompt + 10 new in 128 slots comes back with 9, the "
        "single-stream reference with 10. The repair is a scheduling "
        "rule (bound rounds PER ROW by what the row could still finish "
        "plain, without sending a pool that holds a capacity-clamped "
        "row plain for good) and needs a cell that speculates to be "
        "measured: ROADMAP Queue 3 #12."
    ))
    def test_compaction_with_holes(self):
        """The waterline path under spec mode: rejected-slot holes mean
        row_start no longer names the window start — compaction's
        retire/reclaim must read slot_base, roll the bitmap with the
        cache, and stay token-exact through the slide."""
        from llm_consensus_tpu import obs

        cfg = get_config("tiny-llama")
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=128,
                     stream_interval=8)
        pa = "waterline filler prompt " * 9  # pushes the idle frontier up
        pb = "the stream that outlives compaction"
        ra = eng.generate(
            pa, SamplingParams(max_new_tokens=10, ignore_eos=True)
        )
        rb = eng.generate(
            pb, SamplingParams(max_new_tokens=24, ignore_eos=True)
        )
        obs.install(obs.Recorder())
        try:
            results, _snap = _pool_run(
                eng, [pa, pb], [10, 24],
                SpecConfig(kind="lookup", k=3, adaptive=False,
                           governor=False),
            )
            assert results[0].token_ids == ra.token_ids
            assert results[1].token_ids == rb.token_ids
            # Deterministic given fixed weights: stream B outlives A and
            # drives the frontier to capacity, so the slide really ran.
            assert "pool.compact" in obs.recorder().span_names()
        finally:
            obs.reset()

    def test_sampled_template_keeps_classic_path(self, target):
        """A spec-enabled pool whose template is sampled must decode
        through the classic chunk program (spec rounds are greedy-only),
        not fail or bend the distribution machinery."""
        b = ContinuousBatcher(
            target, max_batch=2,
            spec=SpecConfig(kind="lookup", k=3, governor=False),
        )
        try:
            fut = b.submit("sampled template probe", SamplingParams(
                max_new_tokens=8, temperature=0.8, seed=3,
                ignore_eos=True,
            ))
            r = fut.result(timeout=600)
            snap = b.spec_snapshot()
        finally:
            b.close()
        assert len(r.token_ids) == 8
        assert snap["rounds"] == 0  # no spec round ever dispatched

    def test_spec_with_kv_pool(self, monkeypatch):
        """Spec streams lease/publish through the paged KV pool like any
        other stream (LLMC_KV_POOL=1): admission prefill rides pool hits
        and greedy bytes stay identical pool-on vs pool-off."""
        monkeypatch.setenv("LLMC_KV_POOL", "1")
        monkeypatch.setenv("LLMC_KV_POOL_BLOCK", "16")
        cfg = get_config("tiny-llama")
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        eng = Engine(cfg, params=params, dtype=jnp.float32, max_seq=512,
                     stream_interval=8)
        assert eng._kv_pool is not None
        prompts = ["kv pool spec stream one", "kv pool spec stream two"]
        refs = [
            eng.generate(
                p, SamplingParams(max_new_tokens=14, ignore_eos=True)
            )
            for p in prompts
        ]
        results, snap = _pool_run(
            eng, prompts, [14, 14],
            SpecConfig(kind="lookup", k=3, governor=False),
        )
        assert [r.token_ids for r in results] == \
            [r.token_ids for r in refs]
        assert snap["rounds"] > 0

    def test_acceptance_collapse_fault_exact(self, target):
        """The spec fault site: permanent acceptance_collapse junks
        every round's proposals — acceptance pins to ~1 and greedy
        output must be UNCHANGED (speed fault, never correctness)."""
        from llm_consensus_tpu import faults

        prompt = "collapse fault exactness probe"
        ref = target.generate(
            prompt, SamplingParams(max_new_tokens=20, ignore_eos=True)
        )
        faults.install(
            faults.FaultPlan("acceptance_collapse@times=-1", seed=3)
        )
        try:
            # Fresh engine AFTER the install: fault plans bind at
            # construction (the zero-cost pattern), so the module-scoped
            # target never sees this plan.
            cfg = get_config("tiny-llama")
            params = init_params(cfg, jax.random.PRNGKey(0),
                                 dtype=jnp.float32)
            eng = Engine(cfg, params=params, dtype=jnp.float32,
                         max_seq=512, stream_interval=8)
            results, snap = _pool_run(
                eng, [prompt], [20],
                SpecConfig(kind="lookup", k=3, adaptive=False,
                           governor=False),
            )
        finally:
            faults.reset()
        assert results[0].token_ids == ref.token_ids
        assert snap["collapse_faults"] > 0
        assert snap["mean_accepted"] < 1.5, snap  # proposals were junk


class TestControlPlane:
    """AdaptiveK ladder + SpecGovernor state machine (host-side units)."""

    def test_adaptive_k_converges_down_on_collapse(self):
        from llm_consensus_tpu.engine.speculative import AdaptiveK

        c = AdaptiveK(8)
        assert c.k == 8  # optimistic start
        for _ in range(40):
            c.observe(1.0, c.k)  # only the correction token, every round
        assert c.k == 1

    def test_adaptive_k_regrows_on_wins(self):
        from llm_consensus_tpu.engine.speculative import AdaptiveK

        c = AdaptiveK(8)
        for _ in range(40):
            c.observe(1.0, c.k)
        assert c.k == 1
        for _ in range(60):
            c.observe(c.k + 1, c.k)  # ceiling acceptance at every rung
        assert c.k == 8

    def test_adaptive_k_ladder_is_pow2_bounded(self):
        from llm_consensus_tpu.engine.speculative import k_ladder

        assert k_ladder(1) == [1]
        assert k_ladder(4) == [1, 2, 4]
        assert k_ladder(6) == [1, 2, 4, 6]
        assert k_ladder(8) == [1, 2, 4, 8]

    def test_adaptive_off_pins_k(self):
        from llm_consensus_tpu.engine.speculative import AdaptiveK

        c = AdaptiveK(4, adaptive=False)
        for _ in range(50):
            c.observe(1.0, c.k)
        assert c.k == 4

    def test_governor_locks_faster_mode(self):
        from llm_consensus_tpu.engine.speculative import SpecGovernor

        g = SpecGovernor(probe_tokens=10)
        assert g.mode == "spec"
        assert g.feed(10, 1.0) is True          # spec probe: 10 tok/s
        assert g.mode == "plain"
        assert g.feed(10, 0.5) is False         # plain probe: 20 tok/s
        assert g.state == "plain_locked"
        assert g.disabled_spec is True
        assert g.mode == "plain"

    def test_governor_keeps_winning_spec(self):
        from llm_consensus_tpu.engine.speculative import SpecGovernor

        g = SpecGovernor(probe_tokens=10)
        g.feed(10, 0.5)                          # spec: 20 tok/s
        assert g.feed(10, 1.0) is True           # plain: 10 tok/s
        assert g.state == "spec_locked"
        assert g.disabled_spec is False
        assert g.mode == "spec"

    def test_governor_disabled_runs_spec_forever(self):
        from llm_consensus_tpu.engine.speculative import SpecGovernor

        g = SpecGovernor(enabled=False)
        assert g.state == "spec_locked"
        assert g.feed(1000, 1000.0) is False
        assert g.mode == "spec"


class TestDrafters:
    """Buffer drafter proposal programs (device units)."""

    def test_prompt_lookup_proposes_matched_continuation(self):
        from llm_consensus_tpu.engine.speculative import _lookup_propose

        # Buffer: ... 7 8 9 ... 7 8 | known length 12, gram (7, 8).
        buf = jnp.asarray(
            [[1, 2, 7, 8, 9, 4, 5, 6, 3, 2, 7, 8, 0, 0, 0, 0]], jnp.int32
        )
        blen = jnp.asarray([12], jnp.int32)
        props = _lookup_propose(buf, blen, k=3, g=2)
        # Most recent earlier occurrence of (7, 8) is at 2; continuation
        # is 9, 4, 5.
        assert props.tolist() == [[9, 4, 5]]

    def test_prompt_lookup_no_match_repeats_last(self):
        from llm_consensus_tpu.engine.speculative import _lookup_propose

        buf = jnp.asarray([[1, 2, 3, 4, 5, 6, 0, 0]], jnp.int32)
        blen = jnp.asarray([6], jnp.int32)
        props = _lookup_propose(buf, blen, k=2, g=3)
        assert props.tolist() == [[6, 6]]  # repetition fallback

    def test_oracle_propose_accept_knob(self):
        from llm_consensus_tpu.engine.speculative import _oracle_propose

        obuf = jnp.asarray([[10, 11, 12, 13, 14, 15, 16, 17]], jnp.int32)
        blen = jnp.asarray([3], jnp.int32)
        full = _oracle_propose(obuf, blen, k=3, vocab=100)
        assert full.tolist() == [[13, 14, 15]]
        forced = _oracle_propose(obuf, blen, k=3, vocab=100, accept=2)
        # First accept-1 = 1 proposal true, the rest perturbed (+1).
        assert forced.tolist() == [[13, 15, 16]]

    def test_oracle_forced_acceptance_levels(self, target):
        """accept=a makes every single-stream round accept EXACTLY a
        (the bench's sweep knob) while output stays exact."""
        prompt = "forced acceptance sweep probe"
        s = SamplingParams(max_new_tokens=24, ignore_eos=True)
        ref = target.generate(prompt, s)
        cont = ref.token_ids
        for accept in (1, 2):
            spec = SpeculativeEngine(
                target, OracleDrafter(cont, accept=accept), k=3,
                adaptive=False, governor=False,
            )
            got = spec.generate(prompt, s)
            assert got.token_ids == ref.token_ids
            assert spec.mean_accepted == pytest.approx(accept, abs=0.35)

    def test_oracle_single_stream_ceiling(self, target):
        prompt = "oracle ceiling probe"
        s = SamplingParams(max_new_tokens=24, ignore_eos=True)
        ref = target.generate(prompt, s)
        spec = SpeculativeEngine(
            target, OracleDrafter(ref.token_ids), k=3,
            adaptive=False, governor=False,
        )
        got = spec.generate(prompt, s)
        assert got.token_ids == ref.token_ids
        assert spec.mean_accepted == pytest.approx(4.0, abs=0.5)

    def test_prompt_lookup_single_stream_exact(self, target):
        spec = SpeculativeEngine(
            target, PromptLookupDrafter(), k=3, governor=False,
        )
        s = SamplingParams(max_new_tokens=32, ignore_eos=True)
        prompt = "prompt lookup drafter single stream probe"
        got = spec.generate(prompt, s)
        ref = target.generate(prompt, s)
        assert got.token_ids == ref.token_ids
        assert got.spec is not None and got.spec["rounds"] > 0


def test_sampled_key_schedule_immune_to_fetch_batching(target,
                                                       unrelated_draft):
    """The sampled path's key schedule is a pure function of the round
    counter — NOT of drain cadence — so changing rounds_per_chunk (fetch
    batching) must not change a seeded generation's tokens. A schedule
    keyed on len(out_ids)/pos_ub would collide across fetch batches and
    bend the output distribution. k is pinned (adaptive off): the
    controller observes at DRAIN boundaries, so adaptive k would
    legitimately walk different ladders under different cadences."""
    s = SamplingParams(max_new_tokens=24, temperature=0.8, seed=9,
                      ignore_eos=True)
    prompt = "key schedule collision probe"
    one = SpeculativeEngine(
        target, unrelated_draft, k=3, rounds_per_chunk=1, adaptive=False,
    ).generate(prompt, s)
    batched = SpeculativeEngine(
        target, unrelated_draft, k=3, rounds_per_chunk=8, adaptive=False,
    ).generate(prompt, s)
    assert one.token_ids == batched.token_ids


def test_cli_draft_flag_token_exact(monkeypatch):
    """--draft through the full CLI produces the identical consensus to a
    run without it (greedy exactness at the product surface) — and the
    draft actually engages (placement pinned to one device; a wider
    planner mesh would silently disable speculation and make the
    exactness assertion vacuous)."""
    import io
    import json

    from llm_consensus_tpu.cli.main import main
    from llm_consensus_tpu.providers.tpu import TPUProvider

    orig_prepare = TPUProvider.prepare
    monkeypatch.setattr(
        TPUProvider, "prepare",
        lambda self, models, judge, devices=None: orig_prepare(
            self, models, judge, devices=jax.devices()[:1]
        ),
    )

    def run_cli(extra):
        # Fresh shared provider per invocation: draft state and engines
        # must not carry across the compared runs.
        monkeypatch.setattr(TPUProvider, "_shared", None)
        stdout, stderr = io.StringIO(), io.StringIO()
        code = main(
            ["--models", "tpu:tiny-mistral", "--judge", "tpu:tiny-mistral",
             "--json", "--no-save", "--max-tokens", "16", "exact check"]
            + extra,
            stdin=io.StringIO(""), stdout=stdout, stderr=stderr,
            install_signal_handlers=False,
        )
        assert code == 0, stderr.getvalue()
        return json.loads(stdout.getvalue()), TPUProvider._shared

    plain, _ = run_cli([])
    drafted, provider = run_cli(["--draft", "tiny-llama"])
    assert drafted["responses"][0]["content"] == plain["responses"][0]["content"]
    assert drafted["consensus"] == plain["consensus"]
    entry = provider._specs.get("tiny-mistral")
    assert entry is not None and entry[1] is not None, "draft never engaged"
    assert entry[1].stats["rounds"] > 0
