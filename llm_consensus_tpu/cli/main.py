"""CLI entry point — flag-for-flag parity with the reference binary.

Parity: /root/reference/cmd/llm-consensus/main.go. Preserved behaviors:

  * Flag set (main.go:312-322): --models --judge --file --output --data-dir
    --timeout --quiet/-q --json --no-save --version (single-dash Go-style
    spellings also accepted).
  * Prompt precedence: positional args > --file > piped stdin (main.go:363-393).
  * Registry init: one provider per unique model, judge auto-added
    (main.go:395-415); unknown model errors list the available set.
  * Run lifecycle: signal-cancelled context (main.go:90-91), progress UI on
    stderr when it is a TTY and not quiet/json, best-effort fan-out, judge
    synthesis with its own progress, auto-save to data/<run-id>/, output
    routing matrix file | --json stdout | pretty TTY | JSON stdout
    (main.go:187-273).
  * Errors print ``error: ...`` to stderr and exit 1 (main.go:76-81).

New in the TPU build: ``tpu:<model>`` model names route to the on-device
engine provider; everything else resolves through the known-models table
like the reference.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time
from functools import partial
from dataclasses import dataclass, field as dataclasses_field, replace as dataclasses_replace
from typing import Callable, Optional, TextIO

from llm_consensus_tpu import output as output_mod
from llm_consensus_tpu import ui
from llm_consensus_tpu.analysis import sanitizer
from llm_consensus_tpu.consensus import (
    Judge,
    grade_confidence,
    score_agreement,
    render_critique_prompt,
    render_refine_prompt,
    render_vote_prompt,
    tally_votes,
)
from llm_consensus_tpu.output.persist import reserve_run_dir, save_aux_files
from llm_consensus_tpu.providers import Provider, Registry
from llm_consensus_tpu.runner import Callbacks, Runner
from llm_consensus_tpu.utils.context import Context
from llm_consensus_tpu.version import version_string
from llm_consensus_tpu.utils import knobs

DEFAULT_JUDGE = "gpt-5.2-pro-2025-12-11"  # main.go:34
DEFAULT_TIMEOUT_S = 120  # main.go:35

# Known models → provider kind (main.go:49-61). The catalog itself lives
# in providers/registry.py (REMOTE_MODELS) so the router's spillover lane
# can build remote providers without importing the CLI; this alias keeps
# the CLI's historical name.
from llm_consensus_tpu.providers.registry import REMOTE_MODELS as KNOWN_MODELS

ProviderFactory = Callable[[str], Provider]


@dataclass
class Config:
    """Parsed CLI configuration (main.go:63-74)."""

    models: list[str]
    judge: str = DEFAULT_JUDGE
    file: str = ""
    output: str = ""
    data_dir: str = "data"
    timeout: float = DEFAULT_TIMEOUT_S
    prompt: str = ""
    quiet: bool = False
    json: bool = False
    no_save: bool = False
    max_tokens: "Optional[int]" = None
    trace: str = ""
    rounds: int = 1          # multi-round consensus (TPU-build extension)
    vote: bool = False       # voting mode (TPU-build extension)
    options: list[str] = dataclasses_field(default_factory=list)
    continue_run: str = ""   # run-id to continue from (TPU-build extension)
    system: str = ""         # system prompt for panel models (extension)
    interactive: bool = False  # REPL mode (extension)
    confidence: bool = False  # judge-graded consensus confidence (extension)
    draft: str = ""          # speculative-decoding draft spec (extension)
    spec_k: "Optional[int]" = None  # draft-length ceiling (extension)
    events: bool = False     # run telemetry → trace.json/metrics.json (ext.)
    profile: bool = False    # bounded deep-profiler window (extension)
    prefill_budget: "Optional[int]" = None  # interleaved admission (ext.)
    judge_overlap: bool = False  # incremental judge prefill (extension)
    resume: str = ""         # run-id to resume after a crash (extension)
    priority: str = ""       # panel priority class (pressure/, extension)


class CLIError(Exception):
    """User-facing CLI error → ``error: ...`` + exit 1."""


def create_provider(model: str, draft: Optional[str] = None,
                    spec_k: Optional[int] = None) -> Provider:
    """Resolve a model name to its provider (main.go:417-438).

    ``tpu:<name>`` → on-device engine; otherwise the known-models table.
    ``draft`` / ``spec_k`` (the ``--draft`` / ``--spec-k`` flags)
    configure speculative decoding on the shared tpu provider — plumbed
    as arguments rather than env vars so one run's flags can't leak into
    the next in-process run.
    """
    if model.startswith("tpu:"):
        try:
            from llm_consensus_tpu.providers.tpu import TPUProvider
        except ImportError as err:
            raise CLIError(f"tpu provider unavailable: {err}") from err
        provider = TPUProvider.shared()
        if draft is not None:
            provider.set_draft(draft, k=spec_k)
        return provider
    from llm_consensus_tpu.providers.registry import create_remote_provider

    try:
        return create_remote_provider(model)
    except ValueError:
        available = sorted(KNOWN_MODELS) + ["tpu:<model>"]
        raise CLIError(
            f"unknown model {model!r}; available models: {available}"
        ) from None


def init_registry(
    models: list[str], judge: Optional[str], factory: ProviderFactory
) -> Registry:
    """One provider per unique model, judge included (main.go:395-415).

    ``judge=None`` (voting mode) registers the panel only."""
    registry = Registry()
    for model in dict.fromkeys(models + ([judge] if judge else [])):
        try:
            provider = factory(model)
        except CLIError:
            raise
        except Exception as err:
            raise CLIError(f"initializing provider for {model}: {err}") from err
        registry.register(model, provider)
    return registry


def get_prompt(args: list[str], file: str, stdin: TextIO) -> str:
    """Prompt precedence: positional > --file > piped stdin (main.go:363-393)."""
    if args:
        return " ".join(args)
    if file:
        try:
            with open(file, "r", encoding="utf-8") as f:
                return f.read().strip()
        except OSError as err:
            raise CLIError(f"reading prompt file: {err}") from err
    if stdin is not None and not ui.is_terminal(stdin):
        return stdin.read().rstrip("\n")
    raise CLIError("no prompt provided: use positional argument, --file, or pipe to stdin")


# Config-file keys that set flag defaults (CLI flags always win).
_CONFIG_FLAG_KEYS = frozenset({
    "models", "judge", "timeout", "data_dir", "max_tokens", "system",
    "rounds", "confidence", "draft",
})


def load_config_file() -> tuple[dict, str]:
    """Persistent configuration (reference roadmap §7.1): defaults and
    model aliases from ``.llm-consensus.json`` in the working directory,
    else ``~/.llm-consensus.json``. ``LLMC_CONFIG=<path>`` overrides the
    search; ``LLMC_CONFIG=0`` disables. Returns ({}, "") when none found.
    """
    env = knobs.get_str("LLMC_CONFIG")
    if env == "0":
        return {}, ""
    if env:
        path = os.path.expanduser(env)
        if not os.path.exists(path):
            raise CLIError(f"LLMC_CONFIG points to a missing file: {path}")
        candidates = [path]
    else:
        candidates = [
            ".llm-consensus.json",
            os.path.expanduser("~/.llm-consensus.json"),
        ]
    for path in candidates:
        if not os.path.exists(path):
            continue
        try:
            with open(path, "r", encoding="utf-8") as f:
                data = json.load(f)
        except (OSError, ValueError) as err:
            raise CLIError(f"reading config file {path}: {err}") from err
        if not isinstance(data, dict):
            raise CLIError(f"config file {path}: expected a JSON object")
        unknown = set(data) - _CONFIG_FLAG_KEYS - {"aliases"}
        if unknown:
            raise CLIError(
                f"config file {path}: unknown keys {sorted(unknown)} "
                f"(known: {sorted(_CONFIG_FLAG_KEYS | {'aliases'})})"
            )
        _validate_config_types(data, path)
        return data, path
    return {}, ""


def _validate_config_types(data: dict, path: str) -> None:
    """Reject wrong-typed config values with a CLIError — set_defaults()
    bypasses argparse type conversion, so raw JSON types flow straight
    into the run otherwise."""
    def fail(key, expected):
        raise CLIError(
            f"config file {path}: {key!r} must be {expected}, "
            f"got {type(data[key]).__name__}"
        )

    for key in ("models", "judge", "system", "data_dir"):
        if key in data and not isinstance(data[key], str):
            fail(key, "a string")
    for key in ("timeout", "max_tokens"):
        if key in data and (
            isinstance(data[key], bool) or not isinstance(data[key], (int, float))
        ):
            fail(key, "a number")
    if "rounds" in data and (
        isinstance(data["rounds"], bool) or not isinstance(data["rounds"], int)
    ):
        fail("rounds", "an integer")
    if "confidence" in data and not isinstance(data["confidence"], bool):
        fail("confidence", "a boolean")
    aliases = data.get("aliases")
    if aliases is not None:
        if not isinstance(aliases, dict) or not all(
            isinstance(k, str) and isinstance(v, str) for k, v in aliases.items()
        ):
            raise CLIError(
                f"config file {path}: 'aliases' must map alias names to "
                f"comma-separated model strings"
            )


def expand_aliases(models: list[str], aliases: dict) -> list[str]:
    """``@alias`` entries → their comma-separated model lists (reference
    roadmap §1.2). Duplicates are preserved — an explicit repeated model
    has always meant two queries, and alias overlap follows the same
    rule."""
    out: list[str] = []
    for m in models:
        if m.startswith("@"):
            if m not in aliases:
                raise CLIError(
                    f"unknown model alias {m!r}; defined: {sorted(aliases)}"
                )
            out.extend(x.strip() for x in aliases[m].split(",") if x.strip())
        else:
            out.append(m)
    return out


def parse_args(argv: list[str], stdin: TextIO, stdout: TextIO) -> Optional[Config]:
    """Parse flags; returns None when --version handled (main.go:298-361)."""
    parser = argparse.ArgumentParser(
        prog="llm-consensus",
        description="Query multiple LLMs in parallel and synthesize a consensus answer.",
        add_help=True,
    )
    parser.add_argument("--models", "-models", default="", metavar="LIST",
                        help="Comma-separated list of models to query (required)")
    parser.add_argument("--judge", "-judge", default=DEFAULT_JUDGE,
                        help="Model to use for consensus synthesis")
    parser.add_argument("--file", "-file", default="", help="Read prompt from file")
    parser.add_argument("--output", "-output", default="",
                        help="Write JSON output to specific file (overrides auto-save)")
    parser.add_argument("--data-dir", "-data-dir", default="data",
                        help="Directory for auto-saved runs")
    parser.add_argument("--timeout", "-timeout", type=int, default=DEFAULT_TIMEOUT_S,
                        help="Per-model timeout in seconds")
    parser.add_argument("--max-tokens", "-max-tokens", type=int, default=None,
                        help="Max tokens generated per model (tpu models; TPU-build extension)")
    parser.add_argument("--trace", "-trace", default="", metavar="DIR",
                        help="Write a jax.profiler trace of the run to DIR (TPU-build extension)")
    parser.add_argument("--events", "-events", action="store_true",
                        help="Record the run's host telemetry timeline "
                             "(spans/counters/instants across engine, "
                             "batcher, runner, exchange); persisted as "
                             "trace.json (Perfetto-loadable) + metrics.json "
                             "in the run dir. LLMC_EVENTS=1 is equivalent "
                             "(TPU-build extension)")
    parser.add_argument("--profile", "-profile", action="store_true",
                        help="Arm one bounded deep-profiling window "
                             "(obs/profiler) around the run — the same "
                             "jax.profiler artifact POST /debugz/profile "
                             "produces, capped at LLMC_PROFILE_MAX_S and "
                             "closed when the run finishes. Unlike "
                             "--trace it is rate-limited and lands in an "
                             "atomic artifact dir under LLMC_PROFILE_DIR "
                             "(TPU-build extension)")
    parser.add_argument("--rounds", "-rounds", type=int, default=1,
                        help="Consensus rounds: after each synthesis the panel "
                             "critiques the draft and the judge refines it "
                             "(TPU-build extension)")
    parser.add_argument("--vote", "-vote", action="store_true",
                        help="Voting mode: panel picks one of --options; no judge "
                             "(TPU-build extension)")
    parser.add_argument("--options", "-options", default="", metavar="LIST",
                        help="Comma-separated options for --vote")
    parser.add_argument("--continue", "-continue", dest="continue_run",
                        default="", metavar="RUN_ID",
                        help="Continue the conversation from a saved run in "
                             "--data-dir (TPU-build extension)")
    parser.add_argument("--resume", "-resume", default="", metavar="RUN_ID",
                        help="Finish a crashed run in --data-dir: reuse the "
                             "panel answers its journal already completed "
                             "(data/<run-id>/panel/), rerun only the "
                             "missing/failed models, then the judge "
                             "(TPU-build extension)")
    parser.add_argument("--system", "-system", default="",
                        help="System prompt for every panel model "
                             "(TPU-build extension)")
    parser.add_argument("--system-file", "-system-file", default="",
                        metavar="PATH",
                        help="Read the system prompt from a file")
    parser.add_argument("--interactive", "-interactive", "-i",
                        action="store_true",
                        help="REPL mode: one consensus query per line, "
                             "conversation carried across queries "
                             "(TPU-build extension)")
    parser.add_argument("--confidence", "-confidence", action="store_true",
                        help="After synthesis, the judge grades its "
                             "confidence in the consensus (0-100) and lists "
                             "controversy points (TPU-build extension)")
    parser.add_argument("--spec-k", "-spec-k", type=int, default=None,
                        metavar="K",
                        help="Speculative draft-length ceiling per round "
                             "(default LLMC_SPEC_K or 4); adaptive k walks "
                             "a pow2 ladder below it")
    parser.add_argument("--draft", "-draft", default="", metavar="SPEC",
                        help="Speculative decoding for tpu models: 'lookup' "
                             "(prompt-lookup n-grams, zero draft cost, "
                             "composes with --max-batch pools), a draft "
                             "preset for all targets (e.g. consensus-1b) or "
                             "target=draft pairs (a=b,c=d). Greedy output "
                             "is token-exact; the draft only changes speed")
    parser.add_argument("--prefill-budget", "-prefill-budget", type=int,
                        default=None, metavar="TOKENS",
                        help="Interleaved admission prefill for tpu "
                             "continuous batching: dispatch at most this "
                             "many prompt tokens of a new stream's prefill "
                             "between decode chunks, so resident streams "
                             "keep decoding during admission. 0/unset = "
                             "classic stall-the-pool admission; "
                             "LLMC_PREFILL_BUDGET is equivalent "
                             "(TPU-build extension)")
    parser.add_argument("--judge-overlap", "-judge-overlap",
                        action="store_true",
                        help="Prefill the judge prompt incrementally as "
                             "panel answers arrive (tpu judges), cutting "
                             "judge time-to-first-token by nearly the "
                             "whole prompt prefill. LLMC_JUDGE_OVERLAP=1 "
                             "is equivalent (TPU-build extension)")
    parser.add_argument("--priority", "-priority", default="",
                        metavar="CLASS",
                        help="Priority class for the panel queries "
                             "(high/normal/low or 0-2): orders "
                             "continuous-batcher admission and selects "
                             "preemption victims on shared engines. The "
                             "judge always outranks the panel by one "
                             "class. Default: normal")
    parser.add_argument("--quiet", "-quiet", "-q", action="store_true",
                        help="Suppress progress output")
    parser.add_argument("--json", "-json", action="store_true",
                        help="Output JSON to stdout (no interactive display, no auto-save)")
    parser.add_argument("--no-save", "-no-save", action="store_true",
                        help="Don't auto-save results to data directory")
    parser.add_argument("--version", "-version", action="store_true",
                        help="Print version information and exit")
    parser.add_argument("prompt", nargs="*", help="The prompt (or use --file / stdin)")

    # Config-file values become flag defaults, so explicit flags always
    # win: CLI > config file > built-in default. --version/--help must
    # work even with a broken config (how else would one debug it?), so
    # those invocations skip the config entirely.
    skip_config = any(
        a in ("--version", "-version", "--help", "-h") for a in argv
    )
    config, _config_path = ({}, "") if skip_config else load_config_file()
    flag_defaults = {k: v for k, v in config.items() if k in _CONFIG_FLAG_KEYS}
    if flag_defaults:
        parser.set_defaults(**flag_defaults)

    ns = parser.parse_args(argv)

    if ns.version:
        stdout.write(version_string() + "\n")
        return None

    if not ns.models and not ns.resume:
        raise CLIError("--models flag is required")

    options = [o.strip() for o in ns.options.split(",") if o.strip()]
    if ns.vote and len(options) < 2:
        raise CLIError("--vote requires --options with at least two choices")
    if options and not ns.vote:
        raise CLIError("--options only applies with --vote")
    if ns.rounds < 1:
        raise CLIError("--rounds must be >= 1")
    if ns.vote and ns.rounds != 1:
        raise CLIError("--vote and --rounds are mutually exclusive")
    if ns.vote and ns.confidence:
        raise CLIError(
            "--vote and --confidence are mutually exclusive (voting mode "
            "has no judge to grade the consensus)"
        )

    system = ns.system
    if ns.system_file:
        if system:
            raise CLIError("--system and --system-file are mutually exclusive")
        try:
            with open(ns.system_file, "r", encoding="utf-8") as f:
                system = f.read().strip()
        except OSError as err:
            raise CLIError(f"reading system prompt file: {err}") from err

    models = expand_aliases(
        [m.strip() for m in ns.models.split(",") if m.strip()],
        config.get("aliases", {}) or {},
    )
    judge_list = expand_aliases([ns.judge], config.get("aliases", {}) or {})
    if len(judge_list) != 1:
        raise CLIError(
            f"--judge must resolve to exactly one model, got {judge_list}"
        )
    judge = judge_list[0]
    cfg = Config(
        models=models,
        judge=judge,
        file=ns.file,
        output=ns.output,
        data_dir=ns.data_dir,
        timeout=float(ns.timeout),
        quiet=ns.quiet,
        json=ns.json,
        no_save=ns.no_save,
        max_tokens=ns.max_tokens,
        trace=ns.trace,
        rounds=ns.rounds,
        vote=ns.vote,
        options=options,
        continue_run=ns.continue_run,
        system=system,
        interactive=ns.interactive,
        confidence=ns.confidence,
        draft=ns.draft,
        spec_k=ns.spec_k,
        events=ns.events,
        profile=ns.profile,
        prefill_budget=ns.prefill_budget,
        judge_overlap=ns.judge_overlap,
        priority=ns.priority,
    )
    if cfg.priority:
        from llm_consensus_tpu.pressure import parse_priority

        try:
            parse_priority(cfg.priority)
        except ValueError as err:
            raise CLIError(str(err)) from err
    if ns.resume:
        # A resumed run's identity (prompt, panel, judge, settings) comes
        # from its manifest; flags that would change the identity — or
        # disable the persistence the resume writes into — contradict it.
        if ns.prompt or ns.file:
            raise CLIError("--resume takes the prompt from the saved run")
        if ns.interactive:
            raise CLIError("--resume and --interactive are incompatible")
        if ns.continue_run:
            raise CLIError("--resume and --continue are incompatible")
        if ns.output or ns.json or ns.no_save:
            raise CLIError(
                "--resume writes into the saved run directory; it is "
                "incompatible with --output/--json/--no-save"
            )
        # Identity-changing flags are silently overridden by the
        # manifest — reject them instead of discarding the user's
        # intent. Checked against argv (not parsed values) so config-
        # file defaults don't false-positive.
        identity_flags = (
            "--models", "-models", "--judge", "-judge", "--system",
            "-system", "--system-file", "-system-file", "--max-tokens",
            "-max-tokens", "--vote", "-vote", "--options", "-options",
            "--rounds", "-rounds", "--confidence", "-confidence",
        )
        clashing = sorted({
            f for f in identity_flags
            for a in argv if a == f or a.startswith(f + "=")
        })
        if clashing:
            raise CLIError(
                f"--resume takes {', '.join(clashing)} from the saved "
                "run's manifest; drop the flag(s) or start a fresh run"
            )
        cfg.resume = ns.resume
        return cfg
    if ns.interactive:
        if ns.prompt:
            raise CLIError("--interactive takes queries from stdin, not arguments")
        if ns.file:
            raise CLIError("--interactive takes queries from stdin, not --file")
        if ns.output:
            raise CLIError(
                "--interactive and --output are incompatible (each query "
                "would overwrite the file); use the auto-saved run dirs"
            )
        return cfg
    cfg.prompt = get_prompt(ns.prompt, ns.file, stdin)
    return cfg


def load_history(data_dir: str, run_id: str) -> list[dict]:
    """Conversation history for ``--continue`` (reference roadmap §3.1).

    Returns the prior run's history plus its own exchange, oldest first."""
    path = os.path.join(data_dir, run_id, "result.json")
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError) as err:
        raise CLIError(f"loading run {run_id!r}: {err}") from err
    if not isinstance(data, dict) or "prompt" not in data or "consensus" not in data:
        raise CLIError(f"run {run_id!r} has no usable result.json")
    history = [
        h for h in data.get("history", [])
        if isinstance(h, dict) and "prompt" in h and "consensus" in h
    ]
    history.append({"prompt": data["prompt"], "consensus": data["consensus"]})
    return history


def _slug(model: str) -> str:
    """Filesystem-safe model-name slug for panel journal files."""
    return "".join(c if c.isalnum() or c in "-_." else "_" for c in model)


def write_run_manifest(run_dir: str, cfg: Config, history: list[dict],
                       warn=None) -> None:
    """Persist the run's identity BEFORE the panel fan-out, so a crashed
    process leaves enough in ``data/<run-id>/`` for ``--resume`` to
    finish the run: prompt, panel, judge, and every setting that changes
    what the models see."""
    from llm_consensus_tpu.output.persist import save_file

    manifest = {
        "prompt": cfg.prompt,
        "models": list(cfg.models),
        "judge": cfg.judge,
        "system": cfg.system,
        "max_tokens": cfg.max_tokens,
        "timeout": cfg.timeout,
        "rounds": cfg.rounds,
        "vote": cfg.vote,
        "options": list(cfg.options),
        "confidence": cfg.confidence,
        "history": history,
    }
    save_file(run_dir, "run.json", json.dumps(manifest, indent=2), warn=warn)


def load_resume_manifest(data_dir: str, run_id: str) -> dict:
    """The saved run's manifest, or a CLIError that says what's wrong."""
    run_dir = os.path.join(data_dir, run_id)
    path = os.path.join(run_dir, "run.json")
    if os.path.exists(os.path.join(run_dir, "result.json")):
        raise CLIError(
            f"run {run_id!r} already completed (result.json exists); "
            "use --continue to build on it"
        )
    try:
        with open(path, "r", encoding="utf-8") as f:
            manifest = json.load(f)
    except (OSError, ValueError) as err:
        raise CLIError(
            f"resuming run {run_id!r}: no usable run.json ({err}); only "
            "runs started by this version journal their manifest"
        ) from err
    if not isinstance(manifest, dict) or not manifest.get("models"):
        raise CLIError(f"resuming run {run_id!r}: run.json has no panel")
    return manifest


def load_panel_journal(run_dir: str) -> list:
    """Completed panel answers journaled under ``<run_dir>/panel/``,
    in journal order. Torn or unparseable files are skipped — their
    models simply rerun, which is the safe direction."""
    from llm_consensus_tpu.providers import Response

    panel_dir = os.path.join(run_dir, "panel")
    if not os.path.isdir(panel_dir):
        return []
    out = []
    for name in sorted(os.listdir(panel_dir)):
        if not name.endswith(".json"):
            continue
        try:
            with open(os.path.join(panel_dir, name), encoding="utf-8") as f:
                doc = json.load(f)
            out.append(Response(
                model=doc["model"],
                content=doc["content"],
                provider=doc.get("provider", ""),
                latency_ms=doc.get("latency_ms", 0.0),
                truncated=doc.get("truncated", False),
                tokens=doc.get("tokens"),
                tokens_per_sec=doc.get("tokens_per_sec"),
                mfu=doc.get("mfu"),
                mbu=doc.get("mbu"),
            ))
        except (OSError, ValueError, KeyError, TypeError):
            continue
    return out


def render_conversation(history: list[dict], prompt: str) -> str:
    """Fold earlier exchanges into the prompt the models see."""
    parts = ["Earlier exchanges in this conversation:"]
    for h in history:
        parts.append(f"\n[User]\n{h['prompt']}\n\n[Answer]\n{h['consensus']}")
    parts.append(f"\nCurrent follow-up prompt:\n{prompt}")
    return "\n".join(parts)


def run(
    cfg: Config,
    ctx: Context,
    *,
    factory: ProviderFactory = create_provider,
    stdout: TextIO,
    stderr: TextIO,
    stdin: Optional[TextIO] = None,
) -> None:
    """Full run lifecycle (main.go:83-276); ``--trace`` wraps it in a
    jax.profiler trace (device + host timelines for every phase)."""
    from llm_consensus_tpu import obs

    if cfg.events:
        # Enable the run telemetry recorder BEFORE any provider, engine,
        # runner, or batcher exists: consumers bind it at construction
        # time (the obs/faults zero-cost pattern), so a late install
        # would record nothing. LLMC_EVENTS=1 resolves equivalently.
        if obs.recorder() is None:
            from llm_consensus_tpu.providers.tpu import TPUProvider

            if TPUProvider._shared is not None:
                # A warm shared provider predates this install: its
                # engines/batchers bound None at construction and will
                # not record. Say so rather than emitting a silently
                # hollow trace.
                stderr.write(
                    "warning: --events enabled after the shared tpu "
                    "provider was built; its warm engines will not "
                    "record device spans this run (use --events from "
                    "the first run of the process, or LLMC_EVENTS=1)\n"
                )
            obs.install(obs.Recorder(max_events=obs.resolve_max_events()))
    elif not knobs.get_bool("LLMC_EVENTS", False):
        # The --events install is flag-scoped: a previous run() in this
        # process must not leak its recorder into a run that didn't ask
        # for telemetry. The env remains the process-wide opt-in.
        obs.install(None)
    # A resumed run's identity comes from the saved manifest — applied
    # BEFORE the tpu-model scan below, so a resumed on-device run still
    # joins its cluster / plans its placement exactly like the original.
    resume_manifest = None
    if cfg.resume:
        resume_manifest = manifest = load_resume_manifest(
            cfg.data_dir, cfg.resume
        )
        cfg = dataclasses_replace(
            cfg,
            prompt=manifest.get("prompt", ""),
            models=list(manifest["models"]),
            judge=manifest.get("judge") or cfg.judge,
            system=manifest.get("system") or "",
            max_tokens=manifest.get("max_tokens"),
            timeout=float(manifest.get("timeout") or cfg.timeout),
            rounds=int(manifest.get("rounds") or 1),
            vote=bool(manifest.get("vote", False)),
            options=list(manifest.get("options") or []),
            confidence=bool(manifest.get("confidence", False)),
        )
    # Join the multi-host cluster first: jax.distributed.initialize must
    # run before anything initializes the JAX backend (start_trace does).
    # No-op unless LLMC_COORDINATOR/LLMC_NUM_PROCESSES or a TPU-pod env
    # says this process is part of a cluster. Voting mode never runs the
    # judge, so a tpu: judge name alone doesn't pull in the TPU stack.
    run_models = cfg.models + ([] if cfg.vote else [cfg.judge])
    if cfg.prefill_budget is not None:
        # The batcher reads LLMC_PREFILL_BUDGET at construction; setting
        # it before any provider/engine exists makes the flag and the env
        # equivalent. Batchers already warm in this process keep the
        # budget they were built with (interactive sessions: the flag
        # applies from the first query).
        os.environ["LLMC_PREFILL_BUDGET"] = str(cfg.prefill_budget)
    if factory is create_provider:
        # Thread --draft through to the tpu provider as an argument
        # UNCONDITIONALLY (an env side-channel would leak this run's
        # draft into later in-process runs — and so would skipping the
        # call when the flag is empty: the shared provider would keep a
        # previous run's draft map; set_draft('') clears it). Injected
        # test factories keep their own shape.
        factory = partial(create_provider, draft=cfg.draft, spec_k=cfg.spec_k)
    if any(m.startswith("tpu:") for m in run_models):
        from llm_consensus_tpu.parallel.distributed import initialize

        try:
            initialize()
        except Exception as err:
            raise CLIError(f"joining distributed cluster: {err}") from err
        import jax

        if jax.process_count() > 1 and cfg.interactive:
            # A REPL cannot keep N controller processes in lockstep —
            # secondary controllers have no stdin, and a diverged process
            # would deadlock the cluster inside the next collective.
            raise CLIError(
                "--interactive is not supported under multi-controller "
                "execution; pass the prompt as an argument or --file"
            )

    def body() -> None:
        if cfg.interactive:
            interactive_loop(
                cfg, ctx, factory=factory,
                stdin=stdin if stdin is not None else sys.stdin,
                stdout=stdout, stderr=stderr,
            )
        else:
            _run(cfg, ctx, factory=factory, stdout=stdout, stderr=stderr,
                 resume_manifest=resume_manifest)

    if not cfg.trace:
        if not cfg.profile:
            return body()
        # --profile: one bounded window through the deep profiler — the
        # same artifact contract as POST /debugz/profile (atomic dir,
        # duration capped at LLMC_PROFILE_MAX_S), closed early when the
        # run finishes first. Force-installed like --events: the flag is
        # an explicit ask, it overrides a disabled-by-env profiler.
        from llm_consensus_tpu.obs import profiler as profiler_mod

        prof = profiler_mod.profiler()
        if prof is None:
            prof = profiler_mod.DeepProfiler()
            profiler_mod.install(prof)
        path, status = prof.arm(prof.max_s, tag="cli")
        if status != "armed":
            stderr.write(
                f"warning: --profile window not armed ({status})\n"
            )
            return body()
        try:
            return body()
        finally:
            final = prof.stop_now() or path
            if final:
                stderr.write(f"profile artifact: {final}\n")
    try:
        import jax

        jax.profiler.start_trace(cfg.trace)
    except Exception as err:
        raise CLIError(f"starting profiler trace: {err}") from err
    try:
        return body()
    finally:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass


def _run(
    cfg: Config,
    ctx: Context,
    *,
    factory: ProviderFactory,
    stdout: TextIO,
    stderr: TextIO,
    history: "Optional[list[dict]]" = None,
    resume_manifest: "Optional[dict]" = None,
) -> output_mod.Result:
    show_ui = ui.is_terminal(stderr) and not cfg.quiet and not cfg.json
    start_time = time.monotonic()

    # Per-query telemetry reset AT ENTRY (not exit): interactive sessions
    # call _run once per query and catch CLIError to keep the session
    # alive, so an exit-side clear would be skipped on failure paths and
    # leak the failed query's events into the next query's artifacts.
    # Consumers keep their bound reference (warm engines), so the
    # recorder empties in place.
    from llm_consensus_tpu import obs as obs_mod

    recorder = obs_mod.recorder()
    if recorder is not None:
        recorder.clear()
    # Live/attrib watermarks: the no-events metrics.json below persists
    # only when THIS query grew the (process-lifetime) planes — a run
    # that observed nothing must not inherit telemetry files at all.
    # The PERSISTED content is still the cumulative process snapshot
    # (the same contract serve-mode per-run metrics.json has had since
    # PR 10: one-shot processes are exact, interactive sessions
    # accumulate — see Scheduler.persist).
    _live_plane = obs_mod.live.metrics()
    live_counts0 = _live_plane.counts() if _live_plane is not None else 0
    _attrib_led = obs_mod.attrib.ledger()
    attrib_counts0 = (
        _attrib_led.activity() if _attrib_led is not None else 0
    )

    # Resume state (--resume): the crashed run's dir, conversation
    # history, and the panel answers its journal already completed — the
    # models those answers cover are NOT rerun.
    resume_dir = ""
    completed_responses: list = []
    if cfg.resume:
        resume_dir = os.path.join(cfg.data_dir, cfg.resume)
        manifest = (
            resume_manifest if resume_manifest is not None
            else load_resume_manifest(cfg.data_dir, cfg.resume)
        )
        history = [
            h for h in manifest.get("history", [])
            if isinstance(h, dict) and "prompt" in h and "consensus" in h
        ]
        completed_responses = load_panel_journal(resume_dir)

    # Conversation context: injected by interactive mode, or loaded from
    # --continue's saved run. Folded into the prompt the models (and
    # judge) see; Result.prompt / prompt.txt keep the raw follow-up.
    # Loaded first so a bad run-id fails fast — before provider init,
    # device placement, or the live progress display spin up.
    if history is None:
        history = []
        if cfg.continue_run:
            history = load_history(cfg.data_dir, cfg.continue_run)
    context_prompt = (
        render_conversation(history, cfg.prompt) if history else cfg.prompt
    )

    # Voting mode never queries a judge, so no judge provider (or judge
    # API key / judge chip slice) is required.
    judge = None if cfg.vote else cfg.judge
    registry = init_registry(cfg.models, judge, factory)

    # Announce the run composition so providers can plan device placement
    # (the tpu provider carves panel + judge onto disjoint mesh slices).
    seen: set = set()
    for model in dict.fromkeys(cfg.models + ([judge] if judge else [])):
        provider = registry.get(model)
        if id(provider) in seen:
            continue
        seen.add(id(provider))
        try:
            provider.prepare(cfg.models, judge)
        except Exception as err:
            raise CLIError(f"planning device placement: {err}") from err

    # Multi-controller execution: with several controller processes, each
    # queries only the models whose slice it can address; results merge
    # via one allgather and the judge's owner broadcasts the synthesis
    # (runner/multihost.py, parallel/multicontroller.py). Checked only
    # when on-device models are in play, so HTTP-only runs never touch
    # the JAX backend.
    multictrl = False
    mc = None
    if any(m.startswith("tpu:") for m in cfg.models + ([judge] if judge else [])):
        from llm_consensus_tpu.parallel import multicontroller as mc

        multictrl = mc.is_multicontroller()
    if multictrl:
        # Every controller must run the IDENTICAL prompt: argv/--file
        # reach all processes, but a stdin-piped prompt exists only on
        # the launching terminal — process 0's wins everywhere.
        context_prompt = mc.broadcast_json(context_prompt, owner=0)
        if cfg.resume:
            # The panel journal is process-0-local; a resumed run's
            # "skip these models" set would diverge across controllers
            # and deadlock the merge collective.
            raise CLIError(
                "--resume is not supported under multi-controller "
                "execution; rerun the prompt instead"
            )

    # Crash-safe run persistence: reserve the run dir and journal the
    # run's identity (run.json) BEFORE the panel fan-out, so a process
    # crash mid-run leaves a resumable dir instead of nothing. Panel
    # answers journal into <run_dir>/panel/ as they complete (atomic
    # per-model files via save_file); --resume reuses them. Runs that
    # disable auto-save (--output/--json/--no-save) keep the old
    # nothing-until-success behavior.
    run_dir = ""
    warn = (lambda msg: ui.print_error(stderr, msg)) if show_ui else None
    if resume_dir:
        run_dir = resume_dir
    elif (
        not cfg.output and not cfg.json and not cfg.no_save
        and not (multictrl and mc.process_index() != 0)
    ):
        try:
            _run_id, run_dir = reserve_run_dir(cfg.data_dir)
        except OSError as err:
            raise CLIError(f"creating run directory: {err}") from err
        write_run_manifest(run_dir, cfg, history, warn=warn)

    if show_ui:
        ui.print_header(stderr, cfg.prompt)
        ui.print_phase(stderr, "Querying models...")
        stderr.write("\n")

    # A resumed run queries only the models whose answers are NOT in the
    # panel journal (duplicates consume one journaled answer each).
    models_to_run = list(cfg.models)
    for resp in completed_responses:
        if resp.model in models_to_run:
            models_to_run.remove(resp.model)
    if cfg.resume and show_ui:
        ui.print_phase(
            stderr,
            f"Resuming {cfg.resume}: reusing {len(completed_responses)} "
            f"journaled answers, rerunning {len(models_to_run)} models",
        )

    progress = ui.Progress(stderr, models_to_run, quiet=not show_ui)
    progress.start()

    panel_priority = None
    judge_priority = None  # None → the Judge default (HIGH)
    if cfg.priority:
        from llm_consensus_tpu.pressure import parse_priority

        panel_priority = parse_priority(cfg.priority)
        # The documented contract: the judge outranks ITS OWN panel by
        # one class — an explicit low-priority batch run must not run
        # its judge at HIGH against other tenants.
        judge_priority = max(0, panel_priority - 1)
    if multictrl:
        from llm_consensus_tpu.runner.multihost import MultiControllerRunner

        runner = MultiControllerRunner(
            registry, cfg.timeout, max_tokens=cfg.max_tokens,
            system=cfg.system or None,
            owner_fn=lambda m: mc.model_owner(registry, m),
        )
    else:
        runner = Runner(
            registry, cfg.timeout, max_tokens=cfg.max_tokens,
            system=cfg.system or None, priority=panel_priority,
        )
    # Judge prefill overlap (consensus/overlap.py): panel answers prefill
    # into the judge engine's growing KV as they arrive, so synthesis
    # TTFT drops by nearly the whole judge-prompt prefill. Engages only
    # under --judge-overlap / LLMC_JUDGE_OVERLAP with a tpu judge;
    # multi-controller runs keep the classic broadcast path (the overlap
    # session is process-local, the broadcast is a collective).
    overlap_judge = None
    if not cfg.vote and not multictrl:
        from llm_consensus_tpu.consensus import make_overlap_judge

        try:
            overlap_judge = make_overlap_judge(
                registry.get(cfg.judge), cfg.judge, context_prompt,
                max_tokens=cfg.max_tokens,
                enabled=True if cfg.judge_overlap else None,
                priority=judge_priority,
            )
        except Exception:  # noqa: BLE001 — unknown judge errors later
            overlap_judge = None
    # Panel journal hook: each completed answer lands atomically in
    # <run_dir>/panel/ the moment its worker records it — the on-disk
    # half of crash-safe runs (--resume reads these back). Numbering
    # continues past reused answers so a resumed rerun never overwrites
    # the journal it is reusing.
    journal_response = None
    if run_dir:
        from llm_consensus_tpu.output.persist import save_file as _save_file

        panel_dir = os.path.join(run_dir, "panel")
        _panel_lock = sanitizer.make_lock("cli.panel")
        # Continue numbering past the highest EXISTING file, not the
        # count of parseable answers: a torn journal file still occupies
        # its index, and a rerun must never clobber a valid file it is
        # simultaneously reusing.
        _next = len(completed_responses)
        if os.path.isdir(panel_dir):
            for _name in os.listdir(panel_dir):
                _head = _name.split("-", 1)[0]
                if _head.isdigit():
                    _next = max(_next, int(_head) + 1)
        _panel_n = [_next]

        def journal_response(resp):
            with _panel_lock:
                n = _panel_n[0]
                _panel_n[0] += 1
            _save_file(
                panel_dir, f"{n:03d}-{_slug(resp.model)}.json",
                json.dumps(resp.to_dict(), indent=2), warn=warn,
            )

    response_hooks = [
        h for h in (
            journal_response,
            overlap_judge.on_response if overlap_judge is not None else None,
        ) if h is not None
    ]
    on_model_response = None
    if response_hooks:
        def on_model_response(resp):
            for hook in response_hooks:
                try:
                    hook(resp)
                except Exception:  # noqa: BLE001 — a hook must not fail a model
                    pass

    runner.with_callbacks(
        Callbacks(
            on_model_start=progress.model_started,
            on_model_stream=progress.model_streaming,
            on_model_complete=progress.model_completed,
            on_model_error=progress.model_failed,
            on_model_response=on_model_response,
        )
    )
    panel_prompt = context_prompt
    if cfg.vote:
        panel_prompt = render_vote_prompt(context_prompt, cfg.options)

    from llm_consensus_tpu.runner import AllModelsFailed, RunResult

    try:
        if models_to_run:
            result = runner.run(ctx, models_to_run, panel_prompt)
        else:
            # Every panel answer came from the journal: nothing to rerun.
            result = RunResult()
    except AllModelsFailed as err:
        if not completed_responses:
            progress.stop()
            raise CLIError(f"running queries: {err}") from err
        # The rerun wiped out, but journaled answers carry the run:
        # best-effort semantics, same as a partial panel failure.
        result = RunResult(
            warnings=[f"resumed rerun failed: {err}"],
            failed_models=list(dict.fromkeys(models_to_run)),
        )
    except Exception as err:
        progress.stop()
        raise CLIError(f"running queries: {err}") from err
    progress.stop()
    if completed_responses:
        result.responses[:0] = completed_responses

    agreement = score_agreement(result.responses)
    if show_ui:
        ui.print_success(stderr, f"Received responses from {len(result.responses)} models")
        if agreement is not None:
            ui.print_phase(
                stderr,
                f"Panel agreement: {agreement.level} ({agreement.score:.2f})",
            )
        stderr.write("\n")

    confidence = None
    if cfg.vote:
        # Voting mode (reference roadmap §2.3): host-side tally, no judge.
        vote_result = tally_votes(result.responses, cfg.options)
        consensus = vote_result.summary()
        judge_name = "vote"
        for m in vote_result.unparsed:
            result.warnings.append(f"{m}: no recognizable vote in response")
        if show_ui:
            ui.print_success(stderr, "Votes tallied!")
    else:
        if show_ui:
            ui.print_phase(stderr, "Synthesizing consensus...")
            stderr.write("\n")

        try:
            judge_provider = registry.get(cfg.judge)
        except Exception as err:
            raise CLIError(f"judge model {cfg.judge}: {err}") from err

        if multictrl:
            # The judge's owner runs the real synthesis on its slice; the
            # text (or the error, in lockstep) broadcasts to the rest.
            judge_provider = mc.BroadcastProvider(
                judge_provider, mc.model_owner(registry, cfg.judge)
            )

        judge = Judge(judge_provider, cfg.judge, max_tokens=cfg.max_tokens,
                      priority=judge_priority)
        judge_name = cfg.judge

        def synthesize(user_prompt: str, responses, syn=None) -> str:
            # ``syn``: round 1 may ride the overlap judge (its session
            # was fed during the panel fan-out); refinement rounds use
            # the classic judge — their prompt differs from the one the
            # overlap header was built with.
            syn = syn if syn is not None else judge
            judge_progress = ui.Progress(stderr, [cfg.judge], quiet=not show_ui)
            judge_progress.start()
            judge_progress.model_started(cfg.judge)
            try:
                text = syn.synthesize_stream(
                    ctx,
                    user_prompt,
                    responses,
                    lambda chunk: judge_progress.model_streaming(cfg.judge, chunk),
                )
            except Exception as err:
                judge_progress.stop()
                raise CLIError(f"consensus synthesis: {err}") from err
            judge_progress.model_completed(cfg.judge)
            judge_progress.stop()
            if syn.last_truncated:
                result.warnings.append(
                    f"{cfg.judge}: judge prompt truncated to fit context window"
                )
            return text

        consensus = synthesize(
            context_prompt, result.responses, syn=overlap_judge
        )

        # Multi-round refinement (reference roadmap §2.2): the panel
        # critiques the draft, the judge refines. Critique responses are
        # intermediate — the Result keeps round 1's panel answers. Later
        # rounds are best-effort like everything else: a failed round
        # becomes a warning and the run keeps the last good consensus
        # (tokens already paid must not be discarded).
        for round_no in range(2, cfg.rounds + 1):
            if show_ui:
                stderr.write("\n")
                ui.print_phase(stderr, f"Round {round_no}: panel critique...")
                stderr.write("\n")
            round_progress = ui.Progress(stderr, cfg.models, quiet=not show_ui)
            round_progress.start()
            runner.with_callbacks(Callbacks(
                on_model_start=round_progress.model_started,
                on_model_stream=round_progress.model_streaming,
                on_model_complete=round_progress.model_completed,
                on_model_error=round_progress.model_failed,
            ))
            try:
                critique = runner.run(
                    ctx, cfg.models, render_critique_prompt(context_prompt, consensus)
                )
            except Exception as err:
                round_progress.stop()
                result.warnings.append(
                    f"round {round_no} critique failed, keeping round "
                    f"{round_no - 1} consensus: {err}"
                )
                break
            round_progress.stop()
            result.warnings.extend(
                f"round {round_no}: {w}" for w in critique.warnings
            )
            if show_ui:
                stderr.write("\n")
                ui.print_phase(stderr, f"Round {round_no}: refining consensus...")
                stderr.write("\n")
            try:
                consensus = synthesize(
                    render_refine_prompt(context_prompt, consensus), critique.responses
                )
            except CLIError as err:
                result.warnings.append(
                    f"round {round_no} synthesis failed, keeping round "
                    f"{round_no - 1} consensus: {err}"
                )
                break

        if show_ui:
            ui.print_success(stderr, "Consensus reached!")

        if cfg.confidence:
            # Judge-graded confidence (roadmap §2.4): one extra judge
            # query; best-effort — a failed or unparseable grading is a
            # warning, never a failed run.
            if show_ui:
                stderr.write("\n")
                ui.print_phase(stderr, "Grading confidence...")
            try:
                graded = grade_confidence(
                    ctx, judge_provider, cfg.judge, context_prompt,
                    result.responses, consensus, max_tokens=cfg.max_tokens,
                )
            except Exception as err:  # noqa: BLE001
                result.warnings.append(f"confidence grading failed: {err}")
            else:
                if graded.score is None:
                    result.warnings.append(
                        "confidence grading returned an unparseable reply"
                    )
                else:
                    confidence = graded.to_dict()
                    if show_ui:
                        ui.print_success(
                            stderr,
                            f"Judge confidence: {graded.score}/100",
                        )
                        for point in graded.controversy:
                            stderr.write(f"  • {point}\n")

    out = output_mod.Result(
        prompt=cfg.prompt,
        responses=result.responses,
        consensus=consensus,
        judge=judge_name,
        warnings=result.warnings,
        failed_models=result.failed_models,
        history=history,
        agreement=agreement.to_dict() if agreement else None,
        confidence=confidence,
    )

    # Run telemetry (obs/): collected BEFORE the secondary-controller
    # early return — the multihost timeline merge is a collective, so
    # every process must enter it; only process 0 persists the artifacts.
    # Persistence rides the auto-saved run dir, so runs that disable it
    # (--output / --json / --no-save) skip the merge SYMMETRICALLY (cfg
    # is identical on every controller — no process enters a collective
    # the others skip) and say so instead of discarding telemetry
    # silently.
    from llm_consensus_tpu import faults as faults_mod

    telemetry_persists = (
        not cfg.output and not cfg.json and not cfg.no_save
    )
    trace_doc = metrics_doc = None
    if recorder is not None and not telemetry_persists:
        result.warnings.append(
            "run telemetry recorded but not persisted: trace.json/"
            "metrics.json ride the auto-saved run directory, which "
            "--output, --json, and --no-save disable"
        )
    if recorder is not None and telemetry_persists:
        from llm_consensus_tpu.obs import export as obs_export

        # Snapshot BEFORE the timeline merge: metrics.json must report
        # the degradation the RUN saw. A timeout in the telemetry
        # exchange itself still lands in the module's degraded set (its
        # liveness semantics are uniform) but surfaces here only as
        # timeline_missing_controllers, never as phantom run degradation
        # next to a result.json where every model succeeded.
        degraded_run = mc.degraded_peers() if multictrl else None
        if multictrl and cfg.events:
            # Merge only under the --events FLAG: argv reaches every
            # controller identically (the same contract every other flag
            # rides), so all processes enter the collective together —
            # whereas an env-enabled recorder (LLMC_EVENTS on one host
            # only) must stay local, or the lone merging process would
            # block its full deadline and mark healthy peers degraded.
            from llm_consensus_tpu.obs.multihost import merge_timelines

            trace_doc, trace_missing = merge_timelines(
                recorder, mc.allgather_timeout(ctx)
            )
        else:
            trace_doc, trace_missing = obs_export.local_trace(recorder), []
        batcher_stats = obs_export.collect_batcher_stats(registry)
        plan = faults_mod.plan()
        metrics_doc = obs_export.metrics_summary(
            recorder,
            responses=result.responses,
            batcher_stats=batcher_stats,
            kv_stats=obs_export.collect_kv_stats(registry),
            spec_stats=obs_export.collect_spec_stats(registry),
            disagg_stats=obs_export.collect_disagg_stats(registry),
            fault_trace=list(plan.trace) if plan is not None else None,
            degraded_peers=degraded_run,
            failed_models=result.failed_models,
            warnings=result.warnings,
            live=obs_export.live_summary(),
            attrib=obs_export.attrib_summary(),
        )
        if trace_missing:
            metrics_doc["timeline_missing_controllers"] = sorted(
                trace_missing
            )
    elif telemetry_persists:
        # CLI parity with the serve-mode /metricsz scrape: even without
        # --events, a one-shot run whose live plane OR attribution
        # ledger observed anything (tpu engines record per-token latency
        # and device time by default; LLMC_ATTRIB=1 keeps the ledger on
        # with live histograms off) persists the final per-family
        # histogram quantiles and the chip-time attribution snapshot
        # into metrics.json, so the numbers a scrape would have shown
        # don't evaporate at process exit.
        from llm_consensus_tpu.obs import export as obs_export

        _lp = obs_mod.live.metrics()
        live_doc = (
            obs_export.live_summary(_lp)
            if _lp is not None and _lp.counts() > live_counts0 else None
        )
        _led = obs_mod.attrib.ledger()
        attrib_grew = (
            _led is not None and _led.activity() > attrib_counts0
        )
        if live_doc or attrib_grew:
            metrics_doc = obs_export.metrics_summary(
                responses=result.responses,
                failed_models=result.failed_models,
                warnings=result.warnings,
                live=live_doc,
                attrib=obs_export.attrib_summary(),
            )

    if multictrl and mc.process_index() != 0:
        # Secondary controllers hold the identical merged result but own
        # no output: process 0 persists and prints exactly once.
        return out

    # Output routing (main.go:187-273): --output file, else the run dir
    # reserved BEFORE the fan-out (which routes result.json through the
    # same file-write branch), else --json stdout, else pretty TTY, else
    # JSON stdout.
    output_path = ""
    if cfg.output:
        output_path = cfg.output
    elif run_dir:
        try:
            output_path = save_aux_files(
                run_dir,
                cfg.prompt,
                consensus,
                warn=(lambda msg: ui.print_error(stderr, msg)) if show_ui else None,
            )
        except OSError as err:
            raise CLIError(f"creating run directory: {err}") from err

    if run_dir:
        # Telemetry artifacts live next to result.json in the run dir
        # (non-fatal writes, like the other aux files): trace.json +
        # metrics.json when events are on, and the exact injected fault
        # sequence whenever a fault plan drove this run.
        from llm_consensus_tpu.output.persist import save_file

        warn = (lambda msg: ui.print_error(stderr, msg)) if show_ui else None
        plan = faults_mod.plan()
        if plan is not None:
            save_file(run_dir, "faults.txt", plan.trace_bytes(), warn=warn)
        if trace_doc is not None:
            from llm_consensus_tpu.obs.export import save_run_telemetry

            save_run_telemetry(run_dir, trace_doc, metrics_doc, warn=warn)
        elif metrics_doc is not None:
            # Live-plane-only telemetry (no --events recorder): just
            # metrics.json — there is no event timeline to trace.
            import json as _json

            from llm_consensus_tpu.obs.export import METRICS_FILE

            save_file(
                run_dir, METRICS_FILE,
                _json.dumps(metrics_doc, indent=2) + "\n", warn=warn,
            )

    if output_path:
        # Atomic like every other run artifact: result.json's mere
        # EXISTENCE is the completion sentinel --resume keys on, so a
        # torn write would brick both --resume and --continue for the
        # run.
        from llm_consensus_tpu.output.persist import save_file as _sf

        _errs: list[str] = []
        written = _sf(
            os.path.dirname(output_path) or ".",
            os.path.basename(output_path),
            out.to_json(),
            warn=_errs.append,
        )
        if written is None:
            raise CLIError(
                "creating output file: "
                + (_errs[0] if _errs else output_path)
            )
        if show_ui:
            stderr.write("\n")
            ui.print_success(stderr, f"Run saved to {os.path.dirname(output_path) or '.'}")
    elif cfg.json:
        stdout.write(out.to_json())
    elif show_ui:
        stderr.write("\n")
        for resp in result.responses:
            ui.print_model_response(stderr, resp.model, resp.provider, resp.content, resp.latency_ms)
        ui.print_consensus(stderr, consensus)
        ui.print_summary(
            stderr,
            len(cfg.models),
            len(result.responses),
            len(result.failed_models),
            time.monotonic() - start_time,
        )
        ui.print_throughput(stderr, result.responses)
        if recorder is not None:
            from llm_consensus_tpu.obs.export import aggregate_throughput

            ui.print_aggregate(stderr, aggregate_throughput(recorder))
        if result.warnings:
            stderr.write("\n")
            for w in result.warnings:
                ui.print_error(stderr, w)
    else:
        stdout.write(out.to_json())
    return out


def interactive_loop(
    cfg: Config,
    ctx: Context,
    *,
    factory: ProviderFactory,
    stdin: TextIO,
    stdout: TextIO,
    stderr: TextIO,
) -> None:
    """REPL over warm providers (reference roadmap §7.2).

    Each line is a consensus query; the conversation accumulates across
    queries (same folding as --continue), and engines/compiled programs
    stay warm between them — the prefix cache makes follow-ups pay only
    for new tokens. Slash commands:

      /models            show the panel
      /models +m / -m    add / remove a model
      /judge m           change the judge
      /reset             clear the conversation history
      /exit, /quit       leave
    """
    tty = ui.is_terminal(stderr)
    history: list[dict] = []
    if cfg.continue_run:
        history = load_history(cfg.data_dir, cfg.continue_run)
    if tty:
        stderr.write(
            "Interactive mode: type a prompt, /models [+m|-m], /judge m, "
            "/reset, /exit\n"
        )

    # While idle at the prompt, a plain ctx.cancel() can't unblock
    # readline (Python retries it after EINTR, PEP 475) — so for the
    # REPL's lifetime SIGINT also raises KeyboardInterrupt, which aborts
    # the blocking read and exits the session promptly.
    prev_handler = None
    try:
        def _sigint(*_):
            ctx.cancel()
            raise KeyboardInterrupt

        prev_handler = signal.signal(signal.SIGINT, _sigint)
    except ValueError:
        prev_handler = None  # not the main thread (tests)

    try:
        while True:
            if ctx.done():
                return
            if tty:
                stderr.write("> ")
                stderr.flush()
            line = stdin.readline()
            if not line or ctx.done():
                return  # EOF or cancelled while blocked
            line = line.strip()
            if not line:
                continue
            cmd = line.split()[0]
            if cmd in ("/exit", "/quit"):
                return
            if cmd == "/reset":
                history = []
                if tty:
                    stderr.write("conversation cleared\n")
                continue
            if cmd == "/judge":
                parts = line.split()
                if len(parts) == 2:
                    cfg.judge = parts[1]
                stderr.write(f"judge: {cfg.judge}\n")
                continue
            if cmd == "/models":
                for tok in line.split()[1:]:
                    if tok.startswith("+"):
                        if tok[1:] and tok[1:] not in cfg.models:
                            cfg.models.append(tok[1:])
                    elif tok.startswith("-"):
                        if tok[1:] in cfg.models:
                            if len(cfg.models) == 1:
                                stderr.write(
                                    "cannot remove the last panel model\n"
                                )
                            else:
                                cfg.models.remove(tok[1:])
                stderr.write(f"models: {','.join(cfg.models)}\n")
                continue
            if cmd.startswith("/"):
                stderr.write(f"unknown command {cmd!r}\n")
                continue

            query_cfg = dataclasses_replace(cfg, prompt=line, continue_run="")
            try:
                out = _run(
                    query_cfg, ctx,
                    factory=factory, stdout=stdout, stderr=stderr,
                    history=list(history),
                )
            except CLIError as err:
                # One failed query must not end the session.
                stderr.write(f"error: {err}\n")
                continue
            history.append({"prompt": line, "consensus": out.consensus})
    except KeyboardInterrupt:
        return
    finally:
        if prev_handler is not None:
            try:
                signal.signal(signal.SIGINT, prev_handler)
            except ValueError:
                pass


def main(
    argv: Optional[list[str]] = None,
    *,
    factory: ProviderFactory = create_provider,
    stdin: Optional[TextIO] = None,
    stdout: Optional[TextIO] = None,
    stderr: Optional[TextIO] = None,
    install_signal_handlers: bool = True,
) -> int:
    argv = sys.argv[1:] if argv is None else argv
    stdin = sys.stdin if stdin is None else stdin
    stdout = sys.stdout if stdout is None else stdout
    stderr = sys.stderr if stderr is None else stderr

    if argv and argv[0] in ("serve", "route", "distill"):
        # Resident services: the serving gateway (cli/serve.py) and the
        # fleet router (cli/route.py) — own flag sets, own signal
        # handling (SIGTERM = graceful drain, not context cancel).
        # ``distill`` (cli/distill.py) is the flywheel's offline half:
        # journal → corpus → distilled checkpoint, one JSON summary.
        if argv[0] == "serve":
            from llm_consensus_tpu.cli.serve import serve_main as sub_main
        elif argv[0] == "distill":
            from llm_consensus_tpu.cli.distill import (
                distill_main as sub_main,
            )
        else:
            from llm_consensus_tpu.cli.route import route_main as sub_main

        try:
            return sub_main(
                argv[1:], stdout=stdout, stderr=stderr,
                install_signal_handlers=install_signal_handlers,
            )
        except CLIError as err:
            stderr.write(f"error: {err}\n")
            return 1
        except SystemExit as err:  # argparse --help / parse errors
            return int(err.code or 0)

    ctx = Context.background().with_cancel()
    if install_signal_handlers:
        # SIGINT/SIGTERM → graceful context cancel (main.go:90-91).
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                signal.signal(sig, lambda *_: ctx.cancel())
            except ValueError:
                break  # not the main thread (e.g. under a test runner)

    try:
        cfg = parse_args(argv, stdin, stdout)
        if cfg is None:
            return 0
        run(cfg, ctx, factory=factory, stdout=stdout, stderr=stderr, stdin=stdin)
    except CLIError as err:
        stderr.write(f"error: {err}\n")
        return 1
    except SystemExit as err:  # argparse --help / parse errors
        return int(err.code or 0)
    finally:
        ctx.close()
    return 0
