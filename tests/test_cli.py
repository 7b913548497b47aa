"""CLI tests — flags, prompt precedence, output routing, run persistence.

Coverage the reference lacks entirely (SURVEY.md §4 lesson): golden tests of
cmd/llm-consensus/main.go behaviors through an injected provider factory.
"""

import io
import json
import os

import pytest

from llm_consensus_tpu.cli.main import (
    CLIError,
    create_provider,
    get_prompt,
    main,
)
from llm_consensus_tpu.providers import ProviderFunc, Response


def echo_factory(model: str):
    if model.startswith("bad"):
        def fail(ctx, req):
            raise RuntimeError("provider down")
        return ProviderFunc(fail)
    return ProviderFunc(
        lambda ctx, req: Response(req.model, f"echo({req.prompt[:20]})", "fake", 1.0)
    )


def run_cli(argv, stdin_text="", factory=echo_factory):
    stdin = io.StringIO(stdin_text)
    stdout, stderr = io.StringIO(), io.StringIO()
    code = main(
        argv,
        factory=factory,
        stdin=stdin,
        stdout=stdout,
        stderr=stderr,
        install_signal_handlers=False,
    )
    return code, stdout.getvalue(), stderr.getvalue()


def test_version_flag():
    code, out, _ = run_cli(["--version"])
    assert code == 0
    assert out.startswith("llm-consensus 0.")
    assert "commit:" in out and "built:" in out


def test_models_flag_required():
    code, _, err = run_cli(["hello"])
    assert code == 1
    assert "error: --models flag is required" in err


def test_empty_piped_stdin_accepted():
    # StringIO stdin is not a char device → the piped-stdin branch runs;
    # empty piped input is an empty prompt and the run proceeds (parity:
    # the reference reads zero lines from an empty pipe).
    code, _, err = run_cli(["--models", "m1,m2", "--no-save"], stdin_text="")
    assert code == 0


def test_no_prompt_error_when_stdin_is_tty(monkeypatch):
    # With a TTY stdin and no arg/--file, the CLI must error (main.go:392).
    import importlib

    cli_main = importlib.import_module("llm_consensus_tpu.cli.main")
    monkeypatch.setattr(cli_main.ui, "is_terminal", lambda f: True)
    code, _, err = run_cli(["--models", "m1,m2"])
    assert code == 1
    assert "error: no prompt provided: use positional argument, --file, or pipe to stdin" in err


def test_json_output_to_stdout():
    code, out, err = run_cli(["--models", "m1,m2", "--judge", "j", "--json", "what is up"])
    assert code == 0
    d = json.loads(out)
    assert d["prompt"] == "what is up"
    assert d["judge"] == "j"
    assert len(d["responses"]) == 2
    assert d["consensus"].startswith("echo(")
    assert "warnings" not in d


def test_positional_args_joined():
    code, out, _ = run_cli(["--models", "m1", "--judge", "j", "--json", "a", "b", "c"])
    assert json.loads(out)["prompt"] == "a b c"


def test_prompt_from_file(tmp_path):
    f = tmp_path / "prompt.txt"
    f.write_text("  file prompt\n")
    code, out, _ = run_cli(["--models", "m1", "--judge", "j", "--json", "--file", str(f)])
    assert json.loads(out)["prompt"] == "file prompt"


def test_prompt_from_stdin():
    code, out, _ = run_cli(
        ["--models", "m1", "--judge", "j", "--json"], stdin_text="line1\nline2\n"
    )
    assert json.loads(out)["prompt"] == "line1\nline2"


def test_positional_beats_file(tmp_path):
    f = tmp_path / "p.txt"
    f.write_text("from file")
    code, out, _ = run_cli(
        ["--models", "m1", "--judge", "j", "--json", "--file", str(f), "from", "arg"]
    )
    assert json.loads(out)["prompt"] == "from arg"


def test_missing_prompt_file_error():
    code, _, err = run_cli(["--models", "m1", "--file", "/nonexistent/x.txt"])
    assert code == 1
    assert "error: reading prompt file" in err


def test_partial_failure_reported_in_json():
    code, out, _ = run_cli(["--models", "m1,bad1", "--judge", "j", "--json", "q"])
    assert code == 0
    d = json.loads(out)
    assert d["failed_models"] == ["bad1"]
    assert len(d["responses"]) == 1
    assert "bad1" in d["warnings"][0]


def test_all_models_fail_exits_1():
    code, _, err = run_cli(["--models", "bad1,bad2", "--judge", "j", "--json", "q"])
    assert code == 1
    assert "error: running queries" in err


def test_single_model_judge_passthrough():
    # Single response → judge passthrough (judge.go:74-79): consensus equals
    # the sole model answer even though the judge provider would fail.
    def factory(model):
        if model == "j":
            def fail(ctx, req):
                raise RuntimeError("judge must not be called")
            return ProviderFunc(fail)
        return echo_factory(model)

    code, out, _ = run_cli(["--models", "m1", "--judge", "j", "--json", "q"], factory=factory)
    assert code == 0
    d = json.loads(out)
    assert d["consensus"] == d["responses"][0]["content"]


def test_output_file_routing(tmp_path):
    path = tmp_path / "out.json"
    code, out, _ = run_cli(
        ["--models", "m1", "--judge", "j", "--output", str(path), "--no-save", "q"]
    )
    assert code == 0
    assert out == ""  # JSON went to the file, not stdout
    d = json.loads(path.read_text())
    assert d["judge"] == "j"


def test_auto_save_run_dir(tmp_path):
    data_dir = str(tmp_path / "data")
    code, out, _ = run_cli(
        ["--models", "m1,m2", "--judge", "j", "--data-dir", data_dir, "the question"]
    )
    assert code == 0
    runs = os.listdir(data_dir)
    assert len(runs) == 1
    run_dir = os.path.join(data_dir, runs[0])
    files = sorted(os.listdir(run_dir))
    # run.json (the resume manifest) and panel/ (per-model answer
    # journal) are written BEFORE the fan-out so a crashed run is
    # resumable; the classic artifacts land on success as before.
    assert files == [
        "consensus.md", "panel", "prompt.txt", "result.json", "run.json"
    ]
    panel = sorted(os.listdir(os.path.join(run_dir, "panel")))
    assert len(panel) == 2 and all(p.endswith(".json") for p in panel)
    assert open(os.path.join(run_dir, "prompt.txt")).read() == "the question"
    d = json.load(open(os.path.join(run_dir, "result.json")))
    assert d["prompt"] == "the question"
    # run-id format: YYYYmmdd-HHMMSS-xxxxxx (main.go:278-285)
    stem = runs[0]
    parts = stem.split("-")
    assert len(parts) == 3 and len(parts[0]) == 8 and len(parts[1]) == 6 and len(parts[2]) == 6


def test_json_flag_disables_auto_save(tmp_path):
    data_dir = str(tmp_path / "data")
    code, out, _ = run_cli(
        ["--models", "m1", "--judge", "j", "--json", "--data-dir", data_dir, "q"]
    )
    assert code == 0
    assert not os.path.exists(data_dir)


def test_no_save_flag(tmp_path):
    data_dir = str(tmp_path / "data")
    code, out, _ = run_cli(
        ["--models", "m1", "--judge", "j", "--no-save", "--data-dir", data_dir, "q"]
    )
    assert code == 0
    assert not os.path.exists(data_dir)
    json.loads(out)  # non-TTY stdout falls back to JSON


def test_unknown_model_lists_available():
    code, _, err = run_cli(["--models", "not-a-model", "q"], factory=create_provider)
    assert code == 1
    assert "error: unknown model 'not-a-model'" in err
    assert "tpu:<model>" in err


def test_judge_auto_added_to_registry():
    seen = []

    def factory(model):
        seen.append(model)
        return echo_factory(model)

    run_cli(["--models", "m1,m2", "--judge", "the-judge", "--json", "q"], factory=factory)
    assert "the-judge" in seen


def test_judge_not_duplicated_when_in_panel():
    seen = []

    def factory(model):
        seen.append(model)
        return echo_factory(model)

    run_cli(["--models", "m1,j", "--judge", "j", "--json", "q"], factory=factory)
    assert seen.count("j") == 1


def test_timeout_flag_parsed():
    # timeout is int seconds (main.go:317)
    code, out, _ = run_cli(["--models", "m1", "--judge", "j", "--json", "--timeout", "7", "q"])
    assert code == 0


def test_go_style_single_dash_flags():
    code, out, _ = run_cli(["-models", "m1", "-judge", "j", "-json", "q"])
    assert code == 0
    assert json.loads(out)["judge"] == "j"


# -- --continue (conversation history) ---------------------------------------


def test_continue_folds_history_into_prompts(tmp_path):
    """--continue loads the saved run, panel+judge see the conversation,
    and the new result records the accumulated history."""
    seen_prompts = []

    def factory(model):
        def fn(ctx, req):
            seen_prompts.append((model, req.prompt))
            return Response(req.model, f"ans-{model}", "fake", 1.0)
        return ProviderFunc(fn)

    data_dir = str(tmp_path / "data")
    # First run, auto-saved.
    code, _, err = run_cli(
        ["--models", "m1,m2", "--judge", "j", "--data-dir", data_dir,
         "--quiet", "first question"],
        factory=factory,
    )
    assert code == 0, err
    run_id = os.listdir(data_dir)[0]

    seen_prompts.clear()
    code, out, err = run_cli(
        ["--models", "m1,m2", "--judge", "j", "--data-dir", data_dir,
         "--continue", run_id, "--json", "follow up"],
        factory=factory,
    )
    assert code == 0, err
    data = json.loads(out)
    # Raw follow-up is the recorded prompt; history carries the exchange.
    assert data["prompt"] == "follow up"
    assert data["history"] == [
        {"prompt": "first question", "consensus": "ans-j"}
    ]
    # Panel and judge both saw the folded conversation.
    for model, prompt in seen_prompts:
        assert "first question" in prompt
        assert "ans-j" in prompt
        assert "follow up" in prompt


def test_continue_chains_history(tmp_path):
    """A continued run's save can itself be continued; history accumulates
    oldest-first."""
    data_dir = str(tmp_path / "data")
    code, _, _ = run_cli(
        ["--models", "m1", "--data-dir", data_dir, "--quiet", "q1"])
    assert code == 0
    first = os.listdir(data_dir)[0]
    code, _, _ = run_cli(
        ["--models", "m1", "--data-dir", data_dir, "--continue", first,
         "--quiet", "q2"])
    assert code == 0
    second = next(d for d in os.listdir(data_dir) if d != first)
    code, out, _ = run_cli(
        ["--models", "m1", "--data-dir", data_dir, "--continue", second,
         "--json", "q3"])
    assert code == 0
    hist = json.loads(out)["history"]
    assert [h["prompt"] for h in hist] == ["q1", "q2"]


def test_continue_unknown_run_errors(tmp_path):
    code, _, err = run_cli(
        ["--models", "m1", "--data-dir", str(tmp_path), "--continue",
         "nope", "q"])
    assert code == 1
    assert "loading run 'nope'" in err


# -- --system ----------------------------------------------------------------


def test_system_prompt_reaches_panel_not_judge():
    """--system flows to every panel request; the judge keeps its own role
    prompt (reference roadmap §3.2)."""
    seen = {}

    def factory(model):
        def fn(ctx, req):
            seen[model] = req.system
            return Response(req.model, "ans", "fake", 1.0)
        return ProviderFunc(fn)

    code, _, err = run_cli(
        ["--models", "m1,m2", "--judge", "j", "--system", "be terse",
         "--json", "q"],
        factory=factory,
    )
    assert code == 0, err
    assert seen["m1"] == "be terse" and seen["m2"] == "be terse"
    assert seen["j"] is None


def test_system_file(tmp_path):
    p = tmp_path / "sys.txt"
    p.write_text("from file\n")
    seen = {}

    def factory(model):
        def fn(ctx, req):
            seen[model] = req.system
            return Response(req.model, "ans", "fake", 1.0)
        return ProviderFunc(fn)

    code, _, _ = run_cli(
        ["--models", "m1", "--system-file", str(p), "--json", "q"],
        factory=factory,
    )
    assert code == 0
    assert seen["m1"] == "from file"


def test_system_and_system_file_exclusive(tmp_path):
    p = tmp_path / "sys.txt"
    p.write_text("x")
    code, _, err = run_cli(
        ["--models", "m1", "--system", "a", "--system-file", str(p), "q"])
    assert code == 1 and "mutually exclusive" in err


# -- config file + aliases ---------------------------------------------------


def test_config_file_defaults_and_aliases(tmp_path, monkeypatch):
    """Config supplies flag defaults and @aliases; CLI flags win."""
    cfgp = tmp_path / "cfg.json"
    cfgp.write_text(json.dumps({
        "models": "@panel",
        "judge": "j-from-config",
        "timeout": 7,
        "aliases": {"@panel": "m1, m2", "@solo": "m9"},
    }))
    monkeypatch.setenv("LLMC_CONFIG", str(cfgp))

    seen = []

    def factory(model):
        seen.append(model)
        return ProviderFunc(
            lambda ctx, req: Response(req.model, "ans", "fake", 1.0))

    # No --models flag: the config default (alias-expanded) applies.
    code, out, err = run_cli(["--json", "q"], factory=factory)
    assert code == 0, err
    data = json.loads(out)
    assert [r["model"] for r in data["responses"]] == ["m1", "m2"]
    assert data["judge"] == "j-from-config"

    # Explicit flags beat the config.
    seen.clear()
    code, out, _ = run_cli(
        ["--models", "@solo", "--judge", "j2", "--json", "q"], factory=factory)
    assert code == 0
    data = json.loads(out)
    assert [r["model"] for r in data["responses"]] == ["m9"]
    assert data["judge"] == "j2"


def test_unknown_alias_errors(tmp_path, monkeypatch):
    cfgp = tmp_path / "cfg.json"
    cfgp.write_text(json.dumps({"aliases": {"@a": "m1"}}))
    monkeypatch.setenv("LLMC_CONFIG", str(cfgp))
    code, _, err = run_cli(["--models", "@nope", "q"])
    assert code == 1 and "unknown model alias '@nope'" in err


def test_config_unknown_key_errors(tmp_path, monkeypatch):
    cfgp = tmp_path / "cfg.json"
    cfgp.write_text(json.dumps({"modles": "typo"}))
    monkeypatch.setenv("LLMC_CONFIG", str(cfgp))
    code, _, err = run_cli(["--models", "m1", "q"])
    assert code == 1 and "unknown keys" in err


def test_config_disabled_by_env(tmp_path, monkeypatch):
    cfgp = tmp_path / "cfg.json"
    cfgp.write_text("{not json")
    monkeypatch.setenv("LLMC_CONFIG", "0")
    code, _, err = run_cli(["--models", "m1", "--json", "q"])
    assert code == 0  # broken file never read


def test_alias_overlap_preserves_duplicates(tmp_path, monkeypatch):
    """Explicit duplicates have always meant two queries (reference
    semantics); alias overlap follows the same rule."""
    cfgp = tmp_path / "cfg.json"
    cfgp.write_text(json.dumps({"aliases": {"@a": "m1,m2", "@b": "m2,m3"}}))
    monkeypatch.setenv("LLMC_CONFIG", str(cfgp))
    code, out, _ = run_cli(["--models", "@a,@b", "--json", "q"])
    assert code == 0
    assert [r["model"] for r in json.loads(out)["responses"]] == [
        "m1", "m2", "m2", "m3"
    ]


def test_config_wrong_types_rejected(tmp_path, monkeypatch):
    cfgp = tmp_path / "cfg.json"
    cfgp.write_text(json.dumps({"rounds": "2"}))
    monkeypatch.setenv("LLMC_CONFIG", str(cfgp))
    code, _, err = run_cli(["--models", "m1", "q"])
    assert code == 1 and "'rounds' must be an integer" in err

    cfgp.write_text(json.dumps({"aliases": ["@a"]}))
    code, _, err = run_cli(["--models", "m1", "q"])
    assert code == 1 and "'aliases' must map" in err


def test_explicit_missing_config_path_errors(monkeypatch):
    monkeypatch.setenv("LLMC_CONFIG", "/nonexistent/typo.json")
    code, _, err = run_cli(["--models", "m1", "q"])
    assert code == 1 and "missing file" in err


def test_version_works_with_broken_config(tmp_path, monkeypatch):
    cfgp = tmp_path / "cfg.json"
    cfgp.write_text("{broken")
    monkeypatch.setenv("LLMC_CONFIG", str(cfgp))
    code, out, _ = run_cli(["--version"])
    assert code == 0 and out.startswith("llm-consensus")


# -- interactive mode --------------------------------------------------------


def test_interactive_queries_and_history(tmp_path):
    """Each line is a consensus query; the conversation folds into later
    queries; slash commands mutate the session."""
    seen = []

    def factory(model):
        def fn(ctx, req):
            seen.append((model, req.prompt))
            return Response(req.model, f"ans-{model}", "fake", 1.0)
        return ProviderFunc(fn)

    script = "\n".join([
        "first question",
        "/models +m2",
        "second question",
        "/reset",
        "/models -m2",
        "third question",
        "/exit",
        "never reached",
    ]) + "\n"
    code, out, err = run_cli(
        ["--models", "m1", "--judge", "j", "--interactive", "--no-save",
         "--quiet"],
        stdin_text=script, factory=factory,
    )
    assert code == 0, err
    # Query 1: m1 only, no history.
    q1 = [p for m, p in seen if m == "m1" and "first question" in p]
    assert q1 and "Earlier exchanges" not in q1[0]
    # Query 2: m1 AND m2, history folded in (query 1's consensus is the
    # single-response passthrough, i.e. ans-m1).
    q2 = [p for m, p in seen if m == "m2"]
    assert q2 and "first question" in q2[0] and "ans-m1" in q2[0]
    # Query 3 (after /reset and /models -m2): m1 only, no history.
    q3 = [p for m, p in seen if m == "m1" and "third question" in p]
    assert q3 and "Earlier exchanges" not in q3[0]
    assert not any(m == "m2" and "third" in p for m, p in seen)
    assert "never reached" not in " ".join(p for _, p in seen)


def test_interactive_query_error_keeps_session(tmp_path):
    """A failing query prints an error and the REPL continues."""
    def factory(model):
        def fn(ctx, req):
            if "boom" in req.prompt:
                raise RuntimeError("provider exploded")
            return Response(req.model, "ok", "fake", 1.0)
        return ProviderFunc(fn)

    code, out, err = run_cli(
        ["--models", "m1", "--judge", "m1", "--interactive", "--no-save",
         "--quiet"],
        stdin_text="boom\nworks\n", factory=factory,
    )
    assert code == 0
    assert "error:" in err
    # Second query still ran (non-TTY stdout → JSON line).
    assert '"consensus": "ok"' in out


def test_interactive_rejects_positional_prompt():
    code, _, err = run_cli(["--models", "m1", "--interactive", "hello"])
    assert code == 1 and "stdin" in err


def test_interactive_typod_command_rejected():
    code, out, err = run_cli(
        ["--models", "m1", "--interactive", "--no-save", "--quiet"],
        stdin_text="/judges j2\n/modelsx +m2\n/exit\n",
    )
    assert code == 0
    assert "unknown command '/judges'" in err
    assert "unknown command '/modelsx'" in err


def test_interactive_keeps_last_model():
    code, out, err = run_cli(
        ["--models", "m1", "--interactive", "--no-save", "--quiet"],
        stdin_text="/models -m1\n/exit\n",
    )
    assert code == 0
    assert "cannot remove the last panel model" in err
    assert "models: m1" in err


def test_interactive_rejects_output_and_file(tmp_path):
    code, _, err = run_cli(
        ["--models", "m1", "--interactive", "--output", "x.json"])
    assert code == 1 and "incompatible" in err
    p = tmp_path / "f.txt"
    p.write_text("x")
    code, _, err = run_cli(
        ["--models", "m1", "--interactive", "--file", str(p)])
    assert code == 1 and "stdin" in err


def test_sigint_cancels_run_gracefully():
    """Checklist item main.go:90-91: SIGINT → context cancel → the run
    winds down cooperatively (failed models, exit 1) instead of dying on
    a traceback."""
    import signal
    import threading

    def factory(model):
        def fn(ctx, req):
            ctx.sleep(10)  # cooperative: wakes on cancel
            ctx.raise_if_done()
            return Response(req.model, "never", "fake", 1.0)
        return ProviderFunc(fn)

    # Process-directed delivery (like a real Ctrl-C): the kernel hands the
    # signal to the main thread, interrupting its join so the handler runs
    # promptly. raise_signal from the timer thread would deliver to the
    # timer thread and the handler would wait for the join to finish.
    timer = threading.Timer(
        0.2, lambda: os.kill(os.getpid(), signal.SIGINT)
    )
    timer.start()
    stdin, stdout, stderr = io.StringIO(), io.StringIO(), io.StringIO()
    t0 = __import__("time").monotonic()
    code = main(
        ["--models", "m1,m2", "--judge", "j", "--json", "q"],
        factory=factory, stdin=stdin, stdout=stdout, stderr=stderr,
        install_signal_handlers=True,
    )
    timer.cancel()
    assert code == 1
    assert "error: running queries" in stderr.getvalue()
    assert __import__("time").monotonic() - t0 < 5  # not the 10s sleep


# ---------------------------------------------------------------------------
# the `serve` subcommand (cli/serve.py)


def test_serve_requires_models():
    stdout, stderr = io.StringIO(), io.StringIO()
    code = main(["serve"], stdout=stdout, stderr=stderr,
                install_signal_handlers=False)
    assert code == 1
    assert "error: --models flag is required" in stderr.getvalue()


def test_serve_flag_validation():
    from llm_consensus_tpu.cli.serve import parse_serve_args

    with pytest.raises(CLIError, match="--max-batch"):
        parse_serve_args(["--models", "m1", "--max-batch", "0"])
    with pytest.raises(CLIError, match="--max-concurrency"):
        parse_serve_args(["--models", "m1", "--max-concurrency", "0"])
    with pytest.raises(CLIError, match="--queue-depth"):
        parse_serve_args(["--models", "m1", "--queue-depth", "-1"])
    cfg = parse_serve_args(["--models", "m1,m2", "--max-batch", "16"])
    assert cfg.models == ["m1", "m2"]
    assert cfg.max_batch == 16


def test_serve_max_batch_env_alias(monkeypatch):
    from llm_consensus_tpu.cli.serve import parse_serve_args

    monkeypatch.setenv("LLMC_MAX_BATCH", "12")
    cfg = parse_serve_args(["--models", "m1"])
    assert cfg.max_batch == 12
    # The flag wins over the env.
    cfg = parse_serve_args(["--models", "m1", "--max-batch", "3"])
    assert cfg.max_batch == 3


_LLAMA, _GEMMA = "tpu:tiny-llama", "tpu:tiny-gemma"

# (panel, judge, further flags, LLMC_JUDGE_OVERLAP, the cap or the error)
_CAP_CASES = {
    # one stream per preset per run, the judge on a preset of its own
    "distinct-presets": (f"{_LLAMA},{_GEMMA}", "tpu:tiny-mistral",
                         ["--max-batch", "8"], None, 8),
    # the same preset twice in the panel doubles its per-run streams
    "preset-twice": (f"{_LLAMA},{_LLAMA}", _GEMMA,
                     ["--max-batch", "8"], None, 4),
    # ISSUE 26: the judge runs after the panel, in the row its panel
    # answer gave back, so a judge that is a panelist costs no row
    "judge-a-panelist": (f"{_LLAMA},{_GEMMA}", _LLAMA,
                         ["--max-batch", "8"], None, 8),
    "judge-a-panelist-six-rows": (f"{_LLAMA},{_GEMMA}", _LLAMA,
                                  ["--max-batch", "6"], None, 6),
    "judge-the-preset-asked-twice": (f"{_LLAMA},{_LLAMA}", _LLAMA,
                                     ["--max-batch", "8"], None, 4),
    # ... unless judge overlap opens its session while the panel decodes
    "judge-a-panelist-overlap-flag": (
        f"{_LLAMA},{_GEMMA}", _LLAMA,
        ["--max-batch", "8", "--judge-overlap"], None, 4),
    "judge-a-panelist-overlap-env": (f"{_LLAMA},{_GEMMA}", _LLAMA,
                                     ["--max-batch", "8"], "1", 4),
    "judge-a-panelist-overlap-env-off": (f"{_LLAMA},{_GEMMA}", _LLAMA,
                                         ["--max-batch", "8"], "0", 8),
    "judge-apart-overlap": (_LLAMA, _GEMMA,
                            ["--max-batch", "8", "--judge-overlap"], None, 8),
    "judge-the-preset-asked-twice-overlap": (
        f"{_LLAMA},{_LLAMA}", _LLAMA,
        ["--max-batch", "8", "--judge-overlap"], None, 2),
    # an explicit cap is held to the same arithmetic
    "explicit-cap-fills-the-pool": (
        f"{_LLAMA},{_GEMMA}", _LLAMA,
        ["--max-batch", "6", "--max-concurrency", "6"], None, 6),
    "explicit-cap-refused-with-overlap-flag": (
        f"{_LLAMA},{_GEMMA}", _LLAMA,
        ["--max-batch", "6", "--max-concurrency", "6", "--judge-overlap"],
        None, "needing 12 slots > --max-batch 6"),
    "explicit-cap-refused-with-overlap-env": (
        f"{_LLAMA},{_GEMMA}", _LLAMA,
        ["--max-batch", "6", "--max-concurrency", "6"], "1",
        "needing 12 slots > --max-batch 6"),
    "explicit-cap-half-with-overlap": (
        f"{_LLAMA},{_GEMMA}", _LLAMA,
        ["--max-batch", "6", "--max-concurrency", "3", "--judge-overlap"],
        None, 3),
    # an explicit cap that oversubscribes the batcher fails at startup
    "explicit-cap-oversubscribes": (
        _LLAMA, _GEMMA, ["--max-batch", "4", "--max-concurrency", "8"],
        None, "oversubscribes"),
    # HTTP-only panels have no device budget to validate against
    "http-only": ("m1,m2", "j",
                  ["--max-batch", "1", "--max-concurrency", "32"], None, 32),
    "http-only-default": ("m1,m2", "j", ["--max-batch", "1"], None, 8),
}


@pytest.mark.parametrize("case", list(_CAP_CASES))
def test_serve_concurrency_validated_against_max_batch(case, monkeypatch):
    """The admission cap is the number of runs whose streams fit a pool's
    rows, counted as the scheduler issues them: panel, then judge."""
    from llm_consensus_tpu.cli.serve import parse_serve_args, resolve_concurrency

    models, judge, flags, env, want = _CAP_CASES[case]
    monkeypatch.delenv("LLMC_JUDGE_OVERLAP", raising=False)
    if env is not None:
        monkeypatch.setenv("LLMC_JUDGE_OVERLAP", env)
    cfg = parse_serve_args(["--models", models, "--judge", judge] + flags)
    if isinstance(want, str):
        with pytest.raises(CLIError, match=want):
            resolve_concurrency(cfg)
    else:
        assert resolve_concurrency(cfg) == want


def _max_overlap(spans):
    """The most of ``spans`` ((start, end) pairs) open at one instant."""
    edges = sorted([(t0, 1) for t0, _ in spans] + [(t1, -1) for _, t1 in spans],
                   key=lambda e: (e[0], e[1]))
    live = peak = 0
    for _, d in edges:
        live += d
        peak = max(peak, live)
    return peak


def test_serve_seats_as_many_runs_as_a_pool_has_rows(tmp_path, monkeypatch):
    """ISSUE 26, the row arithmetic proved and not asserted: ``serve``
    with four rows a pool and a judge that is also a panelist admits
    four runs at once; all four complete, and no pool ever holds more
    streams (live or queued) than it has rows — a run's judge stream
    starts only after its panel stream on that pool has ended."""
    import http.client
    import re
    import threading
    import time

    from llm_consensus_tpu.cli.serve import serve_main
    from llm_consensus_tpu.obs import blackbox as bb_mod
    from llm_consensus_tpu.obs.blackbox import FlightRecorder

    monkeypatch.delenv("LLMC_JUDGE_OVERLAP", raising=False)
    ring = FlightRecorder(capacity=16384)
    bb_mod.install(ring)  # before the server: emitters bind at construction
    stderr, stop, rc = io.StringIO(), threading.Event(), []
    server = threading.Thread(target=lambda: rc.append(serve_main(
        ["--models", "tpu:tiny-llama,tpu:tiny-qwen2",
         "--judge", "tpu:tiny-llama", "--max-batch", "4", "--port", "0",
         "--max-tokens", "48", "--cache-size", "0", "--timeout", "300",
         "--data-dir", str(tmp_path / "data"), "--no-save"],
        stdout=io.StringIO(), stderr=stderr,
        install_signal_handlers=False, shutdown=stop,
    )))
    server.start()

    def call(method, path, body=None):
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        try:
            conn.request(method, path, body and json.dumps(body),
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            return r.status, json.loads(r.read())
        finally:
            conn.close()

    samples, docs, done = [], [None] * 4, threading.Event()
    try:
        deadline = time.monotonic() + 120
        while not (m := re.search(r"http://127\.0\.0\.1:(\d+)/", stderr.getvalue())):
            assert server.is_alive() and time.monotonic() < deadline, (
                stderr.getvalue())
            time.sleep(0.05)
        port = int(m.group(1))
        # one run alone first: every program the four meet is compiled
        # at some width, so the four overlap for most of their length
        assert call("POST", "/v1/consensus", {"prompt": "warm the pools"})[0] == 200
        t_four = time.monotonic_ns()

        def sample():
            while not done.is_set():
                samples.append(call("GET", "/statsz")[1])
                time.sleep(0.005)

        gate = threading.Barrier(4)

        def post(i):
            gate.wait()
            docs[i] = call("POST", "/v1/consensus",
                           {"prompt": f"question number {i}: why {i}?"})

        sampler = threading.Thread(target=sample)
        sampler.start()
        posts = [threading.Thread(target=post, args=(i,)) for i in range(4)]
        for t in posts:
            t.start()
        for t in posts:
            t.join(timeout=300)
        done.set()
        sampler.join(timeout=30)
        last = call("GET", "/statsz")[1]
    finally:
        done.set()
        stop.set()
        server.join(timeout=120)
        bb_mod.reset()
    assert rc == [0], stderr.getvalue()
    assert all(d is not None and d[0] == 200 for d in docs), docs
    for _, d in docs:
        assert len(d["responses"]) == 2 and d["consensus"], d
        assert not d.get("failed_models") and "degraded" not in d, d
    assert last["admission"]["max_concurrency"] == 4
    # the four were in flight together, so the pools were asked for
    # everything four runs ask at once
    events = [e for e in ring.snapshot() if e.ts_ns >= t_four]
    runs = [(e.ts_ns, e.ts_ns + e.dur_ns) for e in events
            if e.name == "consensus_run"]
    assert len(runs) == 4 and _max_overlap(runs) == 4, runs
    # ... and no pool was ever asked for a fifth row: by the streams'
    # own spans (exact), and by the governor's samples of the pools
    for model, n in (("tpu:tiny-llama", 8), ("tpu:tiny-qwen2", 4)):
        streams = [(e.ts_ns, e.ts_ns + e.dur_ns) for e in events
                   if e.name == "engine_stream" and e.args["model"] == model]
        assert len(streams) == n, (model, streams)
        assert _max_overlap(streams) <= 4, (model, streams)
    pools = [p for s in samples
             for p in s["pressure"].get("pools", {}).values()]
    assert pools and max(p["live"] for p in pools) >= 2
    assert all(p["cap"] == 4 and p["live"] + p["queued"] <= 4
               for p in pools), [p for p in pools
                                 if p["live"] + p["queued"] > 4]
    # full pools with nothing waiting are throughput: the ladder stays put
    assert {s["pressure"]["state"] for s in samples + [last]} == {"ok"}
    assert last["pressure"]["escalations"] == 0


def test_tpu_provider_reads_llmc_max_batch(monkeypatch):
    from llm_consensus_tpu.providers.tpu import TPUProvider

    monkeypatch.setenv("LLMC_MAX_BATCH", "5")
    assert TPUProvider().max_batch == 5
    monkeypatch.delenv("LLMC_MAX_BATCH")
    monkeypatch.setenv("LLMC_BATCH_STREAMS", "7")
    assert TPUProvider().max_batch == 7
    assert TPUProvider(batch_streams=3).max_batch == 3
