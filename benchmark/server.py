#!/usr/bin/env python3
"""The serving child: the program's own ``serve`` with one configuration.

Started by ``benchmark/run.py`` (which never imports JAX) as the one process
that holds the chips. It reads the configuration's file, and then calls the
program's normal entry, ``llm_consensus_tpu.cli.main(["serve", ...])`` — the
same code path as ``python -m llm_consensus_tpu serve``. Before that call it
does, in this process only, what a deployment would do with a checkpoint
directory and a launcher script, and nothing else:

  * sets the deployment's environment knobs from the file (``env``);
  * puts the file's models that are not presets into ``MODEL_PRESETS`` (a
    dict insert), and checks that those that are presets have the sizes the
    file states: the core fields every entry states and, under
    ``more_fields``, any other field of the program's ``ModelConfig``;
  * decodes every answer to exactly ``max_tokens`` through the provider's
    own constructor argument (``TPUProvider(ignore_eos=True)``; ``serve``
    has no switch for it) — random weights would otherwise end answers at
    random lengths;
  * folds generated ids onto 7-bit bytes in the byte tokenizer that
    random-weight models use, so that every generated token is visible text
    on the stream: the program's fold (``id % 256``) produces invalid UTF-8,
    which its ``StreamDecoder`` holds back until the answer ends, and the
    client then sees one chunk at the very end instead of a stream;
  * gives the profiler window that ``POST /debugz/profile`` arms the
    options that keep Python frames out of the trace (they are most of its
    bytes and of its cost, and no metric reads them).

Each of these reaches into the program by name, so each is checked when it
is made: a name that is gone, or a patch the program's own code does not go
through, stops the child before it serves (and the run prints no result).

On SIGUSR1 it runs the logits parity check (``benchmark/parity.py``) on the
engines it serves and writes ``parity.json`` into the work directory.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The core of the program's ModelConfig that every model's entry states. Any
# other field of it goes in the entry's "more_fields" object.
MODEL_FIELDS = (
    "family", "vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads",
    "head_dim", "d_ff", "rope_theta", "rms_eps", "qkv_bias",
    "sliding_window", "tie_embeddings", "max_seq_len",
)


def _frozen(value):
    """JSON lists as tuples, all the way down: ModelConfig is frozen and
    hashed (it is a static argument of the jitted programs)."""
    if isinstance(value, list):
        return tuple(_frozen(v) for v in value)
    return value


def model_config(name: str, spec: dict):
    """The program's ModelConfig for one entry of a file's ``models``: the
    core fields and ``more_fields`` together. A key of ``more_fields`` that
    is no field of the dataclass stops the child: a mistyped width is never
    dropped silently."""
    from dataclasses import fields

    from llm_consensus_tpu.models.config import ModelConfig

    more = spec.get("more_fields") or {}
    settable = {f.name for f in fields(ModelConfig)} - {"name", *MODEL_FIELDS}
    for key in more:
        if key not in settable:
            raise SystemExit(
                f"{name}: more_fields.{key} is "
                + ("a core field: state it beside the others" if key in MODEL_FIELDS
                   else f"no field of the program's ModelConfig; have {sorted(settable)}"))
    cfg = ModelConfig(
        name=name, **{k: spec[k] for k in MODEL_FIELDS},
        **{k: _frozen(v) for k, v in more.items()})
    hash(cfg)  # a value that cannot be hashed fails here, not in a jit
    return cfg


def install_models(models: dict) -> None:
    from dataclasses import asdict

    from llm_consensus_tpu.models.config import MODEL_PRESETS

    for name, spec in models.items():
        want = model_config(name, spec)
        if spec.get("preset"):
            have = MODEL_PRESETS.get(name)
            if have is None:
                raise SystemExit(f"{name}: the file says preset, the program has none")
            if have != want:
                diff = {
                    k: {"preset": v, "file": asdict(want)[k]}
                    for k, v in asdict(have).items() if v != asdict(want)[k]
                }
                outside = sorted(set(diff) - set(MODEL_FIELDS))
                raise SystemExit(
                    f"{name}: preset differs from the file: {diff}"
                    + (f"; state {outside} under more_fields" if outside else ""))
        elif name in MODEL_PRESETS:
            raise SystemExit(f"{name}: already a preset; say so in the file")
        else:
            MODEL_PRESETS[name] = want


def visible_bytes() -> None:
    from llm_consensus_tpu.engine.tokenizer import ByteTokenizer, StreamDecoder

    if not callable(getattr(ByteTokenizer, "_to_byte", None)):
        raise SystemExit("ByteTokenizer._to_byte is gone: the fold cannot be patched")

    def _to_byte(self, i: int):
        # The special ids too: with EOS ignored they are ordinary tokens of
        # an answer, and a dropped one would make it a token short.
        return i % 128 if i >= 0 else None

    ByteTokenizer._to_byte = _to_byte
    # The fold took effect only if the program's own decoders go through
    # it: one visible character per generated id, whole and streamed.
    tok = ByteTokenizer()
    ids = [65, 200, 255, 256, 257, 258, 1000, 151935]
    stream = StreamDecoder(tok)
    if len(tok.decode(ids)) != len(ids) or any(len(stream.push(i)) != 1 for i in ids):
        raise SystemExit(
            "the 7-bit fold did not take effect: a generated id is not one "
            "visible character on the stream")


_provider: list = []


def fixed_length_provider() -> None:
    import inspect

    from llm_consensus_tpu.providers import tpu

    if "ignore_eos" not in inspect.signature(tpu.TPUProvider.__init__).parameters:
        raise SystemExit("TPUProvider has no ignore_eos argument any more")

    class FixedLengthProvider(tpu.TPUProvider):
        def __init__(self, **kwargs):
            kwargs["ignore_eos"] = True
            super().__init__(**kwargs)
            _provider.append(self)

    tpu.TPUProvider = FixedLengthProvider


def lean_profiler() -> None:
    import jax

    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
    except Exception:  # noqa: BLE001 — an older profiler: default options
        return
    start = jax.profiler.start_trace

    def start_trace(log_dir, *args, **kwargs):
        kwargs.setdefault("profiler_options", options)
        return start(log_dir, *args, **kwargs)

    jax.profiler.start_trace = start_trace


def parity_on_signal(cfg: dict, workdir: str, seed: int) -> None:
    asked = threading.Event()
    signal.signal(signal.SIGUSR1, lambda *_: asked.set())

    def worker() -> None:
        asked.wait()
        try:
            from benchmark import parity

            if not _provider or not _provider[0]._ignore_eos:
                raise RuntimeError(
                    "serve did not build its provider through the patched "
                    "TPUProvider: answers are not of fixed length")
            doc = parity.check_all(_provider[0], cfg, seed)
        except Exception as err:  # noqa: BLE001 — reported, fails `correct`
            import traceback

            doc = {"ok": False, "error": f"{type(err).__name__}: {err}",
                   "traceback": traceback.format_exc()[-2000:]}
        tmp = os.path.join(workdir, "parity.json.tmp")
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, os.path.join(workdir, "parity.json"))

    threading.Thread(target=worker, name="parity", daemon=True).start()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--workdir", required=True)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    with open(args.config) as f:
        cfg = json.load(f)

    os.environ.update({k: str(v) for k, v in cfg.get("env", {}).items()})
    os.environ["LLMC_PROFILE_DIR"] = os.path.join(args.workdir, "profiles")
    os.environ["LLMC_PROFILE_MIN_INTERVAL_S"] = "0"
    sys.path.insert(0, REPO)

    install_models(cfg["models"])
    # A configuration the harness cannot compare stops here, before it serves.
    from benchmark import parity

    parity.stated(cfg)
    visible_bytes()
    fixed_length_provider()
    lean_profiler()
    parity_on_signal(cfg, args.workdir, args.seed)

    from llm_consensus_tpu.cli import main as program_main

    serve = cfg["serve"]
    argv = [
        "serve",
        "--models", ",".join(f"tpu:{m}" for m in cfg["panel"]),
        "--judge", f"tpu:{cfg['judge']}",
        "--port", str(args.port),
        "--max-batch", str(serve["max_batch"]),
        "--timeout", str(serve["timeout_s"]),
        "--queue-depth", str(serve["queue_depth"]),
        "--cache-size", str(serve["cache_size"]),
        "--data-dir", os.path.join(args.workdir, "data"),
        "--blackbox-dir", os.path.join(args.workdir, "blackbox"),
    ]
    return program_main(argv)


if __name__ == "__main__":
    sys.exit(main())
