"""Plain references, one module a model family, named by the configuration.

A model's entry in a configuration file names its reference with
``"reference": "<module>"`` (default ``decoder``); ``benchmark/parity.py``
imports ``benchmark.reference.<module>`` and holds the served engine to it.
A new family brings a new module, and edits none that is here. A module has:

``forward(params, spec, token_ids) -> [T, V] float32``
    Logits of one sequence from the tree the engine serves, computed under
    ``jax.default_matmul_precision("highest")`` in plain ``jax.numpy``: no
    kernels, no cache, no batching. ``spec`` is the model's WHOLE entry in
    the configuration file (the core sizes, ``more_fields``, and whatever
    annotations a cut needs: published counts beside the held ones). It
    imports nothing from ``llm_consensus_tpu`` and raises for a family it
    does not compute. A sequence too long to take whole is chunked inside
    the module, not by the harness.

``compared(err, n_prefill) -> {name: [value, limit]}``
    What of the comparison is held to a limit. ``err`` is a float64 numpy
    array, one relative error a position (``||program - reference||_2 /
    ||reference||_2`` over the vocabulary); the first ``n_prefill`` positions
    went through one prefill, the rest each through the cache. The model is
    compared ``ok`` when every value is at or under its limit. Each limit is
    written with the readings it was set from: above what sound runs give,
    below what one precision lower gives. The harness prints every name with
    its value and limit, in ``parity.json`` and on the run's last lines.

``TOLERANCE``
    The module's principal limit, recorded in ``parity.json``.

``STORED_LEAVES``
    Paths into the served tree, each a tuple of keys, of the matmul weights
    whose storage says what precision the tree is served in. The harness
    walks them (``parity.stored_as_stated``): ``int8`` means every one is a
    ``{"q8", "s"}`` leaf, any other stated type that every one is a plain
    array of that dtype.
"""
