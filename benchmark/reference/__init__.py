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
    does not compute. It is the composition of the two below, kept for
    sequences of at most ``WHOLE_UP_TO`` positions and for the tests.

``hidden(params, spec, token_ids) -> [T, D] float32`` and
``logits(params, spec, rows) -> [n, V] float32``
    The blocked form (PR 39), which a model compared at more than
    ``WHOLE_UP_TO`` positions is compared through: ``hidden`` computes the
    sequence's last hidden states ONCE (normed or not is the module's own
    business), and ``logits`` applies the head to whichever ``n`` rows of
    them it is given, so the harness never holds more than ``[BLOCK, V]`` of
    either side.
    Inside ``hidden`` a long sequence is taken in blocks by the module, not
    by the harness: attention over blocks of queries, each against the keys
    it can see, so that no ``[H, T, T]`` table of scores is ever made (at
    6,144 positions that table is 2.1 GB a layer at 14 heads and 4.8 GB at
    32, beside engines that leave 2-4 GB free); a recurrence carries its
    state through the whole sequence. A module whose limits were read with
    the whole form (``decoder``) takes a sequence of at most ``WHOLE_UP_TO``
    positions whole, as the harness does, so that what was read before it
    blocked reads the same to the last digit; one that has attended in
    blocks at every length since it came (``deepseek_v2``) keeps to that.

``compared(err, n_prefill) -> {name: [value, limit]}``
    What of the comparison is held to a limit. ``err`` is a float64 numpy
    array, one relative error a position (``||program - reference||_2 /
    ||reference||_2`` over the vocabulary); the first ``n_prefill`` positions
    went through one prefill (or, past ``WHOLE_UP_TO`` positions, through
    the cache a block at a time), the rest each through the cache. The model is compared ``ok``
    when every value is at or under its limit. Each limit is written with the
    readings it was set from: above what sound runs give, below what one
    precision lower gives. **Its docstring states the LENGTHS those readings
    were taken at**: rounding accumulates along a sequence and a worst
    position is the worst of more draws, so a limit read at 1,024 positions
    is not a limit at 6,144. A configuration that compares a model at other
    lengths reads its sound runs and its control there first, and a model
    compared beside it stays at the lengths its own limit was read at (a
    ``parity`` object of its own in the file). The harness prints every name
    with its value and limit, in ``parity.json`` and on the run's last lines.

``TOLERANCE``
    The module's principal limit, recorded in ``parity.json``.

``STORED_LEAVES``
    Paths into the served tree, each a tuple of keys, of the matmul weights
    whose storage says what precision the tree is served in. The harness
    walks them (``parity.stored_as_stated``): ``int8`` means every one is a
    ``{"q8", "s"}`` leaf, any other stated type that every one is a plain
    array of that dtype.
"""

# Whole or in blocks is decided by the sequence's length alone, the same way
# by the harness (``parity.check_engine``) and inside a module (``decoder.
# attention``): no file chooses. Every limit read before PR 39 was read whole,
# at 1,024 positions at the most; past about 1.5k positions the whole form no
# longer fits beside resident engines (three ``[T, V]`` float32 arrays, an
# ``[H, T, T]`` table of scores a layer), and every chip reading past 1,024
# was taken in blocks of 512.
WHOLE_UP_TO = 1024   # positions a sequence may have and still be taken whole
BLOCK = 512          # positions at a time, past that
