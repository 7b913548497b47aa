"""Plain float32 reference forward for the ``qwen2`` and ``mistral`` blocks.

The yardstick the served engines are compared with: straightforward
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
no kernels, no cache, no batching, no scan — one Python loop over layers,
each layer's weights upcast (and, for int8 storage, dequantized) only while
that layer runs. Written from the published block (pre-norm decoder:
RMSNorm, grouped-query attention with half-split rotary embeddings and an
optional q/k/v bias and sliding window, SwiGLU MLP, final RMSNorm, tied or
untied head), started from the numpy forward in ``tests/test_hf_golden.py``
(copied, not imported) and independent of ``models/transformer.py``: it
imports nothing from ``llm_consensus_tpu``.

It reads weights from the tree the engine serves (the program's own layout:
``embed [V, d]``, ``final_norm [d]``, ``lm_head [d, V]`` when untied,
``layers.{attn_norm, mlp_norm, wq, wk, wv, wo, w_gate, w_up, w_down}``
stacked on axis 0 and stored ``[contract, out]``, ``bq/bk/bv`` for qwen2;
an int8 leaf is ``{"q8": int8, "s": scale}`` with ``w = q8 * s``), so the
comparison is between two computations over the SAME numbers.

Tolerance (``TOLERANCE``), with its reason, is at the bottom.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference import BLOCK as QUERY_BLOCK, WHOLE_UP_TO

FAMILIES = ("qwen2", "mistral")
# The matmul leaves whose storage says what precision the tree is served in
# (the contract: benchmark/reference/__init__.py).
STORED_LEAVES = (("layers", "wq"), ("layers", "w_up"), ("layers", "w_down"))


def dense(w) -> jax.Array:
    """A stored weight as float32: plain leaves upcast, int8 leaves
    dequantized (code times its per-output-channel scale)."""
    if isinstance(w, dict):
        if "q8" not in w:
            raise ValueError(f"reference cannot read weight leaf {sorted(w)}")
        return w["q8"].astype(jnp.float32) * w["s"].astype(jnp.float32)
    return w.astype(jnp.float32)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w


def rope(x, positions, theta):
    """Half-split rotary embedding: pairs are (i, i + d/2). x [T, H, d]."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None].astype(jnp.float32) * inv_freq  # [T, d/2]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _attend(q, k, v, window, q0: int, k0: int):
    """Causal attention of the query positions ``q0 ...`` over the key
    positions ``k0 ...``. q [Tq, Hq, d], k/v [Tk, Hq, d] (heads repeated)."""
    scores = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(q.shape[-1])
    i = q0 + jnp.arange(q.shape[0])
    j = k0 + jnp.arange(k.shape[0])
    mask = j[None, :] <= i[:, None]
    if window is not None:
        mask &= j[None, :] > i[:, None] - window
    scores = jnp.where(mask[None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hts,shd->thd", p, v)


def attention(q, k, v, window):
    """Causal grouped-query attention over one sequence. q [T, Hq, d].

    A sequence of at most ``WHOLE_UP_TO`` positions is taken whole (one
    ``[H, T, T]`` table of scores: what every reading before PR 39 was taken
    with, to the last digit). A longer one goes ``QUERY_BLOCK`` queries at a
    time, each block against the keys it can see alone (up to its own last
    position; from the window's first), so no table is wider than a block of
    queries by the sequence."""
    t, hq, _ = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    if t <= WHOLE_UP_TO:
        return _attend(q, k, v, window, 0, 0)
    out = []
    for q0 in range(0, t, QUERY_BLOCK):
        q1 = min(q0 + QUERY_BLOCK, t)
        k0 = 0 if window is None else max(0, q0 - window + 1)
        out.append(_attend(q[q0:q1], k[k0:q1], v[k0:q1], window, q0, k0))
    return jnp.concatenate(out, axis=0)


def attention_block(x, w, *, n_heads, n_kv_heads, head_dim, theta, eps, window):
    """The attention half of a block on x [T, D], residual included."""
    t = x.shape[0]
    pos = jnp.arange(t)
    h = rms_norm(x, dense(w["attn_norm"]), eps)
    q, k, v = h @ dense(w["wq"]), h @ dense(w["wk"]), h @ dense(w["wv"])
    if "bq" in w:
        q, k, v = q + dense(w["bq"]), k + dense(w["bk"]), v + dense(w["bv"])
    q = rope(q.reshape(t, n_heads, head_dim), pos, theta)
    k = rope(k.reshape(t, n_kv_heads, head_dim), pos, theta)
    v = v.reshape(t, n_kv_heads, head_dim)
    a = attention(q, k, v, window).reshape(t, n_heads * head_dim)
    return x + a @ dense(w["wo"])


def layer(x, w, *, eps, **attn):
    """One decoder block on x [T, D]; ``w`` holds this layer's leaves."""
    x = attention_block(x, w, eps=eps, **attn)
    h = rms_norm(x, dense(w["mlp_norm"]), eps)
    gate = jax.nn.silu(h @ dense(w["w_gate"]))
    return x + (gate * (h @ dense(w["w_up"]))) @ dense(w["w_down"])


_layer_jit = jax.jit(
    layer, static_argnames=(
        "n_heads", "n_kv_heads", "head_dim", "theta", "eps", "window"),
)


@jax.jit
def _take_layer(stacked, i):
    return jax.tree.map(lambda a: a[i], stacked)


def one_at_a_time(x):
    """``x`` once it is computed. Dispatch runs ahead of the device: a loop
    over layers would otherwise take every layer's slice of the stack before
    the first layer has run, and hold a second copy of the model."""
    return jax.block_until_ready(x)


@jax.jit
def _head(x, final_norm, head, eps):
    return rms_norm(x, dense(final_norm), eps) @ dense(head)


def hidden(params: dict, shape: dict, token_ids) -> jax.Array:
    """The residual stream [T, D] in float32 after the last block (before
    the final norm, which ``logits`` applies), for one sequence of token ids.

    ``shape`` is the model's entry in the configuration file, of which it
    reads the published sizes (``family``, ``n_layers``, ``n_heads``,
    ``n_kv_heads``, ``head_dim``, ``rope_theta``, ``rms_eps``,
    ``sliding_window``, ``tie_embeddings``) — the benchmark's own copy, not
    the program's ``ModelConfig``."""
    if shape["family"] not in FAMILIES:
        raise ValueError(
            f"no plain reference for family {shape['family']!r}; "
            f"have {FAMILIES}"
        )
    ids = jnp.asarray(token_ids, jnp.int32)
    with jax.default_matmul_precision("highest"):
        x = params["embed"][ids].astype(jnp.float32)
        for i in range(shape["n_layers"]):
            x = one_at_a_time(_layer_jit(
                x, _take_layer(params["layers"], i),
                n_heads=shape["n_heads"], n_kv_heads=shape["n_kv_heads"],
                head_dim=shape["head_dim"], theta=float(shape["rope_theta"]),
                eps=float(shape["rms_eps"]), window=shape.get("sliding_window"),
            ))
        return x


def logits(params: dict, shape: dict, rows) -> jax.Array:
    """Logits [n, V] in float32 of ``rows`` [n, D], any rows of ``hidden``'s:
    the final norm and the tied or untied head."""
    with jax.default_matmul_precision("highest"):
        head = (
            params["embed"].T if shape["tie_embeddings"] else params["lm_head"]
        )
        return _head(rows, params["final_norm"], head, float(shape["rms_eps"]))


def forward(params: dict, shape: dict, token_ids) -> jax.Array:
    """Logits [T, V] in float32 for one sequence of token ids: ``logits`` of
    every row of ``hidden``."""
    return logits(params, shape, hidden(params, shape, token_ids))


# The program computes in bfloat16 (8 bits of mantissa) with float32
# accumulation; the reference in float32 over the same stored numbers. The
# error is measured per position as ||program - reference||_2 /
# ||reference||_2 over the vocabulary, and the worst of the 128 positions is
# held to TOLERANCE. Measured on the chip at published width and full depth
# (my chip runs, PR 22; PERF.md section 6): 1.5-1.9% for the served models at
# the precision their files state (the reference dequantizes the same int8
# codes, so weight quantization itself is not in the error; what is, is
# bfloat16 rounding of activations through 24-36 blocks). The same programs
# with the key/value cache held in int8, one step under what any
# configuration states, measured 2.6-3.0% (never under 2.5%). 2.2% sits a
# fifth above the worst reading at the stated precision and a seventh under
# the best reading one step lower: it passes what the files state and fails
# a lower precision.
TOLERANCE = 0.022


def compared(err, n_prefill: int) -> dict:
    """The worst position, prefilled or decoded, against TOLERANCE.

    Lengths the limit was read at: 128 positions, the last 32 decoded, in
    256 slots (PR 22, the readings above), and for the two smaller Qwen2.5
    panelists 1,024 positions, the last 64 decoded, in 1,024 slots (PRs 31
    and 34: the 0.5B reads 1.60-2.12% there over twenty readings, a twentieth
    under the limit; 2.148% on one of twelve more, PR 39: PERF.md section 7
    has what a ``benchmark`` PR does about it).
    **Not admitted as a limit at 6,144 positions**: readings there, the last
    64 decoded, in 8,192 slots, in blocks of 512, beside resident engines (my
    chip runs a1-a3 and b1-b3, PR 39; a dozen seeds a model): sound 0.5B
    1.68-2.13%, 1.5B 1.75-2.03%, 3B 1.80-2.02%, mistral-7b int8 past its
    4,096 window 1.72-1.91%; the int8 cache control 2.57-3.41% over 48
    readings. 2.2% passed every sound reading and failed every control, but
    stands only 3% above the largest sound one and 14% under the smallest
    control: room on one side alone, and a fresh seed reads higher than a
    dozen did. A limit set from these readings would stand near 2.35%. So a
    model of this module stays at or under 1,024 positions (a dense panelist
    beside a long-context model states lengths of its own) until a
    ``benchmark`` PR re-reads every cell and sets a limit for the longer
    lengths."""
    return {"rel_err_max": [float(err.max()), TOLERANCE]}
