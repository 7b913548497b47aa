"""Token sampling: greedy, temperature, top-k, nucleus (top-p).

All paths are jit-compatible (static branch structure chosen by the host
from the sampling params; no data-dependent Python control flow).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from llm_consensus_tpu.obs.scopes import scope
from llm_consensus_tpu.ops.attention import NEG_INF


def sample_token(
    logits: jax.Array,  # [B, V] fp32
    key: jax.Array,
    temperature: float = 0.0,
    top_k: Optional[int] = None,
    top_p: Optional[float] = None,
) -> jax.Array:
    """Sample next-token ids [B]. temperature==0 → greedy argmax."""
    with scope("sample"):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1)

        logits = logits.astype(jnp.float32) / temperature
        if top_k is not None:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, NEG_INF, logits)
        if top_p is not None:
            sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cumprobs = jnp.cumsum(probs, axis=-1)
            # smallest set of tokens whose cumulative probability ≥ top_p
            keep_sorted = cumprobs - probs < top_p
            threshold = jnp.min(
                jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True
            )
            logits = jnp.where(logits < threshold, NEG_INF, logits)
        return jax.random.categorical(key, logits, axis=-1)
