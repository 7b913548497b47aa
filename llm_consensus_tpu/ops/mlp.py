"""Feed-forward blocks: gated (SwiGLU / GeGLU) and plain (``W2 act(W1 x)``).

TPU notes: three matmuls dominate; the gate/up projections contract the same
activations, so XLA fuses the elementwise gate into the MXU epilogue. The
activation switch is static (config-derived), keeping one compiled program
per model family.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from llm_consensus_tpu.ops.quant import qeinsum


def _activate(x: jax.Array, activation: str) -> jax.Array:
    if activation == "silu":
        return jax.nn.silu(x)
    if activation == "gelu_tanh":
        return jax.nn.gelu(x, approximate=True)
    if activation == "relu2":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(f"unknown activation {activation!r}")


def gated_mlp(
    x: jax.Array,        # [..., D]
    w_gate: jax.Array,   # [D, F]
    w_up: jax.Array,     # [D, F]
    w_down: jax.Array,   # [F, D]
    activation: str = "silu",
    multipliers: tuple[float, float] = (1.0, 1.0),  # on the gate, on the output
) -> jax.Array:
    gate_mult, down_mult = multipliers
    gate = qeinsum("...d,df->...f", x, w_gate)
    if gate_mult != 1.0:
        gate = gate * gate_mult
    gate = _activate(gate, activation)
    up = qeinsum("...d,df->...f", x, w_up)
    out = qeinsum("...f,fd->...d", gate * up, w_down)
    return out if down_mult == 1.0 else out * down_mult


def plain_mlp(
    x: jax.Array,        # [..., D]
    w_up: jax.Array,     # [D, F]
    w_down: jax.Array,   # [F, D]
    activation: str = "relu2",
) -> jax.Array:
    """The ungated form, ``act(x W1) W2`` (Nemotron-H's experts)."""
    return qeinsum(
        "...f,fd->...d",
        _activate(qeinsum("...d,df->...f", x, w_up), activation), w_down)
