"""Which backend JAX runs on — and whether anybody asked for it.

With no accelerator visible JAX quietly picks the CPU backend, and every
layer above used to follow it: XLA attention instead of the kernels, the
Pallas interpreter instead of Mosaic, no utilization gauges. A ``tpu:``
model answering from the CPU then looked exactly like success. The rule
here: the TPU is always fine; any other backend only when it was chosen
by name (``JAX_PLATFORMS=cpu`` / ``jax.config.update("jax_platforms",
"cpu")``, as the tests and CI lanes do).
"""

from __future__ import annotations


def requested(backend: str) -> bool:
    """True when ``backend`` is named in JAX's platform selection."""
    import jax

    names = (jax.config.jax_platforms or "").lower().split(",")
    return backend.lower() in (n.strip() for n in names)


def checked_backend(what: str) -> str:
    """``jax.default_backend()``, refusing a fallback nobody asked for."""
    import jax

    backend = jax.default_backend()
    if backend != "tpu" and not requested(backend):
        raise RuntimeError(
            f"{what} needs a TPU, but JAX found none and fell back to "
            f"{backend!r}. To run on the {backend} on purpose, ask for it "
            f"by name: JAX_PLATFORMS={backend}"
        )
    return backend


def pallas_interpret() -> bool:
    """Default ``interpret`` flag of the Pallas kernels: Mosaic on a TPU,
    the interpreter only where another backend was asked for."""
    return checked_backend("a Pallas TPU kernel") != "tpu"
