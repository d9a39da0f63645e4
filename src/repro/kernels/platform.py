"""Where the Pallas kernels run: compiled for the chip, or interpreted."""

from __future__ import annotations


def interpret_mode() -> bool:
    """True only when JAX's default backend is the CPU.

    The kernels are written for the TPU; the CPU backend cannot compile
    them, so there (and only there) they run in Pallas interpret mode.
    Any other backend gets the compiled kernel, and a kernel it cannot
    compile fails loudly instead of silently falling back."""
    import jax

    return jax.default_backend() == "cpu"
