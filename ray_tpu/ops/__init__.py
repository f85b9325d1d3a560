"""Pallas kernels of the model path (flash attention, the grouped matmuls
of a mixture of experts, the gated delta rule's chunk preparation and scan,
the linear mixers' short convolutions, RMSNorm), and the one rule for
interpret mode."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """The one place that decides whether a Pallas kernel is interpreted.

    ``None`` means "from the platform": compiled by Mosaic on a TPU
    backend, interpreted anywhere else (the CPU twin, where tests run the
    identical kernel code). A caller that wants the interpreter says
    ``interpret=True`` or runs under ``JAX_PLATFORMS=cpu``; a caller that
    compiles for a described chip says ``interpret=False``. On a TPU
    backend nothing here ever gives way to a reference implementation: a
    kernel the compiler refuses raises."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
