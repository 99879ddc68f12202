"""Where each Pallas kernel runs: compiled on a TPU, interpreted elsewhere.

Every kernel wrapper resolves its ``interpret`` flag here, from the JAX
backend, so ``use_pallas=True`` never runs the Pallas interpreter on a TPU.
A kernel that the TPU compiler refuses raises `PallasUnsupportedError` on a
TPU, naming the kernel and the compiler's reason, instead of interpreting
or quietly falling back to its jnp twin.  Off the TPU every kernel runs in
the interpreter, which is what the repository's CPU tests check it with.
"""
from __future__ import annotations

import jax

KERNELS = ("fibhash", "match_extend", "fused_compress", "emit_scatter",
           "decode_wave", "plan_spec")

# Kernels the TPU compiler (Mosaic, jax/jaxlib 0.9.0, libtpu 0.0.34) refuses
# for a TPU v5e at the 64 KB block size, with its reason.  Each one reads the
# block (or a table) at data-dependent positions with a 1-D `jnp.take`, and
# Mosaic lowers gathers only within 2-D vreg tiles; the two match kernels
# also slice the block at unaligned per-tile offsets.  No layout change
# removes the gather, so these need a different algorithm on the chip.
# tests/test_tpu_compile.py pins both halves: every kernel not listed
# compiles, and every listed one is still refused.
_GATHER = "Only 2D gather is supported (1-D jnp.take at data-dependent indices)"
TPU_REFUSED = {
    "match_extend": "Unimplemented primitive in Pallas TPU lowering: "
                    "dynamic_slice (unaligned per-tile block slice); "
                    "the candidate read is a 1-D gather as well",
    "fused_compress": "Unimplemented primitive in Pallas TPU lowering: "
                      "dynamic_slice (unaligned per-tile block slice); "
                      "the LVT and candidate reads are 1-D gathers as well",
    "emit_scatter": _GATHER,
    "decode_wave": _GATHER,
    "plan_spec": _GATHER,
}


class PallasUnsupportedError(NotImplementedError):
    """A Pallas kernel that does not compile for the active backend."""


def interpret_mode(kernel: str, backend: str | None = None) -> bool:
    """The ``interpret`` flag for ``kernel`` on ``backend`` (default: JAX's).

    False on a TPU (the Mosaic kernel compiles), True on every other
    backend.  Raises `PallasUnsupportedError` for a kernel in
    `TPU_REFUSED` on a TPU.
    """
    if kernel not in KERNELS:
        raise ValueError(f"unknown Pallas kernel {kernel!r}; one of {KERNELS}")
    backend = backend or jax.default_backend()
    if backend != "tpu":
        return True
    reason = TPU_REFUSED.get(kernel)
    if reason is not None:
        raise PallasUnsupportedError(
            f"Pallas kernel {kernel!r} does not compile for TPU: {reason}. "
            "Use use_pallas=False on this backend.")
    return False
