"""Where the Pallas SPU kernels run: the one platform decision.

Every call site of a Pallas kernel passes ``interpret=interpret_pallas()``.
On a TPU the kernels compile through Mosaic; on the CPU (tier-1 tests,
smoke runs) they run in Pallas interpret mode, which executes the same
kernel body with the same MX8 math.  Any other platform has no path: the
kernels are written for the TPU's memory spaces, and the interpreter is a
correctness tool, never a silent fallback on an accelerator.
"""
from __future__ import annotations

import jax


def interpret_pallas() -> bool:
    """True on the CPU, False on a TPU; any other platform raises."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas SPU kernel path for platform {platform!r}: kernels run "
        f"compiled on 'tpu' or interpreted on 'cpu'")
