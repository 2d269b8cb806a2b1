"""Where a Pallas kernel runs: compiled on an accelerator, interpreted on CPU.

Every kernel entry takes ``interpret: bool | None = None``; ``None`` means
"decide from the backend", which is done here and nowhere else.  The CPU
backend has no Mosaic compiler, so kernels run in the Pallas interpreter
there (the test suite's mode); on a TPU they always compile.  An explicit
``False`` is only for compiling against a described chip while the process
itself runs on the CPU backend (``tests/test_tpu_compile.py``).
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
