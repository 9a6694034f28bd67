"""The one place that decides whether Pallas kernels run compiled or in the
interpreter.

Every kernel entry point takes ``interpret: bool | None = None`` and resolves
``None`` through :func:`interpret_mode` when it is called (not when its module
is imported), so no caller can end up in the interpreter on a TPU by leaving
the argument out.  An explicit ``interpret=False`` is how an ahead-of-time
compile for a described TPU topology (``tests/test_tpu_compile.py``) asks for
the Mosaic lowering while the process's default backend is still the CPU.
"""

from __future__ import annotations

import jax


def interpret_mode(interpret: bool | None = None) -> bool:
    """Whether a Pallas kernel runs in the interpreter: an explicit
    ``interpret`` wins; ``None`` means exactly when the default backend is
    not a TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
