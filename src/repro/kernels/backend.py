"""Where the Pallas kernels run: compiled on TPU, interpreted on CPU.

The choice is made when a kernel is traced, from ``jax.default_backend()``
-- never while a module is imported, and never by probing devices.  A host
whose accelerator failed to initialise reports the ``"cpu"`` backend, so
its kernels run interpreted *visibly*: every measurement path checks the
backend before it starts (``chip_smoke.py`` refuses anything but TPU).
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` flag.

    ``None`` (every caller on the serving path): interpret on the CPU
    backend, compile on TPU, refuse any other backend.  An explicit
    ``False`` compiles for TPU even from a CPU host -- the ahead-of-time
    compile tests lower kernels for a described chip that way.  An explicit
    ``True`` on a TPU backend is refused: nothing runs interpreted there.
    """
    backend = jax.default_backend()
    if interpret is None:
        if backend not in ("cpu", "tpu"):
            raise RuntimeError(
                f"Pallas kernels compile for TPU and run interpreted on CPU; "
                f"backend {backend!r} has neither path")
        return backend == "cpu"
    if interpret and backend == "tpu":
        raise ValueError("Pallas interpret mode requested on a TPU backend")
    return bool(interpret)
