"""Interpret-or-compile: the one switch the Pallas modules share.

Off the TPU every kernel runs in the Pallas interpreter — that is how
the CPU suite checks parity, and it is a test facility only.  On the
chip path the same switch would silently hide a mis-detected backend
(a "TPU" run whose kernels interpret), so
``NPAIRLOSS_PALLAS_INTERPRET=forbid`` turns a kernel that WOULD
interpret into an error.  ``chip_smoke.py`` sets it for every leg.
"""

from __future__ import annotations

import os

import jax

INTERPRET_GUARD_ENV = "NPAIRLOSS_PALLAS_INTERPRET"


def default_interpret() -> bool:
    """True off-TPU (interpret), False on it (Mosaic-compile); raises
    under the ``forbid`` guard instead of returning True."""
    interpret = jax.default_backend() != "tpu"
    if interpret and os.environ.get(
            INTERPRET_GUARD_ENV, "").strip().lower() == "forbid":
        raise RuntimeError(
            f"a Pallas kernel would run interpreted (backend "
            f"{jax.default_backend()!r}) under "
            f"{INTERPRET_GUARD_ENV}=forbid")
    return interpret
