"""Where the Pallas kernels run: compiled on a TPU, interpreted elsewhere.

Every kernel module resolves ``interpret=None`` through
:func:`default_interpret`, so one switch decides the lowering for all of
them.
"""
from __future__ import annotations

import os

import jax


def default_interpret() -> bool:
    """Compiled lowering on TPU, interpreter everywhere else — so CPU/GPU
    hosts validate the kernels while TPU runs never silently benchmark the
    interpreter.  ``REPRO_FORCE_INTERPRET=1`` (or ``0``) pins the mode
    regardless of the detected accelerator, so CI and local debugging can
    force the interpreter (or a compiled lowering) without monkeypatching;
    an empty/unset variable keeps the auto-detect."""
    env = os.environ.get("REPRO_FORCE_INTERPRET", "").strip().lower()
    if env:
        truthy = {"1", "true", "yes", "on"}
        falsy = {"0", "false", "no", "off"}
        if env not in truthy | falsy:
            raise ValueError(
                f"REPRO_FORCE_INTERPRET={env!r} not understood; use one "
                f"of {sorted(truthy)} / {sorted(falsy)} (or unset it for "
                f"accelerator auto-detect)")
        return env in truthy
    return jax.default_backend() != "tpu"
