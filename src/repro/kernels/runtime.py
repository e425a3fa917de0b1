"""Shared kernel-runtime knobs.

``default_interpret`` resolves whether a Pallas kernel runs in interpret
mode.  Resolution order:

1. ``JAX_PALLAS_INTERPRET`` environment variable, when set: truthy values
   ("1", "true", "yes", "on") force interpret mode — this is how CI
   exercises the *kernel bodies* (not just their jnp refs) on CPU
   runners; falsy values ("0", "false", "no", "off") force compiled
   dispatch.  Forcing interpret mode on a TPU backend logs a warning: the
   kernels then run as a Python emulation, not on the chip.
2. Otherwise: interpret everywhere except on a real TPU backend.

Resolution happens when a wrapper *traces* (``interpret`` is a static
jit argument), so a given input shape bakes the mode into its
compilation-cache entry — flip the environment before the first call on
a shape, not between calls.

``tpu_kernels_in`` lists the Pallas kernels a compiled program holds:
every ``pallas_call`` here is named, and Mosaic keeps that name on its
``tpu_custom_call`` instruction.
"""
from __future__ import annotations

import collections
import logging
import os
import re

import jax

log = logging.getLogger(__name__)

_TRUE = ("1", "true", "yes", "on")
_FALSE = ("0", "false", "no", "off")
INTERPRET_ENV = "JAX_PALLAS_INTERPRET"
_warned = []

_CUSTOM_CALL = re.compile(r"%([A-Za-z_][\w.-]*?)(?:\.\d+)? = "
                          r".*custom_call_target=\"tpu_custom_call\"")


def default_interpret() -> bool:
    env = os.environ.get(INTERPRET_ENV, "").strip().lower()
    if env in _TRUE:
        if jax.default_backend() == "tpu" and not _warned:
            _warned.append(True)
            log.warning("%s=%s forces Pallas interpret mode on a TPU "
                        "backend: kernels run as a host emulation, not on "
                        "the chip", INTERPRET_ENV, env)
        return True
    if env in _FALSE:
        return False
    return jax.default_backend() != "tpu"


def tpu_kernels_in(hlo_text: str) -> collections.Counter:
    """Count of ``tpu_custom_call`` instructions per kernel name in
    compiled HLO text (``compiled.as_text()``)."""
    return collections.Counter(
        m.group(1) for m in _CUSTOM_CALL.finditer(hlo_text))
