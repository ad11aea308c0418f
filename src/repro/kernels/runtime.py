"""Kernel runtime policy shared by every Pallas kernel family.

Every kernel wrapper takes ``interpret: bool | None = None`` and resolves
it here: ``None`` means "compiled on a real TPU, interpreter everywhere
else" — so CPU CI keeps validating through the interpreter while real
hardware stops silently running interpreted kernels (the old hardcoded
``interpret=True`` default). Pass an explicit bool to override either
way (e.g. ``interpret=True`` on TPU to debug a kernel).

The ``REPRO_KERNEL_INTERPRET`` environment variable overrides the
*default* resolution per-run without touching call sites (CPU CI /
debugging): ``1``/``true`` forces interpreter mode, ``0``/``false``
forces compiled kernels. An explicit ``interpret=...`` argument at a
call site still wins over the environment.
"""
from __future__ import annotations

import functools
import os

import jax

ENV_INTERPRET = "REPRO_KERNEL_INTERPRET"
_TRUTHY = {"1", "true", "yes", "on"}
_FALSY = {"0", "false", "no", "off"}


@functools.cache
def on_tpu() -> bool:
    """True when the default JAX backend is a real TPU. A backend that
    fails to start raises here rather than quietly selecting the CPU
    interpreter and the reference paths."""
    return jax.default_backend() == "tpu"


def _env_interpret() -> bool | None:
    """The ``REPRO_KERNEL_INTERPRET`` override, if set (and valid)."""
    raw = os.environ.get(ENV_INTERPRET)
    if raw is None:
        return None
    val = raw.strip().lower()
    if val in _TRUTHY:
        return True
    if val in _FALSY:
        return False
    raise ValueError(
        f"{ENV_INTERPRET}={raw!r} is not a boolean "
        f"(use one of {sorted(_TRUTHY | _FALSY)})"
    )


def resolve_interpret(interpret: "bool | None") -> bool:
    """Resolve a kernel's interpret argument against the backend."""
    if interpret is None:
        env = _env_interpret()
        if env is not None:
            return env
        return not on_tpu()
    return bool(interpret)
