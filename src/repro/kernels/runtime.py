"""Kernel runtime policy: interpret-mode + sweep-backend selection.

Pallas kernels compile to Mosaic only on TPU backends; everywhere else
(CPU CI, GPU hosts) the same kernel body must run under the Pallas
interpreter.  Kernels take ``interpret=None`` and resolve it here at trace
time, so the default is "compiled on TPU, interpreted elsewhere" without
any call site hardcoding a mode.

The ``REPRO_KERNEL_INTERPRET`` environment variable overrides the
``interpret=None`` auto policy without touching call sites — ``1`` forces
the interpreter, ``0`` forces compiled kernels, ``auto`` (or unset) keeps
the backend-based default.  An explicit ``interpret=`` argument always
wins over the environment.

The fused sweep engine additionally picks an EXECUTION BACKEND per sweep
(:func:`resolve_backend`): ``"pallas"`` runs the megakernel through
``pallas_call`` (Mosaic-compiled on TPU, interpreted elsewhere) and
``"xla"`` runs the pure-``jnp`` twin (``repro.kernels.fused_sweep_xla``)
that XLA compiles natively on any backend.  ``"auto"`` resolves to
Pallas on TPU and XLA everywhere else — off-TPU the interpreter is pure
overhead, and the jnp lane is the compiled path.  ``REPRO_SWEEP_BACKEND``
overrides the auto policy exactly like ``REPRO_KERNEL_INTERPRET`` does
for interpret mode; an explicit ``backend=`` argument always wins.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

_ENV_VAR = "REPRO_KERNEL_INTERPRET"
_ENV_VALUES = ("0", "1", "auto")

_BACKEND_ENV_VAR = "REPRO_SWEEP_BACKEND"
#: valid sweep backends: "auto" resolves by platform (pallas on TPU,
#: xla elsewhere); explicit values force the lane
SWEEP_BACKENDS = ("auto", "pallas", "xla")

_BACKEND_IS_TPU: Optional[bool] = None


def on_tpu() -> bool:
    global _BACKEND_IS_TPU
    if _BACKEND_IS_TPU is None:
        _BACKEND_IS_TPU = jax.default_backend() == "tpu"
    return _BACKEND_IS_TPU


def reset_backend_cache() -> None:
    """Drop the memoized platform probe.

    ``on_tpu()`` caches ``jax.default_backend()`` on first use, which is
    wrong the moment a process re-initializes its platform set — e.g. a
    ``jax.distributed.initialize`` call, a subprocess test flipping
    ``JAX_PLATFORMS``/``XLA_FLAGS`` before re-importing, or an embedding
    host attaching an accelerator after warmup.  Call this after any
    platform reconfiguration so the next :func:`on_tpu` /
    :func:`resolve_interpret` / :func:`resolve_backend` re-probes.
    """
    global _BACKEND_IS_TPU
    _BACKEND_IS_TPU = None


def _env_override() -> Optional[bool]:
    raw = os.environ.get(_ENV_VAR)
    if raw is None:
        return None
    value = raw.strip().lower()
    if value == "auto" or value == "":
        return None
    if value in ("0", "1"):
        return value == "1"
    raise ValueError(
        f"invalid {_ENV_VAR}={raw!r}; valid values: {list(_ENV_VALUES)}")


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """None -> auto (interpret everywhere except TPU, overridable via
    ``REPRO_KERNEL_INTERPRET``); bool -> as given."""
    if interpret is None:
        env = _env_override()
        if env is not None:
            return env
        return not on_tpu()
    return bool(interpret)


def _backend_env_override() -> Optional[str]:
    raw = os.environ.get(_BACKEND_ENV_VAR)
    if raw is None:
        return None
    value = raw.strip().lower()
    if value == "auto" or value == "":
        return None
    if value in ("pallas", "xla"):
        return value
    raise ValueError(
        f"invalid {_BACKEND_ENV_VAR}={raw!r}; valid values: "
        f"{list(SWEEP_BACKENDS)}")


def explicit_backend(backend: Optional[str] = None) -> Optional[str]:
    """The explicitly REQUESTED backend, or None under the auto policy.

    An explicit ``backend=`` argument wins over ``REPRO_SWEEP_BACKEND``;
    ``None``/``"auto"`` with no env override returns None (platform
    default applies).  Campaign resume uses this to distinguish "the
    caller demanded a backend" (refuse on manifest mismatch) from "the
    caller deferred" (reuse the recorded one).
    """
    if backend is not None and backend != "auto":
        if backend not in ("pallas", "xla"):
            raise ValueError(f"unknown sweep backend {backend!r}; valid: "
                             f"{list(SWEEP_BACKENDS)}")
        return backend
    return _backend_env_override()


def resolve_backend(backend: Optional[str] = None) -> str:
    """Resolve the fused-sweep execution backend to "pallas" or "xla".

    ``None``/``"auto"`` consults ``REPRO_SWEEP_BACKEND`` and then the
    platform default (Pallas-compiled on TPU, XLA-native elsewhere); an
    explicit ``"pallas"``/``"xla"`` always wins over the environment.
    """
    requested = explicit_backend(backend)
    if requested is not None:
        return requested
    return "pallas" if on_tpu() else "xla"


def kernel_mode() -> str:
    """Human-readable Pallas mode tag for benchmark output."""
    return "interpret" if resolve_interpret(None) else "compiled"


def sweep_kernel_mode(backend: Optional[str] = None) -> str:
    """Mode tag for a resolved sweep backend: the XLA lane is always
    natively compiled; the Pallas lane reports its interpret mode."""
    if resolve_backend(backend) == "xla":
        return "xla"
    return kernel_mode()
