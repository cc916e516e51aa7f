"""Kernel registry: one dispatch policy for every Pallas kernel package.

Every ``kernels/*/ops.py`` used to hand-roll the same fallback dance::

    on_tpu = jax.default_backend() == "tpu"
    if use_kernel and (on_tpu or interpret):
        return kernel(..., interpret=interpret or not on_tpu)
    return ref(...)

Four copies of that predicate is four places for the TPU/CPU/interpret
semantics to drift.  This module centralizes it: each package registers a
:class:`KernelOp` — a uniform ``(ref, kernel)`` pair plus an optional
``supports`` eligibility gate (e.g. the gather kernel's block-divisibility
requirement) and a ``sample`` input factory the parity test harness sweeps
— and its ``ops.py`` wrapper becomes one :func:`dispatch` call.

Dispatch semantics (identical to the historical per-op wrappers):

* ``use_kernel=False`` → the jnp reference, always (models may call ops
  unconditionally).
* On TPU the Pallas kernel always runs compiled: ``interpret`` (and
  ``REPRO_KERNEL_INTERPRET``) is ignored there, so nothing can quietly
  swap the device kernel for the interpreter or the reference.
* Off TPU the kernel runs only when ``interpret=True`` (the kernel body
  executes on CPU exactly as it would on the TPU grid — the test path);
  otherwise the reference runs.
* An op whose ``supports`` predicate rejects the concrete operands falls
  back to the reference — a shape outside the kernel's envelope is a
  fallback, not an error.

Registration happens at import of each package's ``ops.py``; the package
facade (:mod:`repro.kernels`) imports them all, so ``import repro.kernels``
yields a fully-populated registry.  ``names()``/``get()`` drive the
registry-wide ref-vs-kernel parity sweep in ``tests/test_kernels.py`` —
registering an op automatically buys it the parity gate.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Optional

import jax

__all__ = ["KernelOp", "OpSample", "register", "get", "names", "dispatch",
           "interpret_default"]


def interpret_default() -> bool:
    """Whether dispatch callers should default ``interpret=True``.

    Controlled by the ``REPRO_KERNEL_INTERPRET`` environment variable
    (``1``/``true``/``yes``): CI's CPU-only ``kernels`` job sets it so the
    serving engine's decode ticks execute the Pallas kernel bodies under
    interpret mode on every PR, instead of only on TPU.  Off by default —
    off-TPU callers then take the pure-jnp reference path.  It has no
    effect on TPU, where :func:`dispatch` always compiles the kernel.
    """
    return os.environ.get("REPRO_KERNEL_INTERPRET", "").strip().lower() in (
        "1", "true", "yes")


@dataclasses.dataclass(frozen=True)
class OpSample:
    """One representative invocation for the registry parity harness.

    ``args`` are positional operands; ``common`` keywords go to BOTH the
    kernel and the reference (semantic switches like ``causal``);
    ``kernel`` keywords go to the kernel only (tuning knobs like block
    sizes).  ``tol=None`` demands bit-exact agreement (integer gathers);
    otherwise ``(rtol, atol)`` for float comparison.
    """

    args: tuple
    common: dict = dataclasses.field(default_factory=dict)
    kernel: dict = dataclasses.field(default_factory=dict)
    tol: Optional[tuple[float, float]] = (2e-5, 2e-5)


@dataclasses.dataclass(frozen=True)
class KernelOp:
    """A registered ``(ref, kernel)`` pair with uniform dispatch metadata.

    ``kernel`` must accept ``interpret=``; ``ref`` is a pure-jnp oracle
    with the same positional signature (plus any ``common`` keywords).
    ``supports(*args, **kwargs)`` gates kernel eligibility per call —
    ``None`` means the kernel handles every shape the op accepts.
    ``sample(key)`` builds an :class:`OpSample` for the parity sweep.
    """

    name: str
    ref: Callable
    kernel: Callable
    supports: Optional[Callable[..., bool]] = None
    sample: Optional[Callable[[jax.Array], OpSample]] = None


_OPS: dict[str, KernelOp] = {}


def register(name: str, *, ref: Callable, kernel: Callable,
             supports: Optional[Callable[..., bool]] = None,
             sample: Optional[Callable[[jax.Array], OpSample]] = None
             ) -> KernelOp:
    """Register one kernel package's ``(ref, kernel)`` pair under ``name``.

    Re-registration with identical callables is a no-op (module reloads);
    conflicting re-registration raises — two packages must not claim one
    name.  Returns the registered :class:`KernelOp`.
    """
    op = KernelOp(name, ref, kernel, supports, sample)
    prev = _OPS.get(name)
    if prev is not None and (prev.ref, prev.kernel) != (ref, kernel):
        raise ValueError(f"kernel op {name!r} already registered with "
                         "different callables")
    _OPS[name] = op
    return op


def get(name: str) -> KernelOp:
    """Look up a registered op (KeyError with the known names on a miss)."""
    try:
        return _OPS[name]
    except KeyError:
        raise KeyError(f"unknown kernel op {name!r}; registered: "
                       f"{sorted(_OPS)}") from None


def names() -> list[str]:
    """Sorted names of every registered op (parity-harness parametrize)."""
    return sorted(_OPS)


def dispatch(name: str, args: tuple, *, common: Optional[dict] = None,
             kernel_kwargs: Optional[dict] = None, use_kernel: bool = True,
             interpret: bool = False):
    """Run ``name`` on ``args`` through the shared kernel/ref policy.

    ``common`` keywords reach both implementations; ``kernel_kwargs``
    reach the kernel only.  See the module docstring for the exact
    fallback semantics.
    """
    op = get(name)
    ck = common or {}
    kk = kernel_kwargs or {}
    on_tpu = jax.default_backend() == "tpu"
    eligible = (op.supports is None or op.supports(*args, **ck, **kk))
    if use_kernel and (on_tpu or interpret) and eligible:
        return op.kernel(*args, **ck, **kk, interpret=not on_tpu)
    return op.ref(*args, **ck)
