"""Shared kernel-dispatch helpers.

Every kernel package exposes ``ops.py`` with a public op that takes
``kernel_mode``:

* ``"reference"``        — pure-jnp oracle (``ref.py``).  Default on CPU and
                           inside dry-run graphs (the CPU backend cannot
                           compile Mosaic/TPU kernels).
* ``"pallas"``           — the TPU kernel (``kernel.py``), compiled by Mosaic.
* ``"pallas_interpret"`` — the same kernel body executed by the Pallas
                           interpreter; used by the test suite to validate
                           kernels against the oracle.  CPU backends only:
                           on a TPU it would hide the chip, so it is refused
                           there.
* ``"auto"``             — resolved by :mod:`repro.core.dispatch`, the one
                           calibrated backend-selection layer.  For a bare
                           per-op call that is the cold-start rule
                           (``pallas`` on TPU backends, else ``reference``);
                           the orchestrated engines make a full
                           :class:`~repro.core.dispatch.DispatchDecision`
                           with per-candidate predicted rates.

The trace-sweep engine (:mod:`repro.core.sweep`) accepts one extra mode on
top of the generic four: ``"stackdist"``, the exact sort-based
stack-distance backend (:mod:`repro.core.stackdist`).  Sweep entry points
validate against :data:`SWEEP_MODES`; whether ``"auto"`` picks it is the
dispatch layer's call (every-spec-eligible pure-LRU TLBs) — per-op kernels
keep the plain four-mode registry.
"""
from __future__ import annotations

from typing import Sequence

import jax

VALID_MODES = ("auto", "reference", "pallas", "pallas_interpret")
SWEEP_MODES = VALID_MODES + ("stackdist",)


def resolve_mode(
    kernel_mode: str,
    *,
    valid: Sequence[str] = VALID_MODES,
) -> str:
    """Validate ``kernel_mode`` against ``valid`` and resolve ``"auto"``.

    Explicit modes are always honoured as given; ``"auto"`` resolves to the
    dispatch layer's generic cold-start default (engine entry points make a
    richer, calibrated decision through :mod:`repro.core.dispatch` before
    their per-op calls ever see a mode).
    """
    if kernel_mode not in valid:
        raise ValueError(f"kernel_mode={kernel_mode!r}; expected one of {tuple(valid)}")
    if kernel_mode == "pallas_interpret" and not interpret_allowed():
        raise ValueError(
            f"kernel_mode='pallas_interpret' runs the Pallas interpreter, a "
            f"CPU validation mode; on the {jax.default_backend()!r} backend "
            f"use 'pallas'")
    if kernel_mode == "auto":
        from repro.core import dispatch

        return dispatch.default_mode()
    return kernel_mode


def interpret_allowed() -> bool:
    """May ``"pallas_interpret"`` run here?  Only on a CPU backend: on an
    accelerator the interpreter would stand in for the chip unseen."""
    return jax.default_backend() == "cpu"


def next_multiple(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


# SMEM is 1 MiB per core on v5e.  Kernels that stream per-access scalars
# through it keep their double-buffered windows within half of that, so
# parameter rows and the compiler's own scalars still fit.
SMEM_STREAM_BUDGET_BYTES = 512 * 1024


def smem_block(block: int, rows: int, streams: int) -> int:
    """Grid-step length for a kernel that streams ``streams`` 4-byte
    ``[rows, step]`` windows through SMEM: ``block`` halved until the
    double-buffered windows fit :data:`SMEM_STREAM_BUDGET_BYTES`.  The
    result divides ``block``, so any length that is a multiple of ``block``
    is one of the step too; results do not depend on it."""
    step = block
    while step % 2 == 0 and step > 8 and \
            2 * 4 * rows * streams * step > SMEM_STREAM_BUDGET_BYTES:
        step //= 2
    return step
