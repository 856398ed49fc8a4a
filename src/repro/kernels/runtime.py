"""Shared Pallas runtime policy for the kernel packages.

Every kernel here runs in one of two modes:

- **compiled** — ``pl.pallas_call(..., interpret=False)``: the Mosaic
  lowering, which is what a TPU host runs.
- **interpret** — the kernel body is evaluated op-by-op by XLA on the host.
  Bit-for-bit the semantics of the kernel jaxpr, so it doubles as the
  *oracle* for the compiled path (the differential suites run it on CPU).

The platform alone decides the default: TPU hosts compile, every other
backend interprets.  An explicit ``interpret=`` argument (tests pin it)
passes through untouched.  There is no environment override — a TPU host
never falls back to the interpreter, so a kernel number taken there is the
kernel's.

The platform probe is **lazy**: it runs on the first kernel dispatch that
needs it, never at import or engine-construction time.  Backend discovery
spins up threads and, on a TPU host, claims the chip for this process.
Child processes the repo starts (``repro.fleet.transport`` shard workers,
``repro.launch.dryrun`` cells) are pinned to ``JAX_PLATFORMS=cpu`` in their
own environment, so they probe the CPU and never contend for the parent's
chip.
"""

from __future__ import annotations

from typing import Optional

import jax

__all__ = ["platform", "resolve_interpret"]

# Memoized ``jax.default_backend()``.  None = not probed yet (module state,
# so tests can stand in a platform without a device).
_PLATFORM: Optional[str] = None


def platform() -> str:
    """This process's JAX backend (``"tpu"``, ``"cpu"``, ...), probed once
    on first use: backend discovery is stable for a process's lifetime."""
    global _PLATFORM
    if _PLATFORM is None:
        _PLATFORM = jax.default_backend()
    return _PLATFORM


def resolve_interpret(interpret=None) -> bool:
    """Resolve an ``interpret=`` kernel argument to a concrete bool.

    ``None`` (the kernel-op default) means the platform default: compiled
    on TPU, interpret elsewhere.  An explicit bool passes through untouched.
    """
    if interpret is None:
        return platform() != "tpu"
    return bool(interpret)
