"""Kernel backend dispatch: ONE place that decides Pallas vs jnp reference.

Every kernel in this package has two interchangeable implementations — a
Pallas kernel and a jnp reference that doubles as the differential-testing
oracle. The backend decides which one runs:

- on a TPU, every Pallas kernel runs, compiled;
- on any other backend the jnp references run, and a Pallas kernel that
  is asked for explicitly runs in interpret mode (nothing else can run it).

``current()`` resolves the active policy (the backend unless overridden),
``override(...)`` installs a scoped override (tests, the chip smoke's
reference drain), and the per-call ``use_pallas=`` / ``interpret=`` kwargs
on each kernel entry point win over both.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Iterator, Optional

import jax


@dataclasses.dataclass(frozen=True)
class KernelDispatch:
    """Resolved kernel-backend policy for one call."""

    use_pallas: bool   # Pallas kernel vs jnp reference
    interpret: bool    # Pallas interpret mode vs compiled (TPU)


_OVERRIDE: list = []  # stack of KernelDispatch overrides (innermost last)


def backend_dispatch() -> KernelDispatch:
    """The backend's own policy: compiled Pallas on a TPU, else references
    (with interpret mode for any Pallas kernel a caller asks for)."""
    on_tpu = jax.default_backend() == "tpu"
    return KernelDispatch(use_pallas=on_tpu, interpret=not on_tpu)


def current() -> KernelDispatch:
    """The active policy: innermost ``override`` if any, else the backend's."""
    return _OVERRIDE[-1] if _OVERRIDE else backend_dispatch()


def auto_partitioned() -> bool:
    """True while tracing under a mesh (``jax.set_mesh``, as the launchers
    enter theirs) of more than one device whose axes are not all manual:
    XLA partitions that program on its own, and a Pallas (Mosaic) call has
    no partitioning rule, so a caller runs its jnp form there. Inside a
    ``shard_map`` over every axis, or with no mesh, it is False."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return False
    return set(mesh.manual_axes) != set(mesh.axis_names)


def resolve(use_pallas: Optional[bool] = None,
            interpret: Optional[bool] = None) -> KernelDispatch:
    """Per-call kwargs beat the active policy; None defers to it."""
    cur = current()
    return KernelDispatch(
        use_pallas=cur.use_pallas if use_pallas is None else use_pallas,
        interpret=cur.interpret if interpret is None else interpret,
    )


@contextlib.contextmanager
def override(use_pallas: Optional[bool] = None,
             interpret: Optional[bool] = None) -> Iterator[KernelDispatch]:
    """Scoped policy override; None fields inherit the surrounding policy."""
    d = resolve(use_pallas, interpret)
    _OVERRIDE.append(d)
    try:
        yield d
    finally:
        _OVERRIDE.pop()
