"""EpBackend: the mode-agnostic staged-EP backend protocol.

The unified API's original rendering dispatched on ``group.mode`` through
if/elif chains in ``core/api.py``, and the staged surface
(``send_only=True`` + ``ep_complete``) existed only for LL — HT and the
baseline accepted the flag and silently ran eager, and ``ep_complete`` was an
``isinstance`` chain over LL's private pending types. This module replaces
all of that with one protocol:

* ``EpBackend`` — the five-phase contract every mode implements:
  ``create_handle``, ``dispatch_send``, ``dispatch_complete``,
  ``combine_send``, ``combine_complete``. The eager ``dispatch``/``combine``
  entry points are derived (send then complete), so **staged is the primitive
  and eager is the composition** — a mode cannot implement the eager path
  without the staged one, which is exactly the no-silent-ignore contract
  tests/test_backends.py pins: every registered backend either executes
  ``send_only=True`` staged or raises ``NotImplementedError``; none may
  accept the flag and run eager.

* ``EpPending`` — the one mode-tagged pending pytree shared by every mode.
  ``mode`` and ``op`` are static (aux-data) fields, so ``ep_complete`` can
  route through the registry by tag with zero ``isinstance`` special-casing,
  and a pending created by one mode handed to another mode's group fails
  loudly instead of silently unpacking garbage.

* the registry — backends self-register at import keyed by their mode name;
  ``get_backend(group.mode)`` is the only mode dispatch left in the API
  layer. Future modes (the ROADMAP's standing contract) plug in by
  registering a backend and shipping their phase maps in ``EpPlan``.
"""
from __future__ import annotations

import dataclasses
from typing import Protocol, runtime_checkable

import jax

from repro.core.group import EpGroup, EpHandle


@dataclasses.dataclass
class EpPending:
    """In-flight staged EP operation (the JAX rendering of the paper's
    posted-but-not-consumed transfer).

    ``recv`` holds the received-but-unconsumed payload blocks — for chunked
    hierarchical HT, the flat concatenation of every chunk's stage-2 buffer —
    and ``recv_scales`` the ride-along fp8 scales when the dispatch payload
    is quantized. ``mode``/``op`` are static pytree metadata: they survive
    jit tracing as Python strings, which is what lets ``ep_complete`` route
    by tag instead of by Python type."""

    mode: str                          # owning backend ("ll" | "ht" | ...)
    op: str                            # "dispatch" | "combine"
    recv: jax.Array                    # received payload rows
    recv_scales: jax.Array | None = None   # fp8 scales riding along


jax.tree_util.register_dataclass(
    EpPending, data_fields=["recv", "recv_scales"], meta_fields=["mode", "op"])


@runtime_checkable
class EpBackend(Protocol):
    """Protocol every mode backend satisfies (see BaseBackend for the
    derived eager surface)."""

    mode: str

    def create_handle(self, group: EpGroup, topk_idx, topk_weights,
                      num_tokens=None) -> EpHandle: ...
    def dispatch_send(self, group: EpGroup, handle: EpHandle,
                      tokens) -> EpPending: ...
    def dispatch_complete(self, group: EpGroup, handle: EpHandle,
                          pending: EpPending): ...
    def combine_send(self, group: EpGroup, handle: EpHandle,
                     expert_out) -> EpPending: ...
    def combine_complete(self, group: EpGroup, handle: EpHandle,
                         pending: EpPending): ...


class BaseBackend:
    """Shared driver half of the protocol: eager = send ∘ complete.

    Subclasses implement the four phase halves (plus ``create_handle``); the
    staged/eager selection and the ``ep_complete`` tag routing live here so
    every mode honors ``send_only`` by construction."""

    mode: str = "?"

    # -- phase halves (mode-specific; subclasses override) ------------------
    def create_handle(self, group, topk_idx, topk_weights, num_tokens=None):
        raise NotImplementedError

    def dispatch_send(self, group, handle, tokens) -> EpPending:
        raise NotImplementedError

    def dispatch_complete(self, group, handle, pending: EpPending):
        raise NotImplementedError

    def combine_send(self, group, handle, expert_out) -> EpPending:
        raise NotImplementedError

    def combine_complete(self, group, handle, pending: EpPending):
        raise NotImplementedError

    # -- derived eager + staged surface ------------------------------------
    # Each phase half runs in a named scope (``ep.dispatch_send``,
    # ``ep.dispatch_recv``, ``ep.combine_send``, ``ep.combine_recv``): the
    # same names for every mode, in the compiled program's op metadata.
    def dispatch(self, group, handle, tokens, *, send_only: bool = False):
        with jax.named_scope("ep.dispatch_send"):
            pending = self.dispatch_send(group, handle, tokens)
        if send_only:
            return pending
        with jax.named_scope("ep.dispatch_recv"):
            return self.dispatch_complete(group, handle, pending)

    def combine(self, group, handle, expert_out, *, send_only: bool = False):
        with jax.named_scope("ep.combine_send"):
            pending = self.combine_send(group, handle, expert_out)
        if send_only:
            return pending
        with jax.named_scope("ep.combine_recv"):
            return self.combine_complete(group, handle, pending)

    def complete(self, group, handle, pending: EpPending):
        if not isinstance(pending, EpPending):
            raise TypeError(f"not a pending EP operation: {type(pending)}")
        if pending.mode != self.mode:
            raise ValueError(
                f"pending op belongs to mode {pending.mode!r}, but the group "
                f"resolved mode {self.mode!r} — handles and pendings are not "
                "transferable across modes")
        if pending.op == "dispatch":
            with jax.named_scope("ep.dispatch_recv"):
                return self.dispatch_complete(group, handle, pending)
        if pending.op == "combine":
            with jax.named_scope("ep.combine_recv"):
                return self.combine_complete(group, handle, pending)
        raise ValueError(f"unknown pending op: {pending.op!r}")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: dict[str, BaseBackend] = {}


def register_backend(backend: BaseBackend) -> BaseBackend:
    """Register a backend instance under its ``mode`` key. Idempotent per
    mode name (last registration wins — lets tests stub modes)."""
    _REGISTRY[backend.mode] = backend
    return backend


def get_backend(mode: str) -> BaseBackend:
    """Resolve a mode name to its registered backend. The ONLY mode dispatch
    in the API layer — no if/elif chains, no isinstance on pending types."""
    try:
        return _REGISTRY[mode]
    except KeyError:
        raise KeyError(
            f"no EP backend registered for mode {mode!r}; "
            f"known: {sorted(_REGISTRY)}") from None


def registered_modes() -> tuple[str, ...]:
    """Registered backend mode names (for the contract tests)."""
    return tuple(sorted(_REGISTRY))
