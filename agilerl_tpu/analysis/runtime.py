"""Runtime compile/sync guards — the dynamic half of graftcheck.

``CompileGuard`` asserts a guarded region triggers no new XLA compilations:
either against specific jitted callables (measured jit cache size, the same
accounting contract as ``llm/serving.measured_cache_size``) or globally via
jax's compile monitoring events. ``SyncGuard`` counts blocking device→host
transfers (``float()``/``int()``/``bool()``/``.item()``/``.tolist()`` on a
``jax.Array``) and emits ``analysis/host_syncs_total`` through the
observability registry — the runtime complement of static rule GX001.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, List, Optional

import jax

from agilerl_tpu.llm.serving import measured_cache_size

#: monitoring event jax records once per backend (XLA) compilation — present
#: on the installed jax 0.9.0; verified by the runtime tests
_COMPILE_EVENT_SUBSTR = "backend_compile"


class CompileGuardError(AssertionError):
    """A guarded region compiled a new XLA program (steady-state recompile)."""


class SyncGuardError(AssertionError):
    """A guarded region exceeded its blocking device→host transfer budget."""


def _register_compile_listener(cb) -> Callable[[], None]:
    """Attach a jax monitoring duration listener; returns a detach callable.
    Detaching uses a private helper when available and otherwise leaves an
    inert listener behind (the callback checks an ``active`` flag)."""
    from jax import monitoring as _mon

    _mon.register_event_duration_secs_listener(cb)

    def detach() -> None:
        try:
            from jax._src import monitoring as _mon_impl

            _mon_impl._unregister_event_duration_listener_by_callback(cb)
        except Exception:  # pragma: no cover - future-jax fallback
            pass

    return detach


class CompileGuard:
    """Context manager asserting **zero** (or ``<= max_new``) new XLA
    compilations inside the guarded region.

    Three accounting modes, strongest available wins:

    - ``CompileGuard(f, g)`` — measured jit cache sizes of specific jitted
      callables (``f._cache_size()``), the serving tier's contract;
    - ``CompileGuard(sizer=lambda: gen.compiled_programs)`` — any callable
      returning a live compiled-program count;
    - ``CompileGuard()`` — global: counts jax's per-backend-compile
      monitoring events process-wide (what the training-loop and pod
      generation steady-state tests use).

    If an explicit mode's accounting API is missing (sentinel ``-1``), the
    guard falls back to global mode rather than silently passing.
    """

    def __init__(self, *jitted: Any, max_new: int = 0,
                 sizer: Optional[Callable[[], int]] = None,
                 label: str = "", registry: Any = None):
        if jitted and sizer is not None:
            raise ValueError("pass either jitted callables or sizer=, "
                             "not both")
        self._jitted = jitted
        self._sizer = sizer
        self.max_new = int(max_new)
        self.label = label
        self._registry = registry
        self._before: Optional[int] = None
        self._event_count = 0
        self._active = False
        self._detach: Optional[Callable[[], None]] = None
        self.new_compilations: Optional[int] = None

    # -- accounting --------------------------------------------------------- #
    def _measure(self) -> int:
        if self._sizer is not None:
            return int(self._sizer())
        if self._jitted:
            return measured_cache_size(*self._jitted)
        return -1  # global mode

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self._active and _COMPILE_EVENT_SUBSTR in event:
            self._event_count += 1

    # -- context protocol --------------------------------------------------- #
    def __enter__(self) -> "CompileGuard":
        self._before = self._measure()
        if self._before < 0:
            # global mode (requested, or the explicit accounting API is
            # gone): count compile monitoring events instead
            self._event_count = 0
            self._detach = _register_compile_listener(self._on_event)
        self._active = True
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._active = False
        where = f" [{self.label}]" if self.label else ""
        accounting_failure = None
        if self._before is not None and self._before >= 0:
            after = self._measure()
            if after < 0:
                # the accounting API vanished mid-region: we cannot prove
                # anything — fail loudly, never silently pass
                accounting_failure = (
                    "compiled-program accounting returned the -1 sentinel at "
                    "exit — cannot prove the region did not recompile")
                self.new_compilations = None
            elif after < self._before:
                accounting_failure = (
                    f"compiled-program count shrank {self._before}→{after} "
                    f"inside the guarded region (jax.clear_caches()? "
                    f"generator reset?) — accounting invalid, recompiles "
                    f"could hide behind the reset")
                self.new_compilations = None
            else:
                self.new_compilations = after - self._before
        else:
            self.new_compilations = self._event_count
            if self._detach is not None:
                self._detach()
                self._detach = None
        if self._registry is not None and self.new_compilations:
            self._registry.counter(
                "analysis/recompilations_total",
                help="new XLA programs observed inside CompileGuard regions",
            ).inc(self.new_compilations)
        if exc_type is None:
            if accounting_failure is not None:
                raise CompileGuardError(
                    f"CompileGuard{where}: {accounting_failure}")
            if self.new_compilations > self.max_new:
                raise CompileGuardError(
                    f"CompileGuard{where}: {self.new_compilations} new "
                    f"compiled program(s) in a region budgeted for "
                    f"{self.max_new} — steady-state recompilation "
                    f"(GX002 hazard)")
        return False


class _SyncPatch:
    """Process-wide patch of the blocking device→host conversion methods on
    ``jax.Array``; installed while at least one SyncGuard is active.
    Reference-counted so guards nest."""

    _lock = threading.Lock()
    _originals: dict = {}
    _guards: List["SyncGuard"] = []

    #: (attribute, is dunder) — the conversions GX001 flags statically,
    #: minus np.asarray (numpy reaches the array through the C buffer
    #: protocol, invisible to a Python-level patch; GX001 covers it)
    _METHODS = ("__float__", "__int__", "__bool__", "item", "tolist")

    @classmethod
    def _array_cls(cls):
        from jax._src import array as _array

        return _array.ArrayImpl

    @classmethod
    def attach(cls, guard: "SyncGuard") -> None:
        with cls._lock:
            if not cls._guards:
                impl = cls._array_cls()
                for name in cls._METHODS:
                    orig = getattr(impl, name, None)
                    if orig is None:  # pragma: no cover - future-jax rename
                        continue
                    cls._originals[name] = orig
                    setattr(impl, name, cls._wrap(name, orig))
            cls._guards.append(guard)

    @classmethod
    def detach(cls, guard: "SyncGuard") -> None:
        with cls._lock:
            if guard in cls._guards:
                cls._guards.remove(guard)
            if not cls._guards:
                impl = cls._array_cls()
                for name, orig in cls._originals.items():
                    setattr(impl, name, orig)
                cls._originals.clear()

    @classmethod
    def _wrap(cls, name: str, orig):
        def counting(self_array, *args, **kwargs):
            for g in list(cls._guards):
                g._record(name)
            return orig(self_array, *args, **kwargs)

        counting.__name__ = f"_syncguard_{name}"
        return counting


class SyncGuard:
    """Count blocking device→host transfers inside a region.

    ``max_syncs=None`` only counts (and emits ``analysis/host_syncs_total``
    when a registry is attached); an integer budget raises
    :class:`SyncGuardError` when exceeded. Counted conversions: ``float()``,
    ``int()``, ``bool()``, ``.item()``, ``.tolist()`` on any ``jax.Array`` —
    the same catalogue static rule GX001 flags. ``np.asarray`` copies are
    not countable from Python (C buffer path) and remain GX001's job.
    """

    def __init__(self, max_syncs: Optional[int] = None, label: str = "",
                 registry: Any = None):
        self.max_syncs = max_syncs
        self.label = label
        self._registry = registry
        self.syncs = 0
        self.by_kind: dict = {}

    def _record(self, kind: str) -> None:
        self.syncs += 1
        self.by_kind[kind] = self.by_kind.get(kind, 0) + 1

    def __enter__(self) -> "SyncGuard":
        self.syncs = 0
        self.by_kind = {}
        _SyncPatch.attach(self)
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        _SyncPatch.detach(self)
        if self._registry is not None and self.syncs:
            self._registry.counter(
                "analysis/host_syncs_total",
                help="blocking device->host transfers observed inside "
                     "SyncGuard regions",
            ).inc(self.syncs)
        if exc_type is None and self.max_syncs is not None \
                and self.syncs > self.max_syncs:
            where = f" [{self.label}]" if self.label else ""
            raise SyncGuardError(
                f"SyncGuard{where}: {self.syncs} blocking device→host "
                f"transfer(s) in a region budgeted for {self.max_syncs} "
                f"({self.by_kind}) — host-sync in a hot path (GX001 hazard)")
        return False
