"""Runtime complements to the static rules: the failure modes only
visible while a program is actually running.

- :class:`RecompileSentinel` — counts XLA compilations per jitted
  function and warns once a function recompiles past its budget.  It
  reads the process's compile watch (``orion_tpu/obs/compilewatch.py``:
  one pair of ``jax.monitoring`` listeners whose duration events carry
  ``fun_name`` on this jax), holding it while installed.  A
  silently-unhashable static arg or a shape that changes every step
  turns a 2 ms train step into a minutes-long compile loop — on a TPU
  pod that is the single most expensive silent failure.
- :func:`guard_scope` — opt-in ``jax.transfer_guard`` wiring for the
  trainers (TrainConfig.transfer_guard): "log" prints every *implicit*
  host transfer inside the training loop, "disallow" raises on them.
  Explicit ``jax.device_get`` fetches (the deliberate once-per-step
  sync) stay allowed either way.
"""

from __future__ import annotations

import contextlib
import threading
import warnings
from typing import Dict, Optional


class RecompileSentinel:
    """Warns when any single jitted function compiles more than
    ``budget`` times.

    ``counts[name]`` is the number of times ``jit(name)`` was LOWERED
    while the sentinel was installed (where jax's "Compiling <name> with
    global shapes" line is written: a ``.lower()`` that is never
    compiled counts too), ``total_compiles`` the number of backend
    compiles.

    Usage::

        sentinel = RecompileSentinel(budget=3).install()
        ...  # train
        sentinel.uninstall()
        sentinel.counts  # {fun_name: n_compiles}
    """

    def __init__(self, budget: int = 3):
        self.budget = int(budget)
        self.counts: Dict[str, int] = {}
        self.total_compiles = 0
        self._lock = threading.Lock()
        self._warned: set = set()
        self._hold = None

    def _on_compile_event(self, kind: str, name: str) -> None:
        """The watch's observer: called on the compiling thread after
        every trace, lowering and backend compile."""
        if kind == "trace":
            return
        with self._lock:
            if kind == "backend":
                self.total_compiles += 1
                return
            self.counts[name] = n = self.counts.get(name, 0) + 1
            fire = n > self.budget and name not in self._warned
            if fire:
                self._warned.add(name)
        if fire:
            warnings.warn(
                f"[orion-tpu recompile sentinel] {name!r} compiled "
                f"{n} times (budget {self.budget}) — look for an "
                "unhashable/varying static arg or a shape that changes "
                "per step", RuntimeWarning, stacklevel=2)

    # -- lifecycle ------------------------------------------------------
    @property
    def installed(self) -> bool:
        return self._hold is not None

    def install(self) -> "RecompileSentinel":
        from orion_tpu import obs

        if self._hold is None:
            self._hold = obs.install_compile_watch(self._on_compile_event)
        return self

    def uninstall(self) -> None:
        hold, self._hold = self._hold, None
        if hold is not None:
            hold.uninstall()


@contextlib.contextmanager
def guard_scope(transfer_guard: Optional[str] = None):
    """Context for a training loop body: applies
    ``jax.transfer_guard(level)`` when a level is configured, a no-op
    otherwise.  Levels: "log" (print implicit transfers), "disallow"
    (raise on them), "allow" / None (off).  The trainers pass
    ``TrainConfig.transfer_guard`` straight through."""
    if transfer_guard in (None, "", "allow"):
        yield
        return
    import jax

    with jax.transfer_guard(transfer_guard):
        yield


def install_from_config(cfg) -> Optional[RecompileSentinel]:
    """TrainConfig wiring: a positive ``recompile_budget`` installs a
    sentinel (caller keeps it to uninstall/inspect); 0 disables."""
    budget = int(getattr(cfg, "recompile_budget", 0) or 0)
    if budget <= 0:
        return None
    return RecompileSentinel(budget=budget).install()
