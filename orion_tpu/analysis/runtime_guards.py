"""Runtime complements to the static rules: the failure modes only
visible while a program is actually running.

- :class:`RecompileSentinel` — counts XLA compilations per jitted
  function (via ``jax_log_compiles`` log records, which carry the
  function name on this jax; a ``jax.monitoring`` duration listener
  keeps the global count as a cross-check) and warns once a function
  recompiles past its budget.  A silently-unhashable static arg or a
  shape that changes every step turns a 2 ms train step into a
  minutes-long compile loop — on a TPU pod that is the single most
  expensive silent failure.
- :func:`guard_scope` — opt-in ``jax.transfer_guard`` wiring for the
  trainers (TrainConfig.transfer_guard): "log" prints every *implicit*
  host transfer inside the training loop, "disallow" raises on them.
  Explicit ``jax.device_get`` fetches (the deliberate once-per-step
  sync) stay allowed either way.
"""

from __future__ import annotations

import contextlib
import logging
import re
import threading
import warnings
from typing import Dict, Optional

# jax logs "Compiling jit(<name>) with global shapes ..."; counts are
# keyed by the bare function name, so the jit(...) wrapper is stripped.
_COMPILE_RE = re.compile(
    r"^Compiling (?:jit\()?([^\s()]+)\)? with global shapes")

# jax_log_compiles emits through child loggers of "jax"
# (jax._src.interpreters.pxla); attaching to the parent survives the
# module moving.
_JAX_LOGGER = "jax"

# Shared install state: refcounted so two live sentinels don't fight —
# the FIRST install snapshots jax_log_compiles, the LAST uninstall
# restores it (a per-sentinel snapshot would record the first
# sentinel's True and make the original value unrecoverable).  The
# jax.monitoring API has no unregister, so exactly ONE listener is
# ever registered; it dispatches to whatever sentinels are active.
_shared_lock = threading.Lock()
_active_sentinels: set = set()
_prev_log_compiles: Optional[bool] = None
_monitor_registered = False


def _on_compile_duration(event: str, duration: float, **kw) -> None:
    if not event.endswith("backend_compile_duration"):
        return
    with _shared_lock:
        targets = list(_active_sentinels)
    for s in targets:
        with s._lock:
            s.total_compiles += 1


class RecompileSentinel(logging.Handler):
    """Warns when any single jitted function compiles more than
    ``budget`` times.

    Usage::

        sentinel = RecompileSentinel(budget=3).install()
        ...  # train
        sentinel.uninstall()
        sentinel.counts  # {fun_name: n_compiles}
    """

    def __init__(self, budget: int = 3):
        super().__init__(level=logging.DEBUG)
        self.budget = int(budget)
        self.counts: Dict[str, int] = {}
        self.total_compiles = 0
        self._lock = threading.Lock()
        self._warned: set = set()
        self._installed = False

    # -- logging.Handler ------------------------------------------------
    def emit(self, record: logging.LogRecord) -> None:
        try:
            m = _COMPILE_RE.match(record.getMessage())
        except Exception:  # pragma: no cover - malformed record
            return
        if not m:
            return
        name = m.group(1)
        with self._lock:
            self.counts[name] = self.counts.get(name, 0) + 1
            n = self.counts[name]
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
    def install(self) -> "RecompileSentinel":
        global _prev_log_compiles, _monitor_registered
        import jax

        if self._installed:
            return self
        with _shared_lock:
            if not _active_sentinels:
                _prev_log_compiles = bool(jax.config.jax_log_compiles)
            _active_sentinels.add(self)
            register_monitor = not _monitor_registered
            _monitor_registered = True
        jax.config.update("jax_log_compiles", True)
        logging.getLogger(_JAX_LOGGER).addHandler(self)
        if register_monitor:
            # Global compile count via jax.monitoring: no per-function
            # metadata on this jax, but it catches compiles that bypass
            # the log path.
            try:
                import jax.monitoring as monitoring

                monitoring.register_event_duration_secs_listener(
                    _on_compile_duration)
            except Exception:  # pragma: no cover - monitoring moved
                pass
        self._installed = True
        return self

    def uninstall(self) -> None:
        import jax

        if not self._installed:
            return
        logging.getLogger(_JAX_LOGGER).removeHandler(self)
        with _shared_lock:
            _active_sentinels.discard(self)
            restore = not _active_sentinels
        if restore and _prev_log_compiles is not None:
            jax.config.update("jax_log_compiles", _prev_log_compiles)
        self._installed = False


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
