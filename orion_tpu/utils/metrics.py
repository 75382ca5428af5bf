"""Metrics/logging (SURVEY.md §2 #18, §5): metric stream → jsonl file
(always) + tensorboard event files via clu when available.

The BASELINE metric — samples/sec (rollout+update) — is first-class:
BaseTrainer computes it every iteration and this writer just persists
whatever dict it gets, so new metrics need no plumbing.

Beyond bare scalars (ISSUE 9), values may be:

- :class:`Counter` — a monotonic event count, written as its value;
- :class:`Histogram` — an observation log, expanded into
  ``<name>_p50/_p95/_p99/_mean/_count`` columns (the serving
  latency-distribution shape: queue wait, TTFT, tok/s);
- ``str`` — jsonl-only annotation (e.g. the profiler trace dir
  surfaced in the final row); tensorboard sees numerics only.

Lifecycle (ISSUE 9 satellite): the writer is a context manager,
``close()`` is idempotent and actually closes the tensorboard writer
(the old code only flushed it), and a failure mid-``__init__`` no
longer leaks the jsonl handle.  ``BaseTrainer.close()`` routes every
trainer/orchestrator exit through it.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Dict, Optional


class Counter:
    """Monotonic event count.  ``add`` from any thread is fine for
    telemetry purposes (a lost increment under a race is noise, never
    corruption)."""

    __slots__ = ("value",)

    def __init__(self, value: float = 0):
        self.value = value

    def add(self, n: float = 1) -> float:
        self.value += n
        return self.value


class Histogram:
    """Observation log with nearest-rank percentile summaries.

    Memory is bounded: past ``max_samples`` the log becomes a ring
    over the most recent observations (deterministic — no reservoir
    randomness to perturb seeded runs), while ``count``/``mean`` stay
    exact over everything ever recorded.
    """

    __slots__ = ("_vals", "_max", "count", "total")

    def __init__(self, max_samples: int = 100_000):
        if max_samples < 1:
            raise ValueError(f"max_samples must be >= 1, got {max_samples}")
        self._vals: list = []
        self._max = max_samples
        self.count = 0
        self.total = 0.0

    def record(self, value: float) -> None:
        v = float(value)
        if len(self._vals) < self._max:
            self._vals.append(v)
        else:  # ring over the most recent window
            self._vals[self.count % self._max] = v
        self.count += 1
        self.total += v

    @property
    def mean(self) -> float:
        return self.total / self.count if self.count else 0.0

    @staticmethod
    def _rank(s: list, q: float) -> float:
        k = min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))
        return s[k]

    def percentile(self, q: float) -> float:
        """Nearest-rank percentile over the retained window (0 when
        empty)."""
        if not self._vals:
            return 0.0
        return self._rank(sorted(self._vals), q)

    def summary(self, prefix: str) -> Dict[str, float]:
        """The p50/p95/p99 + mean/count expansion MetricsWriter (and
        the bench JSON lines) write.  One sort serves all three
        ranks — summary() runs per metrics row over up-to-100k-sample
        windows."""
        s = sorted(self._vals)
        return {
            f"{prefix}_p50": self._rank(s, 50) if s else 0.0,
            f"{prefix}_p95": self._rank(s, 95) if s else 0.0,
            f"{prefix}_p99": self._rank(s, 99) if s else 0.0,
            f"{prefix}_mean": self.mean,
            f"{prefix}_count": float(self.count),
        }


class MetricsWriter:
    """Append-only jsonl + optional tensorboard scalars."""

    def __init__(self, directory: str, tensorboard: bool = True):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._closed = False
        self._jsonl = open(os.path.join(self.directory, "metrics.jsonl"),
                           "a")
        self._tb = None
        try:
            if tensorboard:
                try:
                    from clu import metric_writers

                    self._tb = metric_writers.SummaryWriter(self.directory)
                except Exception:
                    self._tb = None  # clu/tensorboard unavailable: jsonl only
        except BaseException:
            # Partial construction must not leak the jsonl handle (the
            # old writer left it open with no owner).
            self._jsonl.close()
            self._closed = True
            raise

    def write(self, step: int, scalars: dict,
              jsonl_only: bool = False) -> None:
        """``jsonl_only``: a row that is no point of the iterations'
        series (the ``setup`` row shares keys and a step with one)."""
        if self._closed:
            raise ValueError("MetricsWriter is closed")
        numeric: Dict[str, float] = {}
        annot: Dict[str, str] = {}
        for k, v in scalars.items():
            if isinstance(v, Histogram):
                numeric.update({kk: float(x)
                                for kk, x in v.summary(k).items()})
            elif isinstance(v, Counter):
                numeric[k] = float(v.value)
            elif isinstance(v, (int, float)) or _is_scalar_like(v):
                numeric[k] = float(v)
            elif isinstance(v, (str, list, dict)):
                # jsonl-only (a profile trace dir, the setup row's tables)
                annot[k] = v
        rec = {"step": int(step), "time": time.time(), **numeric, **annot}
        self._jsonl.write(json.dumps(rec) + "\n")
        self._jsonl.flush()
        if self._tb is not None and numeric and not jsonl_only:
            self._tb.write_scalars(int(step), numeric)

    def close(self) -> None:
        """Idempotent; closes BOTH sinks (the old close() flushed the
        tensorboard writer but never closed it — its event-file handle
        leaked for the process lifetime)."""
        if self._closed:
            return
        self._closed = True
        self._jsonl.close()
        if self._tb is not None:
            self._tb.flush()
            close_fn = getattr(self._tb, "close", None)
            if close_fn is not None:
                try:
                    close_fn()
                except Exception:  # pragma: no cover - clu teardown quirk
                    pass
            self._tb = None

    @property
    def closed(self) -> bool:
        return self._closed

    def __enter__(self) -> "MetricsWriter":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.close()
        return False


def _is_scalar_like(v) -> bool:
    try:
        float(v)
        return getattr(v, "size", 1) == 1
    except Exception:
        return False
