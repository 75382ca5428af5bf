"""The one general traffic generator.  A mix is a data file
(``traffic/<mix>.json``); a new mix needs no code.

Reworked from ``scripts/bench_ragged.py::make_trace`` (Poisson
arrivals, shared-prefix templates, ragged budgets).  What changed: the
rate is a number in the mix file (found once by a sweep, see PERF.md),
not "4x capacity to saturate"; lengths are lognormal and clipped, as
served traffic is heavy-tailed; and the seed never changes the WORK
(the benchmark's contract: "give every seed the same set of sizes and
arrivals, in another order", so that runs of different seeds differ no
more than runs of one):

- every size (arrival gaps, prompt lengths, budgets, which shared
  prefix) is drawn from the mix's own ``sizes_seed``, so every run of a
  cell has the same multiset of sizes and, in an open loop, exactly the
  same number of requests due in the window;
- ``--seed`` only shuffles the order in which they come and draws the
  token ids.

The generator never reads the program's configuration: the vocabulary
size is handed in by the runner.

A mix's keys:
  loop          "open" (arrivals on a schedule) | "closed" (callers)
  rate_per_s    open: requests per second offered
  arrival_cv    open: coefficient of variation of the gaps between
                arrivals, which are gamma-distributed (default 1: Poisson
                arrivals; above 1: bursts)
  warm_seconds  open: traffic before the window (not measured)
  callers       closed: number of callers, each with one request out
  group         how many times in a row each prompt is asked (default 1)
  pool_groups   closed: how many distinct prompts the stream cycles over
  prefix        {"count", "tokens", "zipf_s"}: shared system prompts
  prompt        {"median", "sigma", "min", "max"}: the private part
  budget        {"median", "sigma", "min", "max"}: new tokens asked for
  sizes_seed    the fixed seed of the sizes
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List

import numpy as np


@dataclasses.dataclass
class Request:
    due_s: float            # open loop: seconds after traffic start
    prompt: np.ndarray      # int32 token ids
    budget: int
    prefix_id: int          # -1: no shared prefix
    measured: bool = True   # False: warm traffic before the window


def _lognormal(rs, spec: dict, n: int) -> np.ndarray:
    x = rs.lognormal(mean=np.log(float(spec["median"])),
                     sigma=float(spec["sigma"]), size=n)
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])
                   ).astype(np.int64)


def _gaps(rs, cv: float, n: int) -> np.ndarray:
    """n gamma-distributed gaps with mean 1 (scaled by the caller) and
    coefficient of variation ``cv``; at 1 they are exponential."""
    shape = 1.0 / (cv * cv)
    return rs.gamma(shape, 1.0 / shape, size=n)


def _prefix_ids(rs, prefix: dict, n: int) -> np.ndarray:
    if not prefix or int(prefix.get("count", 0)) < 1:
        return np.full(n, -1, np.int64)
    k = int(prefix["count"])
    p = 1.0 / np.arange(1, k + 1) ** float(prefix.get("zipf_s", 1.0))
    return rs.choice(k, size=n, p=p / p.sum())


def _sizes(rs, mix: dict, n: int) -> list:
    """n (prompt_len, budget, prefix_id) triples."""
    return list(zip(_lognormal(rs, mix["prompt"], n),
                    _lognormal(rs, mix["budget"], n),
                    _prefix_ids(rs, mix.get("prefix"), n)))


class _Tokens:
    """Token ids from ``--seed``: the shared prefixes once, private
    parts as asked."""

    def __init__(self, mix: dict, seed: int, vocab: int):
        self.rs = np.random.default_rng([int(seed), 0x7045])
        self.vocab = int(vocab)
        prefix = mix.get("prefix") or {}
        self.prefixes = [self.ids(int(prefix["tokens"]))
                         for _ in range(int(prefix.get("count", 0)))]

    def ids(self, n: int) -> np.ndarray:
        return self.rs.integers(2, self.vocab, size=int(n), dtype=np.int32)

    def prompt(self, private_len: int, prefix_id: int) -> np.ndarray:
        private = self.ids(private_len)
        if prefix_id < 0:
            return private
        return np.concatenate([self.prefixes[prefix_id], private])


def shared_prefixes(mix: dict, seed: int, vocab: int) -> List[np.ndarray]:
    """The shared system prompts a run with this seed will send (so a
    runner can put them into the prefix cache while it warms up)."""
    return _Tokens(mix, seed, vocab).prefixes


def _phase(mix: dict, sizes_rs, order_rs, seconds: float) -> list:
    """(offset_s, prompt_len, budget, prefix_id) of one stretch of an
    open loop: round(rate * seconds) requests whose gaps sum to
    ``seconds``; sizes from ``sizes_rs``, order from ``order_rs``."""
    if mix.get("rate_per_s") is None:
        raise ValueError(f"mix {mix.get('name')!r} has no rate_per_s yet: "
                         "find its knee with sweep.py")
    n = int(round(float(mix["rate_per_s"]) * seconds))
    if n < 1:
        return []
    gaps = _gaps(sizes_rs, float(mix.get("arrival_cv", 1.0)), n)
    gaps *= seconds / gaps.sum()
    sizes = _sizes(sizes_rs, mix, n)
    gaps = gaps[order_rs.permutation(n)]
    sizes = [sizes[i] for i in order_rs.permutation(n)]
    due = np.cumsum(gaps) - gaps[0] * 0.5   # first one half a gap in
    return [(float(t), *s) for t, s in zip(due, sizes)]


def open_schedule(mix: dict, seed: int, seconds: float,
                  vocab: int) -> List[Request]:
    """Every request of an open-loop run: ``warm_seconds`` of unmeasured
    traffic, then the window.  ``due_s`` counts from traffic start; the
    window opens at ``warm_seconds``."""
    sizes_rs = np.random.RandomState(int(mix["sizes_seed"]))
    order_rs = np.random.default_rng([int(seed), 0x0DE4])
    tokens = _Tokens(mix, seed, vocab)
    warm = float(mix.get("warm_seconds", 0.0))
    out: List[Request] = []
    for t0, length, measured in ((0.0, warm, False), (warm, seconds, True)):
        for t, plen, budget, pid in _phase(mix, sizes_rs, order_rs, length):
            out.append(Request(t0 + t, tokens.prompt(plen, pid),
                               int(budget), int(pid), measured))
    return out


def closed_stream(mix: dict, seed: int, vocab: int) -> Iterator[Request]:
    """The endless stream a closed loop's callers draw from: each
    distinct prompt ``group`` times in a row (one budget per clone),
    cycling over ``pool_groups`` prompts in an order set by ``--seed``."""
    sizes_rs = np.random.RandomState(int(mix["sizes_seed"]))
    order_rs = np.random.default_rng([int(seed), 0x0DE4])
    tokens = _Tokens(mix, seed, vocab)
    group = int(mix.get("group", 1))
    n = int(mix["pool_groups"])
    plens = _lognormal(sizes_rs, mix["prompt"], n)
    pids = _prefix_ids(sizes_rs, mix.get("prefix"), n)
    budgets = _lognormal(sizes_rs, mix["budget"], n * group).reshape(n, group)
    while True:
        for g in order_rs.permutation(n):
            prompt = tokens.prompt(int(plens[g]), int(pids[g]))
            for j in range(group):
                yield Request(0.0, prompt, int(budgets[g, j]), int(pids[g]))
