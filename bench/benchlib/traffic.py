"""Traffic: cloud pools and sizes from a seed, and the closed loop.

A mix file (`bench/traffic/<mix>.json`) holds:

* "loop": "closed", with "clients";
* "sizes": {"kind": "fixed", "points": n} or {"kind": "loguniform",
  "low": a, "high": b};
* "pool": how many distinct clouds the run makes and cycles through;
* "features" (default 0): per-point channels beyond xyz, colour-like values
  in [0, 1]. They come from a key of their own, so a mix's xyz is the same
  with them or without.

Every seed gets the same set of sizes (quantiles of the distribution); the
seed draws the shapes and the order. So the work of a run does not depend
on its seed.
"""

from __future__ import annotations

import dataclasses
import functools
import queue
import threading
import time
from typing import Callable

import jax
import numpy as np

from benchlib import shapes

DRAIN_S = 60.0  # wait this long past the window for answers still due


def jax_key(seed: int):
    """A PRNG key from any non-negative seed, 64-bit ones included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)), seed >> 31)


def cloud_sizes(sizes: dict, count: int) -> np.ndarray:
    """The fixed multiset of `count` cloud sizes a mix asks for."""
    if sizes["kind"] == "fixed":
        return np.full(count, int(sizes["points"]), np.int64)
    if sizes["kind"] == "loguniform":
        q = (np.arange(count) + 0.5) / count
        lo, hi = float(sizes["low"]), float(sizes["high"])
        return np.round(lo * (hi / lo) ** q).astype(np.int64)
    raise ValueError(f"unknown size distribution {sizes['kind']!r}")


def make_pool(mix: dict, seed: int) -> list[np.ndarray]:
    """The run's distinct clouds, (n, 3 + features), in the seed's order, made on the device."""
    count = int(mix["pool"])
    rng = np.random.default_rng(seed)
    sizes = rng.permutation(cloud_sizes(mix["sizes"], count))
    n_max = int(sizes.max())
    pts = np.asarray(shapes.clouds(jax_key(seed), count, n_max))
    channels = int(mix.get("features", 0))
    if channels:
        key = jax.random.fold_in(jax_key(seed), 1)
        pts = np.concatenate([pts, np.asarray(shapes.features(key, count, n_max, channels))], -1)
    return [np.ascontiguousarray(pts[i, : sizes[i]]) for i in range(count)]


@dataclasses.dataclass
class LoopResult:
    """What a loop observed, one array entry per request, and its window.

    Times are `time.perf_counter()` seconds. `ok` is False for a request
    that failed, was refused at admission, or had no answer by the drain's
    end; `errors` says why for each of those.
    """

    done: np.ndarray
    ok: np.ndarray
    errors: dict[int, str]
    start: float
    end: float
    answers: dict[int, np.ndarray]  # cloud slot -> last answer of that slot

    @property
    def attempted(self) -> int:
        """Requests sent."""
        return len(self.done)

    def completed_in_window(self) -> int:
        """Requests that completed with an answer before the window closed."""
        return int(np.sum(self.ok & (self.done <= self.end)))

    def failed(self) -> int:
        """Requests that failed, were refused, or never answered."""
        return int(np.sum(~self.ok))


class _Client:
    """Submits requests and records their completions.

    Completion times go into a preallocated array and finished futures are
    dropped, so the loop adds few objects for the garbage collector to walk.
    """

    def __init__(self, submit: Callable, pool: list[np.ndarray], keep: set[int]):
        self.submit = submit
        self.pool = pool
        self.keep = keep
        self.n = 0
        self.times = np.zeros(1 << 15)  # completion time, 0 until answered
        self.errors: dict[int, str] = {}
        self.answers: dict[int, np.ndarray] = {}
        self.done: queue.SimpleQueue = queue.SimpleQueue()
        self.cond = threading.Condition()
        self.pending = 0

    def issue(self) -> None:
        i = self.n
        if i == self.times.size:
            with self.cond:  # callbacks write the array being replaced
                self.times = np.concatenate([self.times, np.zeros_like(self.times)])
        self.n += 1
        with self.cond:
            self.pending += 1
        try:
            with jax.profiler.TraceAnnotation("bench.submit"):
                fut = self.submit(self.pool[i % len(self.pool)])
        except Exception as e:  # noqa: BLE001 - refused at admission
            self._finish(None, i, f"refused: {e!r}")
            return
        fut.add_done_callback(functools.partial(self._finish, index=i))

    def _finish(self, fut, index: int, error: str | None = None) -> None:
        t = time.perf_counter()
        if fut is not None:
            if fut.cancelled():
                error = "cancelled"
            elif fut.exception() is not None:
                error = repr(fut.exception())
        with self.cond:
            if error is None:
                self.times[index] = t
                if index % len(self.pool) in self.keep:
                    self.answers[index % len(self.pool)] = fut.result()
            else:
                self.errors[index] = error
            self.pending -= 1
            self.cond.notify_all()
        self.done.put(index)

    def result(self, start: float, end: float) -> LoopResult:
        """Wait up to `DRAIN_S` for answers still due, then freeze the record."""
        with self.cond:
            self.cond.wait_for(lambda: self.pending == 0, timeout=DRAIN_S)
            done = self.times[: self.n].copy()
            errors = dict(self.errors)
            answers = dict(self.answers)
        ok = done > 0
        for i in np.flatnonzero(~ok):
            errors.setdefault(int(i), "no answer within the drain time")
        return LoopResult(done, ok, errors, start, end, answers)


def closed_loop(
    submit: Callable, pool: list[np.ndarray], clients: int, seconds: float,
    keep: set[int], on_start: Callable[[float], None] | None = None,
    on_end: Callable[[], None] | None = None,
) -> LoopResult:
    """`clients` clients each send their next cloud when the last is answered.

    `on_start(t0)` runs as the window opens, `on_end()` as it closes, before
    the answers still due are awaited.
    """
    c = _Client(submit, pool, keep)
    start = time.perf_counter()
    end = start + seconds
    if on_start is not None:
        on_start(start)
    for _ in range(clients):
        c.issue()
    while True:
        left = end - time.perf_counter()
        if left <= 0:
            break
        try:
            c.done.get(timeout=left)
        except queue.Empty:
            break
        if time.perf_counter() < end:
            c.issue()
    if on_end is not None:
        on_end()
    return c.result(start, end)
