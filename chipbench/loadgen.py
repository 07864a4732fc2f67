"""The one general traffic generator: a mix file of parameters plus a seed.

A traffic mix (`chipbench/traffic/<mix>.json`) names its `loop`:

- `open`: Poisson arrivals at `rate_per_s`, timed from each request's due
  instant, whatever the server does;
- `burst`: every `period_ms`, `burst` requests due at once, each within
  +-`jitter_ms` of the tick (genlocked cameras);
- `closed`: `in_flight` clients, each sending its next request when the
  previous one is answered.

`mix` lists the request kinds, each an image name of the configuration, a
filter and a `share`. Every seed gets the same work in another order: the
kinds come in exact shares, and the inter-arrival gaps and jitters are the
same stratified set of quantiles, shuffled by the seed. So two seeds
differ by order alone, and a window of `seconds` holds the same number of
requests whatever the seed.

The loops submit through a callable and stamp each request's due, sent
and done instants on one monotonic clock; the latency a user feels is
done - due, so a stall that delays later sends still counts.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import threading
import time
from typing import Callable

import numpy as np

#: how long the generator waits for one answer past the window's close.
RESULT_TIMEOUT_S = 60.0

#: length of the closed loop's repeating table of kinds and images.
CLOSED_TABLE = 4096


@dataclasses.dataclass
class Request:
    """One request of a run: what it asks for and when."""

    idx: int
    kind: int                  # index into the mix
    image: int                 # index into the kind's image pool
    due: float                 # seconds after the window opens
    sent: float = math.nan     # clock instants
    done: float = math.nan
    error: str | None = None

    @property
    def ok(self) -> bool:
        return self.error is None and not math.isnan(self.done)


def exact_counts(shares: list[float], n: int) -> list[int]:
    """Split `n` by `shares` into whole counts (largest remainder)."""
    total = float(sum(shares))
    raw = [n * s / total for s in shares]
    counts = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: counts[i] - raw[i])
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return counts


def kind_sequence(mix: list[dict], n: int, rng: np.random.Generator
                  ) -> np.ndarray:
    """`n` kind indices in exact shares, in a seeded order."""
    counts = exact_counts([m["share"] for m in mix], n)
    kinds = np.repeat(np.arange(len(mix)), counts)
    return rng.permutation(kinds)


def image_sequence(n: int, pool: int, rng: np.random.Generator) -> np.ndarray:
    """`n` image indices that use the pool evenly, in a seeded order."""
    return rng.permutation(np.arange(n) % pool)


def schedule(traffic: dict, seconds: float, pool: int, seed: int
             ) -> list[Request]:
    """The open or burst loop's requests due in a window of `seconds`."""
    rng = np.random.default_rng(int(seed))
    loop = traffic["loop"]
    if loop == "open":
        rate = float(traffic["rate_per_s"])
        n = max(1, int(round(rate * seconds)))
        # the exponential's quantiles at the midpoints of n equal strata
        q = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-q) / rate)
        dues = np.cumsum(gaps)
    elif loop == "burst":
        period = float(traffic["period_ms"]) / 1e3
        burst = int(traffic["burst"])
        jitter = float(traffic["jitter_ms"]) / 1e3
        ticks = max(1, int(seconds / period))
        n = ticks * burst
        q = (np.arange(n) + 0.5) / n
        offsets = rng.permutation(-jitter + 2 * jitter * q)
        dues = (np.repeat(np.arange(ticks), burst) * period + jitter
                + offsets)
        dues = np.sort(dues)
    else:
        raise ValueError(f"schedule() serves the open and burst loops, "
                         f"not {loop!r}")
    kinds = kind_sequence(traffic["mix"], n, rng)
    images = image_sequence(n, pool, rng)
    return [Request(i, int(k), int(m), float(d))
            for i, (k, m, d) in enumerate(zip(kinds, images, dues))]


def closed_tables(traffic: dict, pool: int, seed: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """The closed loop's repeating (kinds, images) tables; request i takes
    entry i % CLOSED_TABLE."""
    rng = np.random.default_rng(int(seed))
    return (kind_sequence(traffic["mix"], CLOSED_TABLE, rng),
            image_sequence(CLOSED_TABLE, pool, rng))


Submit = Callable[[Request], object]   # -> a future with done(), result()
Answer = Callable[[Request, object], None]


class Poller:
    """Outstanding requests, checked every `POLL_S` by one thread: a done
    future stamps its request's `done` and hands the answer on. One polling
    thread stands in for a thread per waiting client, so the load generator
    takes the interpreter lock from the server as little as it can; the
    stamp is late by at most one poll interval."""

    def __init__(self, on_answer: Answer, clock) -> None:
        self.on_answer = on_answer
        self.clock = clock
        self._lock = threading.Lock()
        self._waiting: list[tuple[Request, object]] = []

    def add(self, req: Request, fut) -> None:
        with self._lock:
            self._waiting.append((req, fut))

    def __len__(self) -> int:
        return len(self._waiting)

    def poll(self) -> list[Request]:
        """Stamp and hand on every answer that came; returns their requests."""
        with self._lock:
            waiting, self._waiting = self._waiting, []
        came, still = [], []
        for req, fut in waiting:
            if fut.done():
                came.append((req, fut))
            elif self.clock() - req.sent > RESULT_TIMEOUT_S:
                req.done = self.clock()
                req.error = "TimeoutError: no answer"
            else:
                still.append((req, fut))
        if still:
            with self._lock:
                self._waiting[:0] = still
        now = self.clock()
        for req, fut in came:
            req.done = now
            try:
                out = fut.result(0)
            except Exception as err:          # noqa: BLE001 -- counted
                req.error = f"{type(err).__name__}: {err}"
                continue
            self.on_answer(req, out)
        return [req for req, _ in came]


#: how often the poller looks at the outstanding requests.
POLL_S = 0.0002


def _send(req: Request, submit: Submit, poller: Poller, clock) -> None:
    if math.isnan(req.sent):
        req.sent = clock()
    try:
        fut = submit(req)
    except Exception as err:                  # noqa: BLE001 -- counted
        req.done = clock()
        req.error = f"{type(err).__name__}: {err}"
        return
    poller.add(req, fut)


def run_scheduled(reqs: list[Request], submit: Submit, on_answer: Answer,
                  t0: float, clock=time.monotonic) -> None:
    """Send each request at its due instant from this thread while one
    polling thread collects the answers. Returns when every request is
    answered, failed or timed out."""
    poller = Poller(on_answer, clock)
    sending = threading.Event()
    sending.set()

    def poll_loop() -> None:
        while sending.is_set() or len(poller):
            if not poller.poll():
                time.sleep(POLL_S)

    thread = threading.Thread(target=poll_loop, name="chipbench-poll",
                              daemon=True)
    thread.start()
    try:
        for req in reqs:
            delay = t0 + req.due - clock()
            if delay > 0:
                time.sleep(delay)
            _send(req, submit, poller, clock)
    finally:
        sending.clear()
        thread.join()


def run_closed(tables: tuple[np.ndarray, np.ndarray], clients: int,
               submit: Submit, on_answer: Answer, t0: float, t_end: float,
               clock=time.monotonic) -> list[Request]:
    """`clients` requests kept in flight from this one thread: each answer
    sends the next request, until `t_end`; returns every request sent, in
    send order."""
    kinds, images = tables
    counter = itertools.count()
    sent: list[Request] = []
    poller = Poller(on_answer, clock)

    def send_next() -> None:
        i = next(counter)
        now = clock()
        req = Request(i, int(kinds[i % len(kinds)]),
                      int(images[i % len(images)]), now - t0, sent=now)
        sent.append(req)
        _send(req, submit, poller, clock)

    for _ in range(clients):
        send_next()
    while len(poller):
        came = poller.poll()
        if not came:
            time.sleep(POLL_S)
            continue
        for _ in came:
            if clock() < t_end:
                send_next()
    return sent
