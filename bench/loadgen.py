"""Traffic: a mix's arrival schedule and the loops that drive a server.

The Poisson schedule is the one of the program's `serving/load_gen.py`
(exponential gaps at the offered rate over a fixed horizon), kept here so
that the yardstick cannot change with the program. Both loops stamp every
request with the time it was due; the front end's own resolve stamps
(`take_trace`) give the time it came back.

* open loop: requests are due on the schedule, whether or not the server
  keeps up. One thread submits them and, every `HARVEST_S`, takes the
  resolved tickets' records and redeems their results, as a client would
  (so the front end holds no window's worth of results); the only host
  threads are the generator and the server's.
* closed loop: `outstanding` clients each send their next request the
  moment their last one resolves.

The records live in flat arrays (`Records`), so the window can run with
the garbage collector on, as a deployment does, without the benchmark's
own bookkeeping giving it anything to walk.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

# how often the trace records are taken (and, in the open loop, results
# redeemed) while serving: often, so that each harvest is short and the
# generator keeps to its schedule
HARVEST_S = 0.01
# the draws every run's traffic is made of; the run's seed orders them
DRAWS_SEED = 20231
USER_CYCLE = 4096  # users a closed loop's clients go through per pass
PENDING, OK, FAILED = 0, 1, 2  # status codes: unresolved, served, not
_COLUMNS = (("ticket", np.int64, -1), ("user", np.int64, -1),
            ("due", np.float64, np.nan), ("submit", np.float64, np.nan),
            ("done", np.float64, np.nan), ("status", np.int8, PENDING),
            ("redeemed", np.bool_, False))


def _extend(a: np.ndarray, rows: int, fill) -> np.ndarray:
    out = np.full((rows,) + a.shape[1:], fill, a.dtype)
    out[:len(a)] = a
    return out


class Records:
    """The requests of one window, a row each, in flat arrays.

    Columns (read as attributes, `n` rows long): ``ticket``, ``user``,
    ``due`` (perf_counter when the request was due), ``submit`` (when it
    was handed to the server), ``done`` (its resolve stamp; NaN until it
    resolves), ``status`` (`PENDING`, `OK` or `FAILED`) and ``redeemed``;
    `stage(name)` gives the stamps of one stage of the span chains (NaN
    where a chain lacks it), and ``items`` / ``scores`` the redeemed
    results (-1 / NaN until redeemed).
    """

    def __init__(self, capacity: int = 4096):
        self.n = 0
        self.cap = max(int(capacity), 1)
        self.index: dict[int, int] = {}  # ticket -> row, until resolved
        self._cols = {name: np.full(self.cap, fill, dtype)
                      for name, dtype, fill in _COLUMNS}
        self._stages: dict[str, np.ndarray] = {}
        self._items = self._scores = None

    def __getattr__(self, name):
        cols = self.__dict__.get("_cols", {})
        if name in cols:
            return cols[name][:self.n]
        raise AttributeError(name)

    @property
    def items(self):
        return None if self._items is None else self._items[:self.n]

    @property
    def scores(self):
        return None if self._scores is None else self._scores[:self.n]

    def stage(self, name: str):
        col = self._stages.get(name)
        return None if col is None else col[:self.n]

    def __len__(self) -> int:
        return self.n

    def _grow(self) -> None:
        self.cap *= 2
        for name, _, fill in _COLUMNS:
            self._cols[name] = _extend(self._cols[name], self.cap, fill)
        self._stages = {k: _extend(v, self.cap, np.nan)
                        for k, v in self._stages.items()}
        if self._items is not None:
            self._items = _extend(self._items, self.cap, -1)
            self._scores = _extend(self._scores, self.cap, np.nan)

    def add(self, ticket: int, user: int, due: float, submit: float) -> int:
        """A new request's row."""
        if self.n == self.cap:
            self._grow()
        i, c = self.n, self._cols
        c["ticket"][i], c["user"][i] = ticket, user
        c["due"][i], c["submit"][i] = due, submit
        self.index[ticket] = i
        self.n += 1
        return i

    def resolve(self, rows, done, status, chains) -> None:
        """The resolve stamps, statuses ("ok" or another) and span chains
        of `rows`, one list entry a row."""
        rows = np.asarray(rows, np.int64)
        self._cols["done"][rows] = done
        self._cols["status"][rows] = np.where(
            np.asarray(status) == "ok", OK, FAILED)
        stamps: dict[str, tuple[list, list]] = {}
        for row, chain in zip(rows.tolist(), chains):
            for name, t in chain:
                at, ts = stamps.setdefault(name, ([], []))
                at.append(row)
                ts.append(t)
        for name, (at, ts) in stamps.items():
            col = self._stages.get(name)
            if col is None:
                col = self._stages[name] = np.full(self.cap, np.nan)
            col[at] = ts

    def store(self, rows, items, scores) -> None:
        """The redeemed results of `rows`, one list entry a row."""
        if self._items is None:
            k = len(items[0])
            self._items = np.full((self.cap, k), -1, np.int64)
            self._scores = np.full((self.cap, k), np.nan, np.float32)
        self._items[rows], self._scores[rows] = np.stack(items), \
            np.stack(scores)
        self._cols["redeemed"][rows] = True

    def take(self, rows) -> "Records":
        """The records of `rows` (indices or a mask), as new records."""
        out = Records.__new__(Records)
        out.index = {}
        out._cols = {k: v[:self.n][rows] for k, v in self._cols.items()}
        out.n = out.cap = len(out._cols["ticket"])
        out._stages = {k: v[:self.n][rows] for k, v in self._stages.items()}
        out._items = None if self._items is None else self.items[rows]
        out._scores = None if self._scores is None else self.scores[rows]
        return out


def subseed(seed: int, tag: int) -> int:
    """A 31-bit seed for stream `tag` of run seed `seed` (any integer)."""
    return int(np.random.default_rng([seed % 2 ** 64, tag]).integers(2 ** 31))


def zipf_users(rng, n_users: int, size: int, a: float) -> np.ndarray:
    """`size` user indices, user i drawn with weight (i + 1) ** -a: the
    same popularity for every seed, which only changes the draws."""
    w = np.arange(1, n_users + 1, dtype=np.float64) ** -a
    return rng.choice(n_users, size=size, p=w / w.sum())


def poisson_offsets(rate: float, seconds: float, rng) -> np.ndarray:
    """Arrival offsets in [0, seconds) of a Poisson stream at `rate`/s."""
    n = max(16, int(rate * seconds * 1.5) + 64)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(
            rng.exponential(1.0 / rate, size=n))])
    return t[t < seconds]


def open_schedule(rate: float, seconds: float, n_users: int, a: float,
                  rng) -> tuple:
    """(offsets, users) of an open loop: one fixed draw of Poisson gaps
    at `rate` and of Zipf(`a`) users, the same for every seed, each put in
    an order of the run's `rng`: every seed offers the same work."""
    fixed = np.random.default_rng(DRAWS_SEED)
    offsets = poisson_offsets(rate, seconds, fixed)
    users = zipf_users(fixed, n_users, len(offsets), a)
    gaps = np.diff(offsets, prepend=0.0)
    return np.cumsum(rng.permutation(gaps)), rng.permutation(users)


def user_cycle(n_users: int, a: float, rng):
    """Endless users for closed-loop clients: passes over one fixed
    Zipf(`a`) draw of `USER_CYCLE` users, each pass in an order of the
    run's `rng`."""
    base = zipf_users(np.random.default_rng(DRAWS_SEED), n_users,
                      USER_CYCLE, a)
    while True:
        yield from rng.permutation(base).tolist()


class Harvester:
    """Takes the server's completed-ticket records into `records`; with
    `redeem`, also redeems each resolved ticket's result."""

    def __init__(self, server, records: Records, redeem: bool):
        self.server = server
        self.records = records
        self.redeem = redeem
        self.last = time.perf_counter()

    def __call__(self, force: bool = False) -> None:
        now = time.perf_counter()
        if not force and now - self.last < HARVEST_S:
            return
        self.last = now
        records, server = self.records, self.server
        rows, tickets, done, status, chains = [], [], [], [], []
        for rec in server.take_trace():
            i = records.index.pop(rec.ticket, None)
            if i is None:
                continue
            rows.append(i)
            tickets.append(rec.ticket)
            done.append(rec.done_s)
            status.append(rec.status)
            chains.append(rec.stages)
        if not rows:
            return
        records.resolve(rows, done, status, chains)
        if self.redeem:
            served = [server.result(t, timeout=0) for t in tickets]
            records.store(rows, [s.items for s in served],
                          [s.scores for s in served])


def _no_span(name):
    return contextlib.nullcontext()


def open_loop(server, pool, users, offsets, t0: float, span=_no_span):
    """Submit `pool[users[i]]` at `t0 + offsets[i]`; -> (records,
    harvester)."""
    records = Records(len(offsets))
    harvest = Harvester(server, records, redeem=True)
    for user, off in zip(np.asarray(users).tolist(),
                         np.asarray(offsets).tolist()):
        due = t0 + off
        lag = due - time.perf_counter()
        if lag > 0:
            with span("bench.gen.sleep"):
                time.sleep(lag)
        with span("bench.gen.submit"):
            ticket = server.submit(pool[user])
        records.add(ticket, user, due, time.perf_counter())
        harvest()
    return records, harvest


def closed_loop(server, pool, next_user, outstanding: int, t_end: float,
                span=_no_span):
    """Keep `outstanding` requests in flight until `t_end`; -> (records,
    harvester, [(row, ticket)] still open).

    The first `outstanding` are submitted before the server's drain
    thread starts, so every chunk it collects is full from the start.
    """
    records = Records()
    harvest = Harvester(server, records, redeem=False)
    open_ = []

    def send():
        user = next_user()
        now = time.perf_counter()
        with span("bench.client.submit"):
            ticket = server.submit(pool[user])
        open_.append((records.add(ticket, user, now, now), ticket))

    for _ in range(outstanding):
        send()
    server.start()
    k = 0
    while time.perf_counter() < t_end:
        row, ticket = open_[k]
        k += 1
        with span("bench.client.wait"):
            served = server.result(ticket, timeout=t_end + 600
                                   - time.perf_counter())
        records.store([row], [served.items], [served.scores])
        harvest()
        if time.perf_counter() < t_end:
            send()
    return records, harvest, open_[k:]


def redeem(server, records: Records, harvest, rest=(),
           deadline_s: float = 60.0) -> None:
    """Wait (up to `deadline_s` past now) for the requests still open: the
    tickets in `rest` ((row, ticket) pairs) are redeemed here, the others
    by the harvester. A request that never resolves stays `PENDING`."""
    limit = time.perf_counter() + deadline_s
    for row, ticket in rest:
        try:
            served = server.result(ticket,
                                   timeout=max(limit - time.perf_counter(), 0))
        except TimeoutError:
            continue
        records.store([row], [served.items], [served.scores])
    while True:
        harvest(force=True)
        if (not np.any(records.status == PENDING)
                or time.perf_counter() >= limit):
            return
        time.sleep(0.01)
