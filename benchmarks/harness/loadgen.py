"""One general traffic generator, driven by a data file of parameters.

A traffic file (``benchmarks/workloads/<traffic>.json``) gives lengths,
arrivals and sharing as numbers; nothing here knows a cell by name. The
same seed gives the same requests. Every seed gets the SAME multiset of
sizes and inter-arrival gaps (the distribution's quantiles) in another
order, so that a run's amount of work does not depend on the seed: only
which request meets which does.

Traffic keys:

``loop``           ``"open"`` (arrivals on a schedule) or ``"closed"``
                   (``clients`` callers, each sending its next request when
                   the last completes)
``rate_per_s``     open loop: mean arrivals a second, fixed in the file
``arrivals``       ``"poisson"``: exponential gaps
``clients``        closed loop: number of callers
``prompt_tokens``  a length distribution, see :func:`quantile_lengths`
``output_tokens``  likewise
``sampled_share``  share of requests decoded at ``temperature`` (the rest
                   greedy)
``lead_in_s``      seconds of the same traffic before the window opens, so
                   the window starts in steady state (counted as set-up)
"""

from __future__ import annotations

import math
import threading
import time
from statistics import NormalDist

import numpy as np


def quantile_lengths(spec, n):
    """``n`` whole lengths at the distribution's mid-quantiles, clipped to
    ``[min, max]``, in ascending order.

    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
    ``{"dist": "uniform", "min": a, "max": b}``
    """
    q = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "uniform":
        vals = spec["min"] + q * (spec["max"] + 1 - spec["min"])
        return np.clip(np.floor(vals), spec["min"], spec["max"]).astype(
            np.int64)
    if kind == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(x)) for x in q])
        vals = spec["median"] * np.exp(spec["sigma"] * z)
        return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(
            np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def _gaps(traffic, n, rng):
    """``n`` inter-arrival gaps (seconds) whose sum is ``n / rate``: the
    exponential's mid-quantiles in an order drawn from ``rng``, rescaled so
    the schedule spans exactly its share of the window whatever the order.
    Request ``i`` is due once the gaps before it have passed."""
    if traffic.get("arrivals", "poisson") != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    gaps *= n / gaps.sum() / float(traffic["rate_per_s"])
    return rng.permutation(gaps)


class Planned:
    """One request as generated: what to send and, in an open loop, when
    it is due (seconds from the schedule's start)."""

    __slots__ = ("index", "due", "prompt", "max_new", "temperature")

    def __init__(self, index, due, prompt, max_new, temperature):
        self.index = index
        self.due = due
        self.prompt = prompt
        self.max_new = max_new
        self.temperature = temperature


def plan(traffic, seed, seconds, vocab):
    """The requests of one run: ``lead_in_s + seconds`` of traffic.

    Open loop: ``round(rate * span)`` requests whose due times fill the
    span. Closed loop: a pool large enough that callers never run out
    (``pool_per_client_s`` requests a client-second, default 4)."""
    span = float(traffic.get("lead_in_s", 0.0)) + float(seconds)
    if traffic["loop"] == "open":
        n = max(1, int(round(float(traffic["rate_per_s"]) * span)))
    else:
        n = max(1, int(math.ceil(traffic["clients"] * span
                                 * float(traffic.get("pool_per_client_s", 4)))))
    rng = np.random.default_rng([int(seed), 0x10ad])
    prompts = rng.permutation(quantile_lengths(traffic["prompt_tokens"], n))
    outputs = rng.permutation(quantile_lengths(traffic["output_tokens"], n))
    n_sampled = int(round(float(traffic.get("sampled_share", 0.0)) * n))
    sampled = np.zeros(n, bool)
    sampled[rng.permutation(n)[:n_sampled]] = True
    temperature = float(traffic.get("temperature", 0.0))
    if traffic["loop"] == "open":
        gaps = _gaps(traffic, n, rng)
        due = np.cumsum(gaps) - gaps            # the first is due at 0
    else:
        due = np.zeros(n)
    ids = rng.integers(0, vocab, int(prompts.sum()), dtype=np.int32)
    out, at = [], 0
    for i in range(n):
        p = ids[at:at + int(prompts[i])]
        at += int(prompts[i])
        out.append(Planned(i, float(due[i]), p, int(outputs[i]),
                           temperature if sampled[i] else 0.0))
    return out


class Record:
    """What the client side saw of one request, on ``time.perf_counter``."""

    __slots__ = ("planned", "due_at", "sent_at", "token_at", "tokens",
                 "error", "done")

    def __init__(self, planned, due_at):
        self.planned = planned
        self.due_at = due_at
        self.sent_at = None
        self.token_at = []
        self.tokens = []
        self.error = None
        self.done = threading.Event()


class LoadGenerator:
    """Drives ``submit(prompt, max_new, temperature) -> iterable of tokens``
    with the planned requests and times each token as it leaves the stream.

    One arrival thread (open loop) or ``clients`` caller threads (closed
    loop); in the open loop each request's stream is read by a thread of
    its own, as independent callers would. The generator never retries and
    never searches for a rate."""

    def __init__(self, traffic, planned, submit):
        self.traffic = traffic
        self.planned = planned
        self.submit = submit
        self.records = []
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = []
        self._next = 0
        self.t0 = None

    # ------------------------------------------------------------ driving --
    def start(self):
        """Begin sending; returns the schedule's origin on perf_counter."""
        self.t0 = time.perf_counter()
        if self.traffic["loop"] == "open":
            t = threading.Thread(target=self._arrivals, name="loadgen-arrive",
                                 daemon=True)
            self._threads.append(t)
            t.start()
        else:
            for c in range(int(self.traffic["clients"])):
                t = threading.Thread(target=self._caller, name=f"loadgen-c{c}",
                                     daemon=True)
                self._threads.append(t)
                t.start()
        return self.t0

    def stop_sending(self):
        self._stop.set()

    def drain(self, timeout_s):
        """Wait for every request sent so far; returns those still
        unfinished after ``timeout_s`` (they count as failed)."""
        deadline = time.perf_counter() + timeout_s
        for t in self._threads:
            t.join(max(0.0, deadline - time.perf_counter()))
        with self._lock:
            records = list(self.records)
        late = []
        for r in records:
            if not r.done.wait(max(0.0, deadline - time.perf_counter())):
                late.append(r)
        return late

    # ------------------------------------------------------------ threads --
    def _arrivals(self):
        for p in self.planned:
            due_at = self.t0 + p.due
            while True:
                wait = due_at - time.perf_counter()
                if wait <= 0 or self._stop.is_set():
                    break
                # coarse sleep, then a short spin: sleep() alone overshoots
                time.sleep(wait - 0.0005 if wait > 0.001 else 0)
            if self._stop.is_set():
                return
            rec = Record(p, due_at)
            with self._lock:
                self.records.append(rec)
            handle = self._send(rec)
            if handle is not None:
                t = threading.Thread(target=self._read, args=(rec, handle),
                                     name=f"loadgen-r{p.index}", daemon=True)
                t.start()

    def _caller(self):
        while not self._stop.is_set():
            with self._lock:
                # the pool is sized never to run out; if it does, go round
                p = self.planned[self._next % len(self.planned)]
                self._next += 1
                rec = Record(p, time.perf_counter())
                self.records.append(rec)
            handle = self._send(rec)
            if handle is not None:
                self._read(rec, handle)

    def _send(self, rec):
        p = rec.planned
        rec.sent_at = time.perf_counter()
        try:
            return self.submit(p.prompt, p.max_new, p.temperature)
        except Exception as e:           # refused: counts as failed
            rec.error = e
            rec.done.set()
            return None

    @staticmethod
    def _read(rec, handle):
        try:
            for tok in handle:
                rec.token_at.append(time.perf_counter())
                rec.tokens.append(int(tok))
        except Exception as e:           # failed mid-stream
            rec.error = e
        finally:
            rec.done.set()
