"""Host-clock client: chat clients in a closed or an open loop, and what
they observed.

Every time here is `time.perf_counter()`, taken in the client's own calls
and callbacks: the send stamp when `ServingClient.chat` is called (in an
open loop, the arrival time the request was due at, so a host busy past it
counts as waiting), a token stamp when the stream hands the client a token.
The serving stack's own times (the event loop's virtual clock, the
roofline estimate of a step) are never read. A token reaches the client
after the executor has brought its logits to the host, so its stamp comes
after the device finished it.

Closed loop: all clients send their first request at the window's start
(the paper's N-concurrent burst), and each sends its next request when its
last one closes, while the window is open. Open loop: a request is sent at
each arrival time, whatever is in flight.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Callable, Iterator, Optional

import numpy as np


@dataclass
class Record:
    client: int
    prompt: list
    target: int
    t_send: float
    late: float = 0.0          # open loop: host clock at the send - t_send
    t_tokens: list = field(default_factory=list)
    tokens: list = field(default_factory=list)
    done: bool = False
    error: Optional[str] = None


class Clients:
    """Clients over `requests`, an iterator of `bench.traffic.Request`.
    Closed loop: `clients` clients; a client whose next request the
    iterator cannot give stops. Open loop: `arrivals`, seconds from the
    window's start of each send. `annotate(name)` returns a context
    manager wrapped around each client callback (a trace annotation, or a
    no-op)."""

    def __init__(self, serving_client, model: str, requests: Iterator, *,
                 clients: int = 0, arrivals: Optional[Iterator] = None,
                 clock: Callable = time.perf_counter,
                 annotate: Optional[Callable] = None):
        self.client = serving_client
        self.model = model
        self.requests = requests
        self.clients = clients
        self.arrivals = arrivals
        self.clock = clock
        self.annotate = annotate
        self.records: list[Record] = []
        self.t_end = 0.0
        self.next_at = math.inf
        self.in_flight = 0

    def start(self, t0: float, t_end: float):
        self.t_end = t_end
        if self.arrivals is None:
            for c in range(self.clients):
                self._send(c, self.clock())
        else:
            self._t0 = t0
            self.next_at = t0 + next(self.arrivals)
            self.send_due()

    def send_due(self):
        """Open loop: send every request whose arrival time has come."""
        while self.next_at <= min(self.clock(), self.t_end):
            self._send(len(self.records), self.next_at)
            self.next_at = self._t0 + next(self.arrivals)

    def _send(self, c: int, t_send: float):
        from repro.api import ChatMessage
        from repro.api.errors import APIStatusError
        if t_send >= self.t_end:
            return
        nxt = next(self.requests, None)
        if nxt is None:                      # a finite source ran dry
            return
        rec = Record(client=c, prompt=nxt.prompt, target=nxt.output,
                     t_send=t_send, late=self.clock() - t_send)
        self.records.append(rec)
        try:
            stream = self.client.chat(
                model=self.model, messages=[ChatMessage("user", nxt.prompt)],
                temperature=0.0, max_tokens=nxt.output,
                target_output_len=nxt.output, session_id=nxt.session,
                stream=True)
        except APIStatusError as e:
            # refused at the door: a failed request; a closed-loop client
            # stops, so a gateway that refuses everything cannot spin the
            # window
            rec.done, rec.error = True, str(e)
            return
        self.in_flight += 1

        def on_token(r, tok, t_virtual):
            rec.t_tokens.append(self.clock())
            rec.tokens.append(int(tok))

        def on_done(s):
            rec.done = True
            self.in_flight -= 1
            if s.error is not None:
                rec.error = str(s.error)
            if self.arrivals is not None:
                return
            if self.annotate is None:
                self._send(c, self.clock())
            else:
                with self.annotate("bench.client"):
                    self._send(c, self.clock())

        stream.subscribe(on_token)
        stream.on_done(on_done)


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def summarize(records: list, t0: float, t1: float) -> dict:
    """End-to-end numbers of the window [t0, t1] from the client records.

    output_tok_s: tokens delivered in the window over its length.
    ttft_p95_s: over every request sent; one with no first token by t1
    enters at its age then (a lower bound), a failed one at the window's
    length (a miss).
    itl_p95_ms: over all gaps between consecutive tokens of one request.
    send_late_max_s: how late the open-loop generator sent at worst.
    """
    window = t1 - t0
    tokens = sum(1 for r in records for t in r.t_tokens if t0 <= t <= t1)
    ttft, gaps = [], []
    for r in records:
        if r.error is not None:
            ttft.append(window)
        elif r.t_tokens:
            ttft.append(r.t_tokens[0] - r.t_send)
        else:
            ttft.append(t1 - r.t_send)
        ts = [t for t in r.t_tokens if t0 <= t <= t1]
        gaps.extend(b - a for a, b in zip(ts, ts[1:]))
    out = {"window_s": window, "output_tokens": tokens,
           "output_tok_s": tokens / window,
           "attempted": len(records),
           "failed": sum(r.error is not None for r in records),
           "finished": sum(r.done and r.error is None for r in records),
           "first_tokens": sum(bool(r.t_tokens) for r in records),
           "ttft_p95_s": percentile(ttft, 95) if ttft else None,
           "itl_p95_ms": percentile(gaps, 95) * 1e3 if gaps else None,
           "itl_count": len(gaps),
           "send_late_max_s": max((r.late for r in records), default=0.0)}
    return out
