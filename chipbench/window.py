"""Window arithmetic on the host clock.

Every time here is ``time.perf_counter()`` seconds.  A request's latency
runs from when it was *due* (open loop), not from when it was submitted,
so a stall that delays later submissions stays in the tail.  A rate is
all the work inside the window over the window's length; a tail is taken
over all requests, or all gaps, of the window.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class RequestRecord:
    idx: int
    due: float
    prompt_len: int
    max_new_tokens: int
    prompt: np.ndarray
    token_times: list[float] = dataclasses.field(default_factory=list)
    tokens: list[int] = dataclasses.field(default_factory=list)
    token_ticks: list[int] = dataclasses.field(default_factory=list)
    submitted: float | None = None
    finish_reason: str | None = None

    @property
    def first_token(self) -> float | None:
        return self.token_times[0] if self.token_times else None

    @property
    def finished(self) -> bool:
        return self.finish_reason in ("stop", "length")

    @property
    def shed(self) -> bool:
        return self.finish_reason in ("rejected", "expired", "errored")


@dataclasses.dataclass(frozen=True)
class Window:
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def holds(self, t: float) -> bool:
        return self.start <= t < self.end


def percentile(values, q: float) -> float | None:
    """The q-th percentile (linear interpolation); None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def arrived_in(records, win: Window) -> list[RequestRecord]:
    return [r for r in records if win.holds(r.due)]


def ttfts(records, win: Window) -> list[float]:
    """TTFT of every request due in the window that has a first token."""
    return [r.first_token - r.due for r in arrived_in(records, win)
            if r.first_token is not None]


def token_gaps(records, win: Window) -> list[float]:
    """Every gap between consecutive output tokens of one request whose
    later token falls in the window, pooled across requests."""
    out = []
    for r in records:
        t = r.token_times
        out.extend(t[i] - t[i - 1] for i in range(1, len(t))
                   if win.holds(t[i]))
    return out


def tokens_in(records, win: Window) -> int:
    """Output tokens emitted inside the window."""
    return sum(1 for r in records for t in r.token_times if win.holds(t))


def attainment(records, win: Window, *, ttft_s: float,
               mean_gap_ms: float) -> float | None:
    """Share of the window's requests whose TTFT is at most ``ttft_s``
    and whose mean gap between tokens is at most ``mean_gap_ms``.  A
    request with no first token, or shed, misses."""
    reqs = arrived_in(records, win)
    if not reqs:
        return None
    met = 0
    for r in reqs:
        if r.shed or r.first_token is None:
            continue
        t = r.token_times
        mean_gap = ((t[-1] - t[0]) / (len(t) - 1) * 1e3 if len(t) > 1
                    else 0.0)
        if t[0] - r.due <= ttft_s and mean_gap <= mean_gap_ms:
            met += 1
    return met / len(reqs)
