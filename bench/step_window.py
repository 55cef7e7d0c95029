"""Timing a job of steps by the program's own step counter.

A job is one call that takes a number of optimizer steps and counts each
on a counter as it completes, a counter the benchmark can read while the
call runs.  The job's window opens at the first step counted while the
job runs, so that what the job does once before it steps (its call, its
preparation, its compile or cache load, and the first step itself) falls
in set-up; it closes when the job's result is ready.  The rate is the
steps counted after the opening over the window's time: all the work and
all the time of the window.

A program that counts its steps only when the job ends gives the window
nothing to open on.  Then the whole job is timed, from its call, and
``Timed.note`` says so; an empty window is never a rate.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass

POLL_S = 0.001


@dataclass
class Timed:
    """One job, timed.  Times are on the host's ``perf_counter``; counts
    are the counter's readings."""
    result: object
    t_call: float
    t_close: float
    n_call: int
    n_close: int
    t_open: float | None = None
    n_open: int | None = None

    @property
    def opened(self) -> bool:
        """The window opened while the job ran, with steps counted after."""
        return self.t_open is not None and self.n_close > self.n_open

    @property
    def steps(self) -> int:
        """Steps timed: those counted after the opening, or, where the
        window did not open, every step the job counted."""
        return self.n_close - (self.n_open if self.opened else self.n_call)

    @property
    def seconds(self) -> float:
        return self.t_close - (self.t_open if self.opened else self.t_call)

    @property
    def step_s(self) -> float:
        return self.seconds / max(self.steps, 1)

    @property
    def start_s(self) -> float | None:
        """From the job's call to its first counted step."""
        return self.t_open - self.t_call if self.opened else None

    @property
    def note(self) -> str | None:
        if self.opened:
            return None
        return ("the step counter did not move while the job ran: the "
                "window is the whole job, timed from its call")


def steps_for(seconds: float, warm: Timed) -> int:
    """Steps for a job whose window, every step after the first, lasts
    about ``seconds`` at the warm-up job's steady time per step."""
    return 1 + max(1, round(seconds / warm.step_s))


def run_job(job, counter, *, on_open=None, on_count=None,
            clock=time.perf_counter, poll_s: float = POLL_S) -> Timed:
    """Call ``job()`` and time it by ``counter()``.

    A watching thread reads the counter every ``poll_s`` while the job
    runs.  At the first change it opens the window and calls
    ``on_open(t)``; at each later change it calls ``on_count(n)`` with the
    steps counted since the opening, until that returns True (without
    ``on_count``, it stops at the next change).  Changes first seen after
    the job has returned open nothing."""
    done = threading.Event()
    seen: dict = {}
    n_call = counter()

    def watch():
        last = n_call
        while not done.wait(poll_s):
            n = counter()
            if n == last:
                continue
            last = n
            if "t" not in seen:
                seen["t"], seen["n"] = clock(), n
                if on_open is not None:
                    on_open(seen["t"])
            elif on_count is None or on_count(n - seen["n"]):
                return

    watcher = threading.Thread(target=watch, name="step-window")
    watcher.start()
    t_call = clock()
    try:
        result = job()
    finally:
        t_close = clock()
        done.set()
        watcher.join()
    return Timed(result=result, t_call=t_call, t_close=t_close,
                 n_call=n_call, n_close=counter(), t_open=seen.get("t"),
                 n_open=seen.get("n"))
