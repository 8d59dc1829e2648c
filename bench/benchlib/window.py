"""The measured window, opened and closed on dispatch completions.

A dispatch completes when the last of the engine's calls for it
returns (``decode_batch``, or the single call of an engine kind with no
stages, ``load.Instrument``), just before the server hands the results
to their requests. The window opens at the
first completion of the load and closes at the first completion at least
``seconds`` later. Every request of the dispatches completed in
``(open, close]`` counts, over the whole of ``close - open``: a stall
anywhere inside lowers the rate, and no dispatch is cut at an edge.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, List, Optional, Sequence


@dataclasses.dataclass
class Dispatch:
    seq: int            # order in which the dispatch was encoded
    t_done: float       # when its last engine call returned
    n: int              # live requests in it
    encode_s: Optional[float]   # its encode_batch span, if it has one
    decode_s: Optional[float]   # its decode_batch span, if it has one


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    dispatches: List[Dispatch]      # those completed in (open, close]

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    @property
    def requests(self) -> int:
        return sum(d.n for d in self.dispatches)

    @property
    def seqs(self) -> set:
        return {d.seq for d in self.dispatches}


def close_index(done: Sequence[Dispatch], seconds: float) -> Optional[int]:
    """Index of the completion that closes a window opened at
    ``done[0]``, or None while there is none yet."""
    if not done:
        return None
    due = done[0].t_done + seconds
    return next((i for i, d in enumerate(done) if i and d.t_done >= due),
                None)


def window_of(done: Sequence[Dispatch], seconds: float) -> Window:
    """The window over ``done`` (completions in order of time)."""
    i = close_index(done, seconds)
    if i is None:
        raise ValueError("no completion closes the window")
    return Window(done[0].t_done, done[i].t_done, list(done[1:i + 1]))


class DispatchLog:
    """Completions as they happen, recorded from the pipeline's decode
    thread; ``wait_window`` blocks the caller until the window closes."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self.clock = clock
        self.done: List[Dispatch] = []
        self._cv = threading.Condition()

    def record(self, d: Dispatch) -> None:
        with self._cv:
            self.done.append(d)
            self._cv.notify_all()

    def wait_window(self, seconds: float, stall_s: float) -> Window:
        """The window, once it has closed. Raises ``TimeoutError`` when no
        dispatch completes for ``stall_s`` seconds."""
        with self._cv:
            seen = len(self.done)
            while close_index(self.done, seconds) is None:
                if not self._cv.wait(timeout=stall_s) \
                        and len(self.done) == seen:
                    raise TimeoutError(f"no dispatch completed in "
                                       f"{stall_s:.0f} s")
                seen = len(self.done)
            return window_of(list(self.done), seconds)


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``
    percent of the values at or below it."""
    xs = sorted(values)
    if not xs:
        raise ValueError("no values")
    k = max(1, -(-len(xs) * q // 100))
    return xs[int(k) - 1]
