"""The measured window, and the verify call it is measured at.

`VerifyProxy` stands between a traffic loop and a verify-service handle:
it times each `verify_batch` call (the span `scan.verify`, everything
else in the window is `scan.outside`), keeps each call's rounds and
verdicts for the check, and closes the window at the first verdict past
`--seconds`.
"""

import contextlib
import time


class Window:
    def __init__(self, seconds: float, annotate=None):
        self.seconds = seconds
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.start = None           # perf_counter at the window's start
        self.end = None             # perf_counter of the closing verdict
        self.closed = False
        self.chunks = []            # (scan index, rounds, sigs, prevs, ok)
        self.spans = []             # (name, t0, t1) relative to start
        self.scan_index = 0
        self.submitted = 0          # rounds handed to the verifier
        self._outside = None

    @property
    def recording(self) -> bool:
        return self.start is not None and not self.closed

    def open(self) -> None:
        self.start = time.perf_counter()
        self._enter_outside(self.start)

    def _enter_outside(self, t: float) -> None:
        self._outside = (t, self.annotate("scan.outside"))
        self._outside[1].__enter__()

    def _leave_outside(self, t: float) -> None:
        if self._outside is not None:
            t0, ctx = self._outside
            ctx.__exit__(None, None, None)
            self.spans.append(("scan.outside", t0 - self.start,
                               t - self.start))
            self._outside = None

    @property
    def elapsed(self) -> float:
        return self.end - self.start

    @property
    def rounds(self) -> int:
        """Rounds whose verdict came in the window."""
        return sum(len(c[1]) for c in self.chunks)


class VerifyProxy:
    """A loop's verifier: the handle's `verify_batch`, timed."""

    def __init__(self, verify_batch, window: Window, kind: str = "device"):
        self._verify = verify_batch
        self.window = window
        self.kind = kind

    def verify_batch(self, rounds, sigs, prev_sigs=None):
        w = self.window
        rec = w.recording
        t0 = time.perf_counter()
        if rec:
            w.submitted += len(rounds)
            w._leave_outside(t0)
            with w.annotate("scan.verify"):
                ok = self._verify(rounds, sigs, prev_sigs)
        else:
            ok = self._verify(rounds, sigs, prev_sigs)
        t1 = time.perf_counter()
        if rec:
            w.spans.append(("scan.verify", t0 - w.start, t1 - w.start))
            w.chunks.append((w.scan_index, list(rounds), list(sigs),
                             list(prev_sigs) if prev_sigs is not None
                             else [None] * len(rounds),
                             [bool(x) for x in ok]))
            if t1 - w.start >= w.seconds:
                w.end = t1
                w.closed = True
            else:
                w._enter_outside(t1)
        return ok
