"""A run keeps its own time limit.

The builder's contract: a run exits within ``CONTRACT_S`` seconds; the
first run of a cell in a checkout, which compiles, may take
``FIRST_RUN_S``.  A run that the driver has to kill refuses the PR, on
whichever side it ran; a run that ends itself with a reason only fails.
So a watchdog thread, which needs nothing from the phase in flight (XLA's
compile and a ``block_until_ready`` release the interpreter lock), ends
the run ``MARGIN_S`` before its limit: one JSON line that names the phase,
the reference child stopped, exit code ``EXIT_OVER_BUDGET``, no result
line.  The run is "the first of its cell in this checkout" when the
marker it leaves under the scratch directory is not there yet; nothing
else lengthens a limit.
"""

from __future__ import annotations

import json
import os
import threading
import time

CONTRACT_S = 360.0
FIRST_RUN_S = 1200.0
#: what stopping the child and flushing may take
MARGIN_S = 15.0
EXIT_OVER_BUDGET = 4


class Budget:
    def __init__(self, t_start: float, marker: str, on_expire=None,
                 detail=None):
        """``t_start``: the process's start on ``time.monotonic()``;
        ``marker``: the file that says this cell has run here before;
        ``on_expire()`` stops what the run started; ``detail()`` gives
        more keys for the line of a run that ends itself."""
        self.t_start = t_start
        self.first_run = not os.path.exists(marker)
        os.makedirs(os.path.dirname(marker), exist_ok=True)
        with open(marker, "a", encoding="utf-8"):
            pass
        self.limit_s = FIRST_RUN_S if self.first_run else CONTRACT_S
        self.phase = "start"
        self.on_expire = on_expire
        self.detail = detail
        self._lock = threading.Lock()
        self._closed = False
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="budget")
        self._thread.start()

    def elapsed(self) -> float:
        return time.monotonic() - self.t_start

    def _watch(self):
        while True:
            left = self.limit_s - MARGIN_S - self.elapsed()
            if left <= 0:
                break
            time.sleep(min(left, 1.0))
            if self._closed:
                return
        self.expire()

    def need(self, seconds: float):
        """Ends the run now when ``seconds`` more cannot fit."""
        if self.elapsed() + seconds > self.limit_s - MARGIN_S:
            self.expire()

    def expire(self):
        with self._lock:
            if self._closed:        # the result line went out first
                return
            line = {"phase": "over_budget", "in": self.phase,
                    "elapsed_s": self.elapsed(), "limit_s": self.limit_s,
                    "first_run": self.first_run}
            try:
                if self.detail is not None:
                    line.update(self.detail())
            finally:
                print(json.dumps(line, default=str), flush=True)
            try:
                if self.on_expire is not None:
                    self.on_expire()
            finally:
                # the main thread may sit in native code: no exception
                # reaches it, and nothing may print after this line
                os._exit(EXIT_OVER_BUDGET)

    def close(self):
        """Called before the result line: after it the watchdog is off."""
        with self._lock:
            self._closed = True
