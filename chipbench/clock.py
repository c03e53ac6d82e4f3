"""The benchmark's ``stop_check``: the callable ``engine.run`` polls once
per step, before that step's dispatch.

It stamps ``time.perf_counter()`` at every poll, lets ``warmup_steps``
polls pass (set-up), opens the measured window at a poll and returns
True at the first poll ``seconds`` after it.  Inside the window it does
nothing but stamp; what blocks (the copies of the program's state that
the output check needs) happens at the first polls of the warm-up.

What it takes from the program, read-only and found by type, never
altered: at polls 0, 1 and 3 the ``TrainState`` local of the frame
that polls (``engine.train_one_epoch``), and at polls 1 to 3 that
frame's newest metric vector ``[loss_sum, top1, top5, n, ...]``, the
engine's own for every family.  ``stop_check`` takes no argument, so
the frame is the only way to them without an edit to the program
(PERF.md section 7 names the hook that would replace the walk).  A
program that no longer keeps them where a poll can see them makes the
run fail loudly (no result line), not pass silently.  What of the
optimizer's state the first gradient is worked out from is the
family's to say (``families/<family>/program.py::optimizer_memory``).
"""

from __future__ import annotations

import sys
import time

FOLLOWED_STEPS = 3  # the reference follows this many optimizer steps


def _engine_frame():
    """The nearest caller frame that holds the program's train state."""
    f = sys._getframe(1)
    while f is not None:
        for v in f.f_locals.values():
            if type(v).__name__ == "TrainState":
                return f
        f = f.f_back
    raise RuntimeError("chipbench: no frame polling stop_check holds a "
                       "TrainState; the output check cannot read the "
                       "program's first steps")


def _flat(tree) -> dict:
    """{'a/b/c': numpy array} of a nested mapping of device arrays."""
    import jax
    import numpy as np
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "name", p)))
                       for p in path)
        out[key] = np.asarray(jax.device_get(leaf))
    return out


class Clock:
    def __init__(self, seconds: float, warmup_steps: int,
                 optimizer_memory, annotate: bool = False):
        if warmup_steps <= FOLLOWED_STEPS:
            raise ValueError("the warm-up must outlast the followed "
                             f"steps ({FOLLOWED_STEPS})")
        self.seconds = float(seconds)
        self.warmup_steps = int(warmup_steps)
        self.optimizer_memory = optimizer_memory
        self.annotate = annotate
        self.polls = 0
        self.stamps: list[float] = []   # polls of the window, first = open
        self.all_stamps: list[float] = []
        self.closed = False
        self.captured: dict = {"losses": []}
        self._ann = None

    # -- what the output check reads (warm-up only) ----------------------

    def _capture(self, i: int) -> None:
        import numpy as np
        f = _engine_frame()
        state = next(v for v in f.f_locals.values()
                     if type(v).__name__ == "TrainState")
        if i == 0:
            self.captured["p0"] = _flat(state.params)
        if 1 <= i <= FOLLOWED_STEPS:
            m = f.f_locals.get("metrics")
            if m is None:
                raise RuntimeError("chipbench: the polling frame has "
                                   "no metric vector of the last step")
            v = np.asarray(m, np.float64)
            self.captured["losses"].append(float(v[0] / max(v[3], 1.0)))
        if i == 1:
            self.captured["opt1"] = _flat(
                self.optimizer_memory(state.opt_state))
        if i == FOLLOWED_STEPS:
            self.captured["p_end"] = _flat(state.params)

    # -- the poll ---------------------------------------------------------

    def __call__(self) -> bool:
        i = self.polls
        self.polls += 1
        if i <= FOLLOWED_STEPS:
            self._capture(i)
        if self.annotate:
            import jax
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
            self._ann = jax.profiler.TraceAnnotation(
                "chipbench_step", poll=i)
            self._ann.__enter__()
        now = time.perf_counter()
        self.all_stamps.append(now)
        if i < self.warmup_steps:
            return False
        self.stamps.append(now)
        if now - self.stamps[0] >= self.seconds:
            self.closed = True
            if self._ann is not None:
                self._ann.__exit__(None, None, None)
                self._ann = None
            return True
        return False

    # -- the window, once closed -----------------------------------------

    @property
    def t_open(self) -> float:
        return self.stamps[0]

    @property
    def t_close(self) -> float:
        return self.stamps[-1]

    def intervals_s(self) -> list[float]:
        s = self.stamps
        return [b - a for a, b in zip(s, s[1:])]
