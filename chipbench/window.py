"""The measured window: whole units between two fetches.

A unit is what the job says it is: one step for plain, FT-DDP and HSDP, one
round of ``sync_every`` steps (every fragment synced once) for streaming
DiLoCo. The window opens right after a value fetch at a unit boundary, runs
whole units until ``seconds`` have passed (and ``min_units`` are done: a
traffic mix whose unit is longer than the window still measures more than
one), closes with a value fetch, and the
rate divides the units' work by the time BETWEEN THE TWO FETCHES, never by
``seconds``. A window cut by the clock in the middle of a round holds one
fragment sync more or fewer from run to run; this one cannot.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List


@dataclass
class Window:
    units: int
    opened: float
    closed: float
    # Clock reading after each unit, before the closing fetch.
    unit_ends: List[float] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.closed - self.opened


def run_window(
    run_unit: Callable[[int], None],
    fetch: Callable[[], None],
    seconds: float,
    min_units: int = 1,
    clock: Callable[[], float] = time.monotonic,
) -> Window:
    """``fetch()``, then ``run_unit(0), run_unit(1), ...`` until ``seconds``
    have passed since the opening fetch returned and ``min_units`` units are
    done, then ``fetch()``.

    The clock is read only between units, so no unit is ever cut."""
    fetch()
    opened = clock()
    ends: List[float] = []
    while True:
        run_unit(len(ends))
        ends.append(clock())
        if ends[-1] - opened >= seconds and len(ends) >= min_units:
            break
    fetch()
    return Window(units=len(ends), opened=opened, closed=clock(), unit_ends=ends)
