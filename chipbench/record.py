"""What one run measured, as the metric readers see it.

Times are wall seconds since the serving clock started; the measured
window is `[w0, w1]`. A reader (`chipbench/metrics/<name>.py`) defines
`read(run) -> float | None` and returns None where the run has nothing
for it to read.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence

from chipbench import arch
from chipbench.flops import Shape
from chipbench.trace_reduce import Reduced
from chipbench.wallclock import Step


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (q in 0..100): a value that was measured."""
    if not len(values):
        return None
    v = sorted(values)
    return v[max(0, math.ceil(q / 100 * len(v)) - 1)]


@dataclasses.dataclass
class Run:
    cell: dict
    config: dict                    # the configuration file, as run
    mix: dict
    peaks: dict                     # this device's row of peaks.json
    seconds: float
    setup_s: float
    w0: float
    w1: float
    steps: List[Step]
    arrival: Dict[int, float]       # rid -> due time
    tokens: Dict[int, List[int]]    # rid -> served token ids
    times: Dict[int, List[float]]   # rid -> emission times
    memory_peak_bytes: Optional[int] = None
    trace: Optional[Reduced] = None
    traced: Optional[tuple] = None  # the traced span on the same clock

    @property
    def shape(self) -> Shape:
        return arch.resolve(self.config).shape(self.config)

    @property
    def open_loop(self) -> bool:
        return self.mix["kind"] != "backlog"

    def window_steps(self, kind: str) -> List[Step]:
        """Steps of `kind` that ended inside the window."""
        return [s for s in self.steps
                if s.kind == kind and self.w0 <= s.end <= self.w1]

    def due_in_window(self) -> List[int]:
        return [rid for rid, a in self.arrival.items()
                if self.w0 <= a <= self.w1]

    def traced_steps(self, kind: str) -> List[Step]:
        """Steps of `kind` that ran wholly inside the traced span."""
        lo, hi = self.traced
        return [s for s in self.steps
                if s.kind == kind and lo <= s.start and s.end <= hi]
