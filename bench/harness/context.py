"""What one run hands to the metric readers (bench/metrics/<name>.py).

A reader is a module with `read(ctx: RunContext) -> float | None`. It
returns None when the run holds nothing for it to read (no trace, no
collective, ...), and the harness then leaves the metric out of the line.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

from bench.harness.trace import TraceSummary


@dataclass
class RoundStat:
    """One round of the window, counted from the algorithm's work."""
    wall_s: float            # host clock, dispatch to params ready
    samples: int             # real sample passes (sample x local epoch)
    flops: float             # required training FLOPs of those passes
    min_bytes: float         # least HBM traffic of the round's local SGD
    calib_s: float           # the program's own calibration span


@dataclass
class RunContext:
    cell: str
    chips: int
    peak_flops: float        # per chip, from bench/peaks.json
    peak_bytes_per_s: float
    setup_s: float
    window_s: float
    rounds: List[RoundStat] = field(default_factory=list)
    compiles_in_window: int = 0
    trace: Optional[TraceSummary] = None

    @property
    def samples(self) -> int:
        return sum(r.samples for r in self.rounds)

    @property
    def flops(self) -> float:
        return sum(r.flops for r in self.rounds)

    def ms_per_round(self, seconds: float) -> float:
        """`seconds` spread over the window's rounds, in ms per round."""
        return 1e3 * seconds / len(self.rounds)
