"""Host spans and counters of one FLuID round.

`span(name)` times a block of host code. It enters
`jax.profiler.TraceAnnotation(name)`, so a profiler trace shows the block on
its host plane, on the device planes' clock, and it adds the block's
`perf_counter` seconds to the open round record. `count(name, n)` adds to
the open record's counters. `FluidServer.run_round` opens one record per
round (`record()`) and copies it into its `RoundLog`; outside a record,
`span` only annotates and `count` does nothing.

The names, what each covers, and the benchmark metric that reads it
(bench/metrics/<metric>.py):

    fleet.stage            FleetEngine._stacked_data: the
                           round's sample orders, labels and
                           weights, with their host-to-device
                           copies                               stage_ms
    fleet.data_upload      FleetEngine._client_data: the one
                           upload of an engine's client
                           samples                              -
    fluid.calibrate        FluidServer's calibration block      calib_ms
    fleet.stats_wait       gather + stats dispatch, and the
                           host's wait on the cohort program    stats_wait_ms
    fleet.cohort_wait      inside fleet.stats_wait: the host's
                           wait on the cohort program's deltas,
                           before the gather is dispatched      cohort_wait_ms
    fleet.stats_copy       device-to-host copies of the
                           stacked stats, one per unit group    stats_copy_ms
    fluid.threshold        threshold search, policy.observe     threshold_ms
    fluid.plan             straggler plan, store rates          plan_ms
    fluid.threshold_iters  invariant-count passes of
                           calibrate_threshold (counter)        threshold_iters
    fleet.stats_copies     device-to-host copies of the
                           stacked stats, one per unit group
                           (counter)                            stats_copies
    fleet.data_uploads     uploads of an engine's client
                           samples: 1 in its first round, then
                           none (counter)                       -

One thread records at a time.
"""
from __future__ import annotations

import collections
import contextlib
import time
from dataclasses import dataclass, field
from typing import Deque, Dict, Iterator, List, Optional

import jax


@dataclass
class Record:
    """One round: seconds per span name, totals per counter name."""
    spans: Dict[str, float] = field(default_factory=dict)
    counters: Dict[str, int] = field(default_factory=dict)


_open: Optional[Record] = None
# the last rounds' records, newest last: in-process readers that hold no
# RoundLog (the benchmark's per-layer metrics) take the window's from here
_closed: Deque[Record] = collections.deque(maxlen=4096)


@contextlib.contextmanager
def record() -> Iterator[Record]:
    """Open a round record; spans and counts inside go to it."""
    global _open
    outer = _open
    rec = _open = Record()
    try:
        yield rec
    finally:
        _open = outer
        _closed.append(rec)


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    rec = _open
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        if rec is not None:
            rec.spans[name] = (rec.spans.get(name, 0.0)
                               + time.perf_counter() - t0)


def count(name: str, n: int = 1) -> None:
    if _open is not None:
        _open.counters[name] = _open.counters.get(name, 0) + int(n)


def last_rounds(n: int) -> List[Record]:
    """The records of the last n rounds, oldest first (fewer if fewer)."""
    return list(_closed)[-n:] if n > 0 else []
