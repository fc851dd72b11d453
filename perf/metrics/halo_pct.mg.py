"""Share of device busy time the V-cycle's halo exchanges took: the device
time of the ``collective-permute`` ops that carry an ``mg_level`` tag (the
sharded cycle refreshes each level's halo ring with them,
``poisson_tpu/parallel/mg_sharded.py``; ``perf/mg_trace.py`` reads the
tag), each nanosecond counted once, over the chip's busy time, mean over
the cell's chips. The CG recurrence's own permutes carry no tag. A trace
without tagged permutes (one chip, or a program without the sharded
cycle) reads nothing."""

from typing import Dict, List, Optional, Sequence

from perf import mg_trace, trace

PERMUTES = {"collective-permute", "collective-permute-start",
            "collective-permute-done"}


def halo_intervals(events: Sequence[trace.Event],
                   window: trace.Interval) -> List[trace.Interval]:
    """The merged intervals, clipped to ``window``, of the tagged halo
    permutes among one device's op events."""
    lo, hi = window
    return trace.merge(
        (max(s, lo), min(e, hi)) for name, s, e in events
        if mg_trace.level_of(name) is not None
        and trace.op_key(name)[1] in PERMUTES and min(e, hi) > max(s, lo))


def share(device_events: Dict[str, Sequence[trace.Event]],
          summary: trace.Summary) -> Optional[float]:
    """Mean over ``summary``'s devices of halo time ÷ busy time, in %;
    None where no device ran a tagged permute."""
    halo = [trace.total(halo_intervals(device_events.get(d.name, ()),
                                       summary.window))
            for d in summary.devices]
    if not any(halo):
        return None
    shares = [h / d.busy_ns for h, d in zip(halo, summary.devices)
              if d.busy_ns > 0]
    return 100.0 * sum(shares) / len(shares) if shares else None


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    from perf import run as harness

    path = trace.find_xspace(str(harness.TRACE_DIR))
    events, _, _ = trace.read_xspace(path, [d.id for d in run.devices])
    return share(events, run.trace)
