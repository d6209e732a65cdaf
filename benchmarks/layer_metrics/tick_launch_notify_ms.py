"""What only a tick in flight can hide: median, over the quiet tick
pairs of the traced window, of `G - H` (`tick_gap.py`): the device's
idle between two tick executions (device clock) less the host's time
from `llm_engine.tick_ready`'s end to the next
`llm_engine.tick_dispatch`'s start (host clock).  What is left is the
time from the device's last operation until the host is told, plus the
time from the dispatch call's start to the device's first operation;
the sum is free of the two clocks' offset, its split is not.  Prints the
`CLOCK` and `TICK GAP` lines.  None for a program that writes no
`llm_engine.tick_ready` (its `G - H` would hold the readback)."""
import tick_gap as TG


def read(run):
    got = TG.report(run)
    return got["launch_notify"] if got and got["ready"] else None
