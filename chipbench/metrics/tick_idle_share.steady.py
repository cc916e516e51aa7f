"""Host: share of the traced span in which the device ran nothing while the scheduler was inside sched.tick, time-weighted, %."""
from chipbench import program_spans


def read(ctx):
    return program_spans.tick_idle_share(ctx)
