"""Engine: the engine's host_reads counter (blocking reads of device arrays) from the first to the last decode tick of the traced span, per tick."""
from chipbench import program_spans


def read(ctx):
    return program_spans.decode_host_reads(ctx)
