"""Engine: mean duration of the program span engine.decode.prepare (host work of a decode tick before its dispatch) over the traced span's decode ticks, ms."""
from chipbench import program_spans


def read(ctx):
    return program_spans.decode_prepare_ms(ctx)
