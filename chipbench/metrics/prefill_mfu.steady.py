"""Model step: model FLOPs of the real prompt tokens of the traced prefills (the engine.prefill spans' real) over the device time of their jit__prefill runs times the bf16 peak, %."""
from chipbench import prefill_spans


def read(ctx):
    return prefill_spans.prefill_mfu(ctx)
