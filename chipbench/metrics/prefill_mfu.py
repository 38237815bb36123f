"""Model step: the model operations of the window's steps that ran prefill
(their unpadded prompt tokens and their decode batch) over those steps'
summed host time times the chip's bf16 peak, in percent."""
from chipbench.work import dense


def read(run):
    steps = [s for s in run.host_steps if s.segments]
    seconds = sum(s.end - s.start for s in steps)
    if not seconds:
        return None
    flops = sum(dense.prefill_flops(run.conf, s.segments)
                + (dense.decode_step_flops(run.conf, s.decode_ctx)
                   if s.decode_ctx else 0)
                for s in steps)
    return 100.0 * flops / (seconds * run.peaks["flops_bf16_per_s"])
