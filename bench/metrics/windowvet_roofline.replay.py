"""Share of its HBM roofline that the vet launch reaches, %.

The least time the windows need is the bytes they need (their records read
once, their results written once; ``bench/roofline.py``) over the chip's
HBM bandwidth; the time taken is the device time of every launch of the
fused vet program (its gather of the windows and its kernel) in the
window.  Nothing is returned where the trace holds no such launch.
"""

from bench import roofline

PROGRAM = "fused_window_vet"


def read(ctx):
    took = ctx.trace.module_s(PROGRAM)
    if took <= 0 or not ctx.vetted_lengths.size or ctx.peaks is None:
        return None
    need = roofline.hbm_seconds(
        roofline.vet_launch_bytes(ctx.vetted_lengths), ctx.peaks)
    return 100.0 * need / took
