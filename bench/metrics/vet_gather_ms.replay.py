"""Device time of the fused vet program less its kernel, named ``windowvet``: the gather of the windows out of the arena and the copies around it, ms per tick.

Nothing where the trace holds no kernel op of that name, as from a program
whose kernel is not named: there the kernel and the gather cannot be told
apart.
"""

PROGRAM = "fused_window_vet"
KERNEL = "windowvet"


def _is_kernel(key: str) -> bool:
    """Whether the op ``<program>/<op>`` is the named kernel: the op is
    ``%windowvet`` or ``%windowvet.<n>``."""
    program, _, op = key.partition("/")
    op = op.lstrip("%")
    head, _, tail = op.rpartition(".")
    return PROGRAM in program and (head if tail.isdigit() else op) == KERNEL


def read(ctx):
    took = ctx.trace.module_s(PROGRAM)
    kernel = sum(v for k, v in ctx.trace.ops.items() if _is_kernel(k))
    if took <= 0 or kernel <= 0:
        return None
    return 1e3 * (took - kernel) / ctx.ticks
