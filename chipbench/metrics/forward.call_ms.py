"""Mean host time of one call of the compiled forward in the window, in
ms: the program's ``plan.call`` spans (retrace guard and jit dispatch, up
to the enqueue; the wait for the device is not in them), those that
started after set-up."""

from chipbench.registry import registry


def read(r):
    reg = registry()
    if reg is None or r.ctx.setup_end is None:
        return None
    calls = reg.spans("plan.call", since=r.ctx.setup_end)
    if not calls:
        return None
    return 1e3 * sum(s.seconds for s in calls) / len(calls)
