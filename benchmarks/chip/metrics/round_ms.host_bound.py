"""The round time of a host-bound cell, where it is too unsteady from run
to run to stand as an end-to-end metric: the untraced window's time over
its whole rounds, each ending in ``block_until_ready`` on the params (the
window the traced run takes just before it starts the profiler)."""


def read(ctx):
    return 1e3 * ctx["round_s"]
