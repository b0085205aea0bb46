"""Host time in the first call of each compiled signature (re-trace, load
from the persistent cache, first execution), mean over the window's
simulations (``SimResult.compile_walltime_s``)."""


def read(run):
    return sum(r.compile_walltime_s for r in run.sims) / len(run.sims)
