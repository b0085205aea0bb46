"""Jitted programs the host launched per simulated round
(``SimResult.host_dispatches``, summed over the window's simulations)."""


def read(run):
    return sum(r.host_dispatches for r in run.sims) / run.rounds
