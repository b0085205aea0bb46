"""Host time of Alg. 2 and host aggregation per round
(``SimResult.server_overhead_s``, summed over the window's simulations)."""


def read(run):
    return 1e3 * sum(r.server_overhead_s for r in run.sims) / run.rounds
