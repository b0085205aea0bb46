"""How unevenly the router loads the held experts: over the window's
simulations, the sum over every worker's training steps and expert layers of
the largest held expert's tokens, over the same sum of the mean held
expert's tokens (tokens routed to held experts over the number held); 1 is
an even load.  Read from the program's counters (``SimResult.counters``:
``held_load_max``, ``held_tokens``); nothing where the program keeps none."""


def read(run):
    counters = [getattr(r, "counters", None) or {} for r in run.sims]
    if not all("held_tokens" in c and "held_load_max" in c for c in counters):
        return None
    held = run.cfg["model"]["experts_held"]
    tokens = sum(c["held_tokens"] for c in counters)
    if tokens <= 0:
        return None
    return sum(c["held_load_max"] for c in counters) / (tokens / held)
