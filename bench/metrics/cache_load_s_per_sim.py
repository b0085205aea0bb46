"""Host seconds of the backend compiles in each simulation, which on a warm
persistent compile cache are mostly reads of the cache
(``SimResult.span_s["compile.load"]``, from JAX's compile events), mean
over the window's simulations; nothing where the program keeps no such
count."""


def read(run):
    spans = [getattr(r, "span_s", None) for r in run.sims]
    if not spans or None in spans:
        return None
    return sum(s.get("compile.load", 0.0) for s in spans) / len(spans)
