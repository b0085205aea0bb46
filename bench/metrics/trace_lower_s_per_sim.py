"""Host seconds JAX spent tracing the program's functions to jaxprs and
lowering them to MLIR in each simulation (``SimResult.span_s
["compile.trace_lower"]``, from JAX's compile events), mean over the
window's simulations; nothing where the program keeps no such count."""


def read(run):
    spans = [getattr(r, "span_s", None) for r in run.sims]
    if not spans or None in spans:
        return None
    return sum(s.get("compile.trace_lower", 0.0) for s in spans) / len(spans)
