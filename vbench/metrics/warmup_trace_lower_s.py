"""Set-up: seconds the engine's warm-up spent tracing and lowering its
programs (``stats()["warmup_s"]``, frozen when warm-up ends), which it
does again on every start, compile cache or not."""

def read(run):
    warm = run.stats0.get("warmup_s")
    return warm["trace_lower"] if warm else None
