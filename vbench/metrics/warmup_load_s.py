"""Set-up: seconds the engine's warm-up spent in the backend's compile call:
compiling, or loading an executable from the persistent cache."""

def read(run):
    warm = run.stats0.get("warmup_s")
    return warm["compile"] + warm["cache_load"] if warm else None
