"""Window attention: the bytes the window layers' rings hold (every slot's,
whatever its session's length: ``stats()["recurrent_state_bytes"]``) over
what those layers would hold for the same sessions were they paged as the
full layers are (the pool's allocated pages, the mean of the window's two
ends, times ``stats()["ring_bytes_per_position"]`` a token), in percent:
lower is better. None where the program keeps no rings."""


def read(run):
    per_position = run.stats1.get("ring_bytes_per_position")
    page = run.stats1.get("kv_page")
    if not per_position or not page:
        return None
    pages = (run.stats0["kv_pool_used"] + run.stats1["kv_pool_used"]) / 2
    if not pages:
        return None
    return 100.0 * run.stats1["recurrent_state_bytes"] / (
        pages * page * per_position)
