"""Admission: prompt tokens per request that left the waiting line inside
the window (a count)."""


def read(run):
    lens = [r.prompt_len for r in run.records
            if 0.0 <= r.depart_s < run.seconds]
    return sum(lens) / len(lens) if lens else None
