"""Kernels: of the rows of the window's launches through a model that holds
experts (steps, admissions, chunks), the share in launches whose shape had
each held expert multiply the rows routed to it alone rather than all rows
(``stats()`` counters ``expert_rows_grouped`` over ``expert_rows``). The
rule has no threshold of size that the cells' launches cross (PERF.md
section 3), so in the three cells it reads 100 and is an alarm: under 100
means a launch the kernels refused (``vtpu.ops.grouped_ffn.takes``: more
rows than they were compiled and timed at, such as several prompts admitted
in one launch, or widths that leave VMEM) ran every held expert over all
rows. None where the program keeps no such counters or no such launch was
dispatched."""


def read(run):
    if "expert_rows" not in run.stats1:
        return None
    rows = run.counter("expert_rows")
    return 100.0 * run.counter("expert_rows_grouped") / rows if rows else None
