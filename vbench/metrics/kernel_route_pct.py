"""Paged pool and attention route: share of the window's decode ticks the
engine's router sent to the fused paged kernel (the rest gathered)."""


def read(run):
    k = run.counter("paged_attn_kernel_ticks")
    g = run.counter("paged_attn_gather_ticks")
    return 100.0 * k / (k + g) if k + g else None
