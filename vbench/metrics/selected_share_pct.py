"""Sparse attention: of the cached tokens the window's decode ticks could
see, the share their attention read (``stats()`` counters
``attn_selected_tokens`` over ``attn_visible_tokens``). None where the
program keeps no such counters or no tick saw a token."""


def read(run):
    if "attn_visible_tokens" not in run.stats1:
        return None
    seen = run.counter("attn_visible_tokens")
    return 100.0 * run.counter("attn_selected_tokens") / seen if seen else None
