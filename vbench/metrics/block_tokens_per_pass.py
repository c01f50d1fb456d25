"""Block generation: tokens committed over the passes slots took in the
window, denoising and writing passes alike (``stats()`` counters
``block_tokens_committed`` over ``block_slot_passes``, read off every pass's
fetched result). At the static rule with two commits a pass a whole block is
4 tokens in 3 passes, 1.33; a rule that commits more a pass, or a writing
pass folded into the next block's first, moves it. None where the program
keeps no such counters or no slot took a pass."""


def read(run):
    if "block_slot_passes" not in run.stats1:
        return None
    passes = run.counter("block_slot_passes")
    return run.counter("block_tokens_committed") / passes if passes else None
