"""stage1.merge_rounds_per_tile: top-k merge rounds the int8 stage-1
kernel ran per database tile over the window, from the engine counters
``stage1_merge_rounds`` and ``stage1_tiles``; ``None`` where the program
counts no tiles (a stage 1 that does not report them, or the jnp ref)."""
from readers import delta


def read(ctx):
    try:
        tiles = delta(ctx, "engine", "stage1_tiles")
        rounds = delta(ctx, "engine", "stage1_merge_rounds")
    except KeyError:
        return None
    return rounds / tiles if tiles else None
