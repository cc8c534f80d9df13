"""stage1.map_uploads_in_window: engine calls in the window that built
the int8 flat stage 1's device-resident candidate id map in full, from
the engine counter ``stage1_map_uploads``; ``None`` where the program
does not count them."""
from readers import delta


def read(ctx):
    try:
        return delta(ctx, "engine", "stage1_map_uploads")
    except KeyError:
        return None
