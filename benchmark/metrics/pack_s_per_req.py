"""Host packing: the program's `pack` stage (lane and segment tables,
wire buffers), per request.  None where the program names no such
stage."""

PACK = "pack"


def read(ctx):
    if not ctx["requests"] or PACK not in ctx["stages"]:
        return None
    return ctx["stages"][PACK] / len(ctx["requests"])
