"""Host packing: the program's `scan` stage (history to event stream:
the columnar, object or Python scanners and the crash split), per
request.  None where the program names no such stage."""

SCAN = "scan"


def read(ctx):
    if not ctx["requests"] or SCAN not in ctx["stages"]:
        return None
    return ctx["stages"][SCAN] / len(ctx["requests"])
