"""Mean sample batches scanned per answered request in the window (the
``batches_used`` every answer carries on the wire): how early the plan's
stop rule ends a query."""
from chipbench import e2e


def read(ctx):
    used = [r["answer"]["batches_used"] for r in ctx["records"]
            if e2e.answered(r)]
    if not used:
        return None
    return sum(used) / len(used)
