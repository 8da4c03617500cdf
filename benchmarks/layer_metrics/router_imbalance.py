"""Largest over mean number of requests a replica answered in the run:
1.0 is a perfectly even router.  Only where there are replicas."""
LAYER, UNIT, SOURCE, MOVES = "serve plane", "ratio", "program_counter", "request_p95_ms"


def read(ctx):
    served = [r["served"] for r in ctx.get("replicas", [])]
    if len(served) < 2 or not sum(served):
        return None
    return max(served) / (sum(served) / len(served))
