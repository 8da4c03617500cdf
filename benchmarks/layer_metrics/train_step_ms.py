"""One step as the host reads it (median): from a step's dispatch, or
from the end of the step before it where that is later (the loop keeps
`ahead_steps` steps in flight, so it is), to the device->host read of
its loss.  The steady statistic beside `train_tokens_per_s`."""
LAYER, UNIT, SOURCE, MOVES = "models", "ms", "host_clock", "train_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics._common import med

    if ctx.get("plane") != "train":
        return None
    m = med(ctx["train"]["spans"]["step"])
    return None if m is None else m * 1e3
