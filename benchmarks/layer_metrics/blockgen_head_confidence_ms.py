"""Device time a FORWARD spends on the head and the confidence: the
trace's time under the scopes `lm_head` (`[slots x B, vocab]` float32
logits) and `unmask` (their argmax, their largest softmax probability
and the denoising choice) inside the decode programs, over the forwards
they ran.  A commit forward needs neither (`blockgen_commit_forward_
share` says how many are commits); this is what skipping them, or
committing inside the next block's first forward, would save."""
LAYER, UNIT, SOURCE, MOVES = "models", "ms", "device_trace", "serve_tokens_per_s"


def read(ctx):
    from benchmarks.layer_metrics import _block_diffusion_common as c

    if ctx.get("plane") != "serve" or not c.widths(ctx):
        return None
    sc = c.scopes(ctx)
    if not sc or not sc.get("lm_head") or not sc.get("unmask"):
        return None
    return 1e3 * (sc["lm_head"] + sc["unmask"]) / c.forwards(ctx, sc)
