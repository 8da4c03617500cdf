"""The whole step's share of the chip's peak in a closed serve cell: the
operations the ANSWERED requests needed (`benchmarks/needed_flops/<the
configuration's plane>.py`: what the mathematics needs, from the
configuration's and the mix's files and the client's record of each
request), credited over the read window exactly as `serve_tokens_per_s`
credits their tokens (`loadgen.credited`), over chips x the published
bf16 peak.  It reads no trace, no counter and nothing a replica says,
so no kernel taken out, renamed or fused can silence it; None only by
symmetry with `train_mfu`: not the serve plane, an open-loop mix (no
credit is kept), no `peaks` (a rehearsal), or no request answered."""
LAYER, UNIT, SOURCE, MOVES = "models", "%", "host_clock", "serve_tokens_per_s"


def read(ctx):
    from benchmarks import manifest

    credited = (ctx.get("client") or {}).get("credited")
    if ctx.get("plane") != "serve" or "peaks" not in ctx or not credited:
        return None
    cfg, mix = ctx["config"], ctx["traffic"]
    count = manifest.needed_flops(cfg["plane"]).request_flops
    done = sum(r["share"] * count(cfg, mix, r["prompt_len"], r["got"],
                                  r["fields"])
               for r in credited["requests"])
    if done <= 0.0:   # nobody answered, or every answer ended before S/5
        return None
    rate = done / (credited["to_s"] - credited["from_s"])
    chips = int(ctx["cell"]["chips"])
    return 100.0 * rate / (chips * ctx["peaks"]["bf16_flops_per_s"])
