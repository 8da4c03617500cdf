"""What the engine-phase readers share: the window's lifecycle records
out of each replica's `request_ring` (one record per finished request,
kept by `LlamaEngine` and shipped in `stats()`), and the programs the
engine names in the device trace.  A program that keeps no such ring or
gives its programs no names (the parent of the PR that added them)
yields nothing here, and the readers then return None."""

# what the trace prints for the engine's four jitted families
DECODE = ("jit_decode_chunk_",)
PREFILL = ("jit_prefill_", "jit_suffix_prefill_", "jit_kv_write_")


def window_records(ctx):
    """Per replica, the last K records with status ok, K = the answers
    the client got from that replica (its requests are the ring's
    newest: warm-up came before them), capped by what the ring kept;
    pooled over the replicas."""
    answers = ctx.get("client", {}).get("per_replica", {})
    out = []
    for r in ctx.get("replicas", []):
        ring = r.get("engine", {}).get("request_ring") or []
        k = int(answers.get(str(r.get("rid")), 0))
        if k:
            out += [q for q in ring[-k:] if q.get("status") == "ok"]
    return out


def program_seconds(trace, prefixes):
    """Device seconds of the executed programs whose printed name
    starts with one of `prefixes`."""
    return sum(v for n, v in (trace.get("module_seconds") or {}).items()
               if n.startswith(prefixes))
