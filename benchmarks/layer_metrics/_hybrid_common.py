"""What the hybrid model's readers share: ticks that carry the counters
of BOTH cache kinds and of the experts, and the live rows of a decode
step.  The scopes' device time comes through `_moe_common.scopes` as it
stands.  A program without the counters (the parent of the PR that
added them) yields nothing here, and the readers then return None."""


def hybrid_ticks(ctx):
    """Tick records that dispatched a chunk over live state rows and
    harvested a chunk's expert counters."""
    return [t for r in ctx.get("replicas", []) for t in r.get("tick_ring", [])
            if t.get("state_rows_live") and t.get("row_steps")
            and t.get("experts_total")]


def live_rows(ctx, ticks):
    """Rows that owed a token, a decode step: a tick's live row-steps
    over its chunk's steps, averaged."""
    from benchmarks.layer_metrics._common import mean

    return mean(t["row_steps_live"] / ctx["config"]["engine"]["chunk"]
                for t in ticks)
