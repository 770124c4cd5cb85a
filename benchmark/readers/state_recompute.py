"""Share of the hits' prompt tokens that were recomputed behind a state
checkpoint: a model with recurrent layers can use a cached prefix only from
the last boundary at which its state was kept, so the tokens between that
boundary and the end of the cached pages run again.  From the engine's
`debug_stats()["state"]` at the window's two ends: `tokens_recomputed` over
`hit_prompt_tokens`.  None where the program keeps no such state."""


def read(ctx, args):
    before = (ctx.get("stats_before") or {}).get("state") or {}
    after = (ctx.get("stats_after") or {}).get("state") or {}
    if not after.get("enabled") or not before.get("enabled"):
        return None
    hit = after["hit_prompt_tokens"] - before["hit_prompt_tokens"]
    if hit <= 0:
        return None
    return 100.0 * (after["tokens_recomputed"]
                    - before["tokens_recomputed"]) / hit
