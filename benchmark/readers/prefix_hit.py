"""Share of prompt tokens served from cached pages: the engine's
`hit_pages` counter over the window (debug_stats before and after) times
the page size, over the prompt tokens of the requests sent in it."""


def read(ctx, args):
    if "stats_before" not in ctx:
        return None
    before = ctx["stats_before"]["prefix_cache"]
    after = ctx["stats_after"]["prefix_cache"]
    t0, t1 = ctx["window"]
    prompt = sum(r["prompt_len"] for r in ctx["records"]
                 if t0 <= r["sent"] < t1)
    if not prompt or not after.get("enabled"):
        return None
    pages = after["hit_pages"] - before["hit_pages"]
    return 100.0 * pages * ctx["engine"]["page_size"] / prompt
