"""Share of the window's one-shot queries the planner's result cache
answered (``cache_hit`` of each response), in %."""


def read(ctx):
    oneshot = [r for r, _ in ctx.deltas
               if r.op == "query" and not r.req["session"]]
    if not oneshot:
        return None
    return 100.0 * sum(bool(r.body.get("cache_hit")) for r in oneshot) \
        / len(oneshot)
