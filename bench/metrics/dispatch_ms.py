"""Mean host time inside one timed call until it returned an unblocked
result: plan lookup, dispatch wrapper and enqueue, on the bench's own
clock."""


def read(ctx):
    calls = ctx.window.extra.get("dispatch_s")
    if not calls:
        return None
    return 1e3 * sum(calls) / len(calls)
