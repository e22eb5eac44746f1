"""requests_per_group: requests the executor served per coalesced group
of queued read-only requests, over the window: Δ``coalesced_requests`` ÷
Δ``coalesced_groups`` (the store's registry). A program that forms no
group reads nothing."""


def read(ctx):
    groups = ctx.counters.get("coalesced_groups", 0)
    if not groups:
        return None
    return ctx.counters.get("coalesced_requests", 0) / groups
