"""A number the driver or the harness already holds (``ctx.facts[key]``):
counts and host-clock sums that need no reduction of their own."""


def read(ctx, reduced, key: str):
    return ctx.facts.get(key)
