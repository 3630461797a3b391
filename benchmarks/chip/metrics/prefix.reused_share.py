"""Share of the prompt tokens admitted in the window that the prefix
cache served from pages already in the arena (engine counters)."""


def read(ctx):
    total = ctx.stats["prefill_tokens"]
    if not total:
        return None
    return 100.0 * ctx.stats["prefix_tokens_reused"] / total
