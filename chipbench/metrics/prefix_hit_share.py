"""KV manager (``serving/engine.py`` ``BlockAllocator``): prompt tokens
of the prefills completed in the window that were not fed (served from
shared or retained blocks), over those prompts' tokens."""


def read(ctx):
    c = ctx["counts"]
    if c.prompt_tokens <= 0:
        return None
    return 100.0 * (1.0 - c.prompt_fed / c.prompt_tokens)
