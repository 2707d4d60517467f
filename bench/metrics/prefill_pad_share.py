"""Share of the prefill token rows dispatched in the window that were
padding: 1 - true prompt tokens / padded rows x bucket, in percent."""


def read(ctx):
    padded = ctx.counters["prefill_padded_tokens"]
    if not padded:
        return None
    return 100.0 * (1.0 - ctx.counters["prefill_tokens_total"] / padded)
