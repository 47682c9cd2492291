"""Share of decode lanes in use (%): the mean of each window step's decode
batch over the pool's slots (engine schedule events). Moves tokens_per_s."""


def read(ctx):
    evs = [e for e in ctx.window_events if not e.idle_steps]
    if not evs:
        return None
    return 100.0 * sum(e.decode_batch for e in evs) / (len(evs) * ctx.slots)
