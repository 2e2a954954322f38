"""A sum of the program's counters over the images the stream handed
the consumer in the window (a count: it repeats exactly)."""


def read(obs, counters):
    images = obs["window"]["images_handed"]
    present = [c for c in counters if c in obs["counters"]]
    if not images or not present:
        return None
    return sum(obs["counters"][c] for c in present) / images
