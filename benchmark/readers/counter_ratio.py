"""A ratio of the program's counters in the window: the sum of the
``numerator`` counters over the sum of the ``denominator`` counters (a
ratio of counts: it repeats exactly). ``None`` where either side has no
counter the program booked, or the denominator sums to 0."""


def read(obs, numerator, denominator):
    counters = obs["counters"]
    if not any(c in counters for c in numerator):
        return None
    below = sum(counters.get(c, 0) for c in denominator)
    if not below:
        return None
    return sum(counters.get(c, 0) for c in numerator) / below
