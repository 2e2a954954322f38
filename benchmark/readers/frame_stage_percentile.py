"""A percentile of the time between two of the frame tracer's stages
(monotonic clock, one host) over the traced frames that reached the
later stage inside the window, by the benchmark's own arithmetic."""

import stats


def read(obs, first, last, q):
    w = obs["window"]
    ages = stats.frame_ages_ms(
        obs["frames"], w["t0_mono"], w["t1_mono"], first=first, last=last,
        published_after=w["pull0_mono"],
    )
    return stats.percentile(ages, q) if ages else None
