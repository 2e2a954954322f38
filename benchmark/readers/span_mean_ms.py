"""Mean duration of one of the program's spans over the window, ms."""


def read(obs, span):
    s = obs["spans"].get(span)
    if not s or not s.get("count"):
        return None
    return 1e3 * s["total_s"] / s["count"]
