"""Images the producers published in the window per second: the advance
of each producer's sequence number in ``lineage.report()`` x the message
batch, summed over producers. Absent where there are no producers."""


def read(obs):
    published = obs["producers"].get("messages_published")
    if published is None:
        return None
    return published * obs["window"]["batch"] / obs["window"]["seconds"]
