"""Share of the traced training window in which no operation ran on the
device: 1 - (union of the ops' intervals) / window, averaged over the
chips, from the profiler trace."""


def read(art):
    if art.get("kind") != "train":
        return None
    tr = art["trace"]
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
