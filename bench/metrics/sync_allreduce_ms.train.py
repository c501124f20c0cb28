"""Device time of the collectives (the Eq. 8d all-reduce of x across the
chips, and the inner steps' loss mean) per round over the traced
training window: on each chip the union of the collectives' intervals,
an asynchronous one from its start to its done, averaged over the chips
(bench/trace.py).  Nothing to read where no collective ran."""


def read(art):
    if art.get("kind") != "train" or not art["trace"]["collectives"]:
        return None
    return 1e3 * art["trace"]["collective_s"] / art["rounds"]
