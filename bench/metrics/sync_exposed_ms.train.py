"""The part of the collectives' device time (``sync_allreduce_ms.train``)
in which no other op ran on the same chip, per round over the traced
training window, averaged over the chips (bench/trace.py): the sync's
exposed time; the rest of it is hidden under compute.  Nothing to read
where no collective ran."""


def read(art):
    if art.get("kind") != "train" or not art["trace"]["collectives"]:
        return None
    return 1e3 * art["trace"]["collective_exposed_s"] / art["rounds"]
