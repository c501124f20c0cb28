"""Device self time of the Parle sync (Eq. 8c-8d with its statistics,
the ``parle_sync`` scope of ``core/parle.py``) per round, over the
traced window (bench/scopes.py)."""
from bench import scopes


def read(art):
    return scopes.per_round_ms(art, ("parle_sync",))
