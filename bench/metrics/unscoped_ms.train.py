"""Device self time of the ops that no program scope owns, per round
over the traced window: what no layer's metric sees (bench/scopes.py,
whose ``reduce`` also names the longest such ops)."""
from bench import scopes


def read(art):
    return scopes.per_round_ms(art, (scopes.UNSCOPED,))
