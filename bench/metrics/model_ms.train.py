"""Device self time of the model's loss, forward and backward of the n
replicas (the ``model`` scope of ``models/model.py`` and the scopes its
configuration's architecture module lists inside it), per inner step over
the traced window (bench/scopes.py)."""
from bench import scopes


def read(art):
    if art.get("kind") != "train":
        return None
    return scopes.per_step_ms(art, scopes.in_model(art["config"]))
