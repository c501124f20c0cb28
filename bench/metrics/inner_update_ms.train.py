"""Device self time of the Parle inner update (Eq. 8a-8b, the
``parle_inner`` scope of ``core/parle.py::inner_step``) per inner step:
the Pallas inner kernel or the XLA update, with the layout copies XLA
puts round the kernel calls, summed over the traced window and divided
by its rounds x L (bench/scopes.py)."""
from bench import scopes


def read(art):
    return scopes.per_step_ms(art, ("parle_inner",))
