"""Device self time of the SSD, forward and backward (the ``ssd`` scope
of ``models/mamba2.py::ssm_block_forward``: on a TPU the fused Pallas op
of ``kernels/ssd_scan.py``, elsewhere the chunked form), per inner step
over the traced window (bench/scopes.py)."""
from bench import scopes


def read(art):
    return scopes.per_step_ms(art, ("ssd",))
