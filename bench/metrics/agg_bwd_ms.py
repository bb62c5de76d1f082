"""Device milliseconds per epoch of the backward aggregation: the device
ops the program's ``agg_bwd`` scope holds (the transposed ``Â`` product
of the input gradient in ``repro.engine.forward``), put down to the
scope by ``bench/scopes.py``'s rule, summed over the traced window and
divided by the epochs completed in it."""
import scopes


def read(ctx):
    return scopes.ms_per_epoch(ctx, "agg_bwd")
