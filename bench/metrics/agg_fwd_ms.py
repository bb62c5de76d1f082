"""Device milliseconds per epoch of the forward aggregation: the device
ops the program's ``agg_fwd`` scope holds (``repro.engine.forward``'s
``Â·h`` gather, edge-weight multiply and segment-sum), put down to the
scope by ``bench/scopes.py``'s rule, summed over the traced window and
divided by the epochs completed in it."""
import scopes


def read(ctx):
    return scopes.ms_per_epoch(ctx, "agg_fwd")
