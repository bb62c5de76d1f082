"""Device milliseconds per epoch of the 1-bit ReLU mask: the device ops
of the program's ``relu_mask`` scope (the sign-bit pack in the forward,
the unpack and its multiply in the backward, with the loops XLA builds
to relayout the packed words), put down to the scope by
``bench/scopes.py``'s rule, over the epochs of the traced window."""
import scopes


def read(ctx):
    return scopes.ms_per_epoch(ctx, "relu_mask")
