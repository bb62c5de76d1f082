"""Device milliseconds per epoch of the compression route, whatever
implements it: the device ops of the program's ``act_compress`` scope
(random projection, blocking, quantize + pack, or the whole fused
matmul-quantize kernel) and ``act_decompress`` scope (unpack +
dequantize and the inverse projection, or the whole fused
dequantize-matmul kernel), put down to the scopes by
``bench/scopes.py``'s rule, over the epochs of the traced window."""
import scopes


def read(ctx):
    return scopes.ms_per_epoch(ctx, "act_compress", "act_decompress")
