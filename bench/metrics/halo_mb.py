"""Halo bytes a chip sends per epoch, in MB (1e6 bytes): the program's own
count ``halo_bytes_per_epoch`` (``result_extras`` of its mesh lowering:
float32 rows of the padded ``(m, H, width)`` send buffer, every layer,
every device) over the cell's chips."""


def read(ctx):
    sent = (getattr(ctx, "extras", None) or {}).get("halo_bytes_per_epoch")
    return sent / ctx.cell["chips"] / 1e6 if sent else None
