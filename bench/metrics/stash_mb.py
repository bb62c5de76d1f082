"""Saved-for-backward bytes of one epoch step, in MB (1e6 bytes), as the
program's own ledger ``repro.graph.train.activation_memory_report``
counts them: compressed codes, block statistics and 1-bit ReLU masks
where a layer is compressed, float32 inputs where it is not."""


def read(ctx):
    from repro.graph.train import activation_memory_report

    rep = activation_memory_report(ctx.graph, ctx.gnn_cfg)
    return rep.get("compressed_bytes", rep["fp32_bytes"]) / 1e6
