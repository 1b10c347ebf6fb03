"""Host prep and transfer: the share of the sharded route's launched
rows that are padding, 100 x (padded - rows) / padded summed over its
`ops.verify_dispatch` spans that ended in the slice. Each chip's share
is padded to a power of two up to 256 rows, then to a multiple of 256
(`parallel/sharded_verify.py` `chip_rows`): 6667 -> 7168 and 10000 ->
10240 rows, where a power of two over the batch gives 8192 and 16384."""

from benchmark import sharded


def read(ctx):
    spans = sharded.dispatches(ctx)
    padded = sum(sp["args"]["padded"] for sp in spans)
    return 100.0 * (padded - sum(sp["args"]["rows"] for sp in spans)) / padded if padded else None
