"""The control for the sharded route, beside `benchmark/tools/faults.py`'s
faults: one chip's verdicts lost where the route puts the mesh's shares
back together.

    shard_dropped  `parallel/sharded_verify.py` `collect` hands back the last
                   chip's share of every launch as all valid: a bad row there
                   is accepted, so the probe in the light batch's last quarter
                   is applied and the reference refuses the commit it applied

`python3 -m benchmark.tools.faults_sharded` is `benchmark.tools.many`
with that fault beside the others:

    python3 -m benchmark.tools.faults_sharded --workload blocksync-10k-4chip --seconds 8 \\
        --seeds 11,21:shard_dropped
"""

from __future__ import annotations

import sys

from benchmark.tools import faults, many


def shard_dropped():
    from tendermint_tpu.parallel import sharded_verify as sharded

    collect = sharded.collect

    def last_chip_valid(handle):
        bitmap = collect(handle)
        chips = len(handle[0].sharding.device_set)
        bitmap[sharded.chip_rows(len(bitmap), chips) * (chips - 1):] = True
        return bitmap

    return faults._patch(sharded, "collect", last_chip_valid)


def main(argv=None, **kwargs) -> int:
    faults.FAULTS.setdefault(shard_dropped.__name__, shard_dropped)
    return many.main(argv, **kwargs)


if __name__ == "__main__":
    sys.exit(main())
