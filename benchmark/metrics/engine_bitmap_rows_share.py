"""Engine: signatures verified on the per-signature (bitmap) route over
all signatures verified, from `engine_path_rows_total{path}`.
`engine_device_rows_share` counts both device routes; this is the part
of it that is not the MSM. A share of rows: 0 is a reading."""

from benchmark.readers import rows_by_path
from benchmark.routes import bitmap_rows


def read(ctx):
    total = sum(rows_by_path(ctx).values())
    return 100.0 * bitmap_rows(ctx) / total if total else None
