"""Engine: signatures verified on a device route over all signatures
verified, from `engine_path_rows_total{path}`. A share of rows, not of
a peak: 0 is a reading (the host route took every batch)."""

from benchmark.readers import device_rows, rows_by_path


def read(ctx):
    total = sum(rows_by_path(ctx).values())
    return 100.0 * device_rows(ctx) / total if total else None
