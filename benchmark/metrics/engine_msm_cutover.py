"""Engine: the MSM cutover in force (rows): a device batch of at least
this many rows takes the two-phase MSM, a smaller one the per-signature
kernel. The autotune probe's draw, or the configuration's pin;
`engine_device_cutover` reads the other threshold."""


def read(ctx):
    return float(ctx["cutovers"]["msm"])
