"""Engine: the device cutover in force (rows): the autotune probe's
draw, or the configuration's pin. The MSM cutover is printed on an
earlier line of the run."""


def read(ctx):
    return float(ctx["cutovers"]["device"])
