"""Device time per iteration (of the traced solve) of the solve program's
events under no stage scope, in milliseconds, on the chip where it is
largest: the glue between stages and the loops the compiler makes of its
own, which carry no ``op_name``.  With the five stage metrics it adds up
to the program's whole device time on each chip."""

from chipbench import scopes


def read(r):
    return scopes.ms_per_iter(r, scopes.UNSCOPED)
