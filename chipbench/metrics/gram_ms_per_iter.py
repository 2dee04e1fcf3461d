"""Device time per iteration (of the traced solve) of the Gram stage, in
milliseconds, on the chip where it is largest: the events under the
program's ``ecg.gram`` scope (the local products, the fused Gram kernel and
their all-reduces)."""

from chipbench import scopes


def read(r):
    return scopes.ms_per_iter(r, scopes.GRAM)
