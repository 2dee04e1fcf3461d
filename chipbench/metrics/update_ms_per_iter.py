"""Device time per iteration (of the traced solve) of the update stage, in
milliseconds, on the chip where it is largest: the events under the
program's ``ecg.update`` scope (the fused X/R/Z tail)."""

from chipbench import scopes


def read(r):
    return scopes.ms_per_iter(r, scopes.UPDATE)
