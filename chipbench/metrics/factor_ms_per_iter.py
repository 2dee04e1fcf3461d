"""Device time per iteration (of the traced solve) of the factor stage, in
milliseconds, on the chip where it is largest: the events under the
program's ``ecg.factor`` scope (the t x t Cholesky or pivoted factor and the
triangular solves)."""

from chipbench import scopes


def read(r):
    return scopes.ms_per_iter(r, scopes.FACTOR)
