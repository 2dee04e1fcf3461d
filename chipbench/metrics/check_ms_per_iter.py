"""Device time per iteration (of the traced solve) of the check stage, in
milliseconds, on the chip where it is largest: the events under the
program's ``ecg.check`` scope (the residual norm and its all-reduce, the
loop condition and the breakdown guard's select over the carry)."""

from chipbench import scopes


def read(r):
    return scopes.ms_per_iter(r, scopes.CHECK)
