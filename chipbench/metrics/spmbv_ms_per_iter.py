"""Device time per iteration (of the traced solve) of the SpMBV stage, in
milliseconds, on the chip where it is largest: the events under the
program's ``ecg.spmbv`` scope, which holds the gather of V, the Block-ELL
kernel and, across chips, the halo exchange (``ecg.exchange``)."""

from chipbench import scopes


def read(r):
    return scopes.ms_per_iter(r, scopes.SPMBV, scopes.EXCHANGE)
