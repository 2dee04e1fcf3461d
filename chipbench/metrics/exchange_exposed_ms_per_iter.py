"""The exposed part of the halo exchange, per iteration of the traced solve,
in milliseconds, on the chip where it is largest: the measure of the union
of the ``ecg.exchange`` events' intervals that no other event on that chip
covers.  Only a cell across chips has it."""

from chipbench import scopes


def read(r):
    return scopes.exposed_ms_per_iter(r)
