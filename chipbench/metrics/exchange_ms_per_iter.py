"""Device time per iteration (of the traced solve) of the halo exchange, in
milliseconds, on the chip where it is largest: the events under the
program's ``ecg.exchange`` scope (halo pack, the collective permutes,
unpack).  Only a cell across chips has them."""

from chipbench import scopes


def read(r):
    return scopes.ms_per_iter(r, scopes.EXCHANGE)
