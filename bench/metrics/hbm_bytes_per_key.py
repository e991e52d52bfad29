"""Device bytes in use at the end of the window, summed over the cell's
chips, per live key (the model's count): the index's space cost.  The
client holds nothing on the device then."""


def read(r):
    return r.bytes_in_use / r.live_keys
