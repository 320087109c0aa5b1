"""Time of the device operations that carry one of the program's scopes (a
layer, ``cast_in``, a halo, ``scatter``, ``gather``) over the time of all device
operations, in percent. The rest is listed by name and seconds on an earlier
line. 0 says the program names no layer (``benchmark/layer_times.py``)."""

from benchmark import layer_times


def read(ctx):
    lt = layer_times.of(ctx)
    share = None if lt is None else lt.scoped_share()
    return None if share is None else 100.0 * share
