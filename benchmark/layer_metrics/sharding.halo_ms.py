"""Device milliseconds per step and chip in the halo exchanges (every scope
``halo.<layer>``), from start to done: a chip that waits for its neighbour
inside one counts."""

from benchmark import layer_times


def read(ctx):
    return layer_times.ms(ctx, lambda scope: scope.startswith(layer_times.HALO))
