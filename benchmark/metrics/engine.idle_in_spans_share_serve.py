"""Reader of the per-layer metric `engine.idle_in_spans_share_serve`: idle seconds under the program's own `generation/` and `executor/` spans, over all idle seconds but the pauses under 50 us (%)."""

import span_math


def read(x):
    return span_math.idle_in_spans_share(x["trace"]["idle_gaps"],
                                         ("generation/", "executor/"))
