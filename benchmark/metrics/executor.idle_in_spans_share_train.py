"""Reader of the per-layer metric `executor.idle_in_spans_share_train`: idle seconds under the program's own `executor/` spans, over all idle seconds but the pauses under 50 us (%)."""

import span_math


def read(x):
    return span_math.idle_in_spans_share(x["trace"]["idle_gaps"],
                                         ("executor/",))
