"""Share of the live rows still feeding their prompt (%): the
``prefill_rows`` over the ``active`` counters of the ``serve.admit`` spans
in the traced window."""
from spans import admit_ratio


def read(ctx):
    r = admit_ratio(ctx, "prefill_rows", "active")
    return None if r is None else r * 100.0
