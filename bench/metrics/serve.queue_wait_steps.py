"""Mean queue wait of the requests admitted in the traced window, in
engine steps: the ``queued_steps`` over the ``admitted`` counters of the
``serve.admit`` spans."""
from spans import admit_ratio


def read(ctx):
    return admit_ratio(ctx, "queued_steps", "admitted")
