"""``scope_unscoped_share``: the share, in percent, of the traced window's
device time that the scope table places nowhere: ``unscoped`` (an
instruction of the round program under no scope of the vocabulary) plus
``unjoined`` (an event of another program: the keys and staging between
rounds) over all device time, joined or not (``harness/scope_time.py``).
Small means the per-scope metrics account for the round. Source: device
trace. Moves ``round_s``. Reads nothing without the table or a trace."""

from harness import scope_time


def read(ctx):
    seconds = scope_time.by_scope(ctx)
    total = sum(seconds.values()) if seconds else 0.0
    if total <= 0:
        return None
    return 100.0 * (seconds[scope_time.UNSCOPED]
                    + seconds[scope_time.UNJOINED]) / total
