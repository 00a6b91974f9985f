"""95th percentile, over the requests due in the window, of the time from
when each was due (the open-loop schedule) to its first output token.
Requests never served are counted as failed, not here."""

import harness as H


def read(run):
    return H.percentile(
        ((run.tokens[r][0] - run.due[r]) * 1e3 for r in run.in_window if run.tokens.get(r)), 95
    )
