"""95th percentile, over the requests due in the window, of the time
from due to the start of their admission (harness timestamps)."""

import harness as H


def read(run):
    return H.percentile(
        ((run.admitted[r] - run.due[r]) * 1e3 for r in run.in_window if r in run.admitted), 95
    )
